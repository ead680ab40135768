"""The Mamba-1 scan's two kernels (Pallas TPU): ``ops.mamba1.ssm1_step``
and ``ops.mamba1.selective_scan_chunk`` are their definitions and the
paths the CPU takes.

Both rest on one layout, ``ops/mamba1.py`` says why: a layer's state is
``[N, D]`` float32 with N = 16 on the sublanes (two tiles) and the D
channels along the lanes; ``dt`` and ``u = dt x`` are rows along the
lanes, ``B_t`` and ``C_t`` columns broadcast along them, ``exp(dt A)``
runs on the transcendental unit for every value of the state (the decay
is a value's own: no scalar a head, no matmul form), and the contraction
with ``C_t`` is a sum of 16 rows: no matrix unit, no cross-lane
reduction.

``mamba1_decode_step``: one position a lane, the lanes' states
``[lanes, N, D]`` (the layer's lane-state array: 327,680 B a lane at 16 x
5,120) updated IN PLACE (``input_output_aliases``).  A state is read
once and written once and takes a handful of operations a value, so the
kernel is meant to run at the pace of its copies.  At 0.8 us of copies
a lane a grid step a lane would be mostly the step's own overhead, so
the grid walks BLOCKS of ``_LANES_A_BLOCK`` lanes: the blocks that hold
a running lane first (their numbers arrive as scalar prefetch), the
blocks that hold none behind them repeating the last one's index, which
copies nothing and runs nothing; an idle lane beside a running one is
copied back as it came.  A block's lanes are also the eight rows of its
``dt``, ``u`` and ``y`` tiles, so those arrays go in and out as ``[lanes,
D]`` lies.  The next block's states are fetched and the last one's
written behind this one's arithmetic (the grid's own double buffering).

``mamba1_chunk_scan``: a prompt chunk's positions from a lane's state.
A grid program owns a tile of ``cols`` (1,024) channels, whose ``[N,
cols]`` state stays in VMEM (in registers within a block) while the
chunk's positions are walked in order, ``_POSITIONS_A_BLOCK`` rows of ``dt`` and
``u`` a grid step, eight positions a loop step: for each position the
decay ``exp(dt_t A)``, the update and the sum over N.  Nothing of
``[T, D, N]`` exists outside the registers (2,048 x 5,120 x 16 float32
would be 671 MB a layer in HBM); what a position reads and writes in HBM
is its rows of ``dt``, ``u`` and ``y``.  ``B`` and ``C`` arrive as
``[T / 8, N, 8]``: a loop step's eight columns side by side, so that a
position's column is a static slice.  Pads (positions past ``n_valid``)
have ``dt = 0``: no decay, no update.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES_A_BLOCK = 8        # lanes a grid step of the decode kernel: 2.6 MB of states in, 2.6 MB out
_POSITIONS_A_BLOCK = 256  # positions a grid step of the chunk kernel
_STEP_COLS = 1024         # channels the decode kernel's arithmetic takes at a time (16 registers of state)
_CHUNK_COLS = 1024        # channels a grid program of the chunk kernel owns (16 registers of state)
# two blocks of states in and two out (10.5 MB) beside the rows: past the 16 MB a kernel gets unasked
_VMEM_BYTES = 48 * 1024 * 1024


def _lanes_a_block(lanes: int) -> int:
    """A block's lanes are the rows of its ``dt``, ``u`` and ``y``
    tiles: whole sublane tiles of float32, or every lane there is."""
    return _LANES_A_BLOCK if lanes % _LANES_A_BLOCK == 0 else lanes


def _cols(D: int, most: int) -> int:
    """The widest whole-lane-tile divisor of D that is at most `most`."""
    return max((c for c in range(128, most + 1, 128) if D % c == 0), default=0)


def step_kernel_takes(lanes, N, D) -> bool:
    """The shapes the decode kernel's tiling can take: a state whole
    (8, 128) float32 tiles, the lanes whole blocks or fewer than one."""
    return N % 8 == 0 and _cols(D, _STEP_COLS) > 0 and (lanes % _LANES_A_BLOCK == 0 or lanes < _LANES_A_BLOCK)


def chunk_kernel_takes(T, N, D) -> bool:
    """The shapes the chunk kernel's tiling can take: a state whole
    (8, 128) float32 tiles, the positions whole loop steps of eight and
    whole grid steps (or fewer than one)."""
    return (N % 8 == 0 and _cols(D, _CHUNK_COLS) > 0 and T % 8 == 0
            and (T <= _POSITIONS_A_BLOCK or T % _POSITIONS_A_BLOCK == 0))


# ----------------------------------------------------------------------
# decode: one position a lane, the states in place
# ----------------------------------------------------------------------
def _step_kernel(order_ref, n_ref, act_ref,                      # scalar prefetch (SMEM)
                 a_ref, dt_ref, u_ref, b_ref, c_ref, s_ref,      # inputs
                 y_ref, so_ref,                                  # outputs (so_ref is s_ref's buffer)
                 *, cols):
    i = pl.program_id(0)
    lanes_a_block, _, D = s_ref.shape

    @pl.when(i < n_ref[0])
    def _():
        first = order_ref[i] * lanes_a_block
        for l in range(lanes_a_block):  # a lane is a row of the block's [lanes, D] tiles: a static slice
            runs = act_ref[first + l] > 0

            @pl.when(runs)
            def _(l=l):
                b, c = b_ref[l], c_ref[l]                              # [N, 1]
                for c0 in range(0, D, cols):
                    at = slice(c0, c0 + cols)
                    h = jnp.exp(dt_ref[l:l + 1, at] * a_ref[:, at]) * s_ref[l, :, at] + b * u_ref[l:l + 1, at]
                    so_ref[l, :, at] = h
                    y_ref[l:l + 1, at] = (h * c).sum(axis=0, keepdims=True)

            @pl.when(jnp.logical_not(runs))
            def _(l=l):
                so_ref[l] = s_ref[l]
                y_ref[l:l + 1, :] = jnp.zeros((1, D), jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mamba1_decode_step(x, dt, A, B, C, D, state, active, *, interpret=False):
    """The arguments and results of ``ops.mamba1.ssm1_step`` (active [L]
    bool given): x [L, D], dt [L, D] float32, A [N, D], B, C [L, N], D
    [D], state [L, N, D] float32 -> (y [L, D] in x's dtype, which means
    nothing for a lane that is not active; the new states, in the buffer
    the old ones came in where the caller donates it).
    ``interpret=True`` runs the same kernel on the CPU for tests."""
    L, N, Dn = state.shape
    per = _lanes_a_block(L)
    blocks = L // per
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    # the blocks that hold a running lane first, in order; behind them the last of them again
    held = active.reshape(blocks, per).any(axis=1)
    n = held.sum(dtype=jnp.int32)
    order = jnp.argsort(~held, stable=True).astype(jnp.int32)
    order = jnp.where(jnp.arange(blocks) < n, order, order[jnp.maximum(n - 1, 0)])

    def a_block(*tail):
        return lambda i, order, n, act: (order[i], *tail)

    def column(v):  # [L, N] -> [L, N, 1]: along the sublanes, as the state's N lies
        return v.astype(jnp.float32)[:, :, None]

    y, state = pl.pallas_call(
        functools.partial(_step_kernel, cols=_cols(Dn, _STEP_COLS)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(blocks,),
            in_specs=[
                pl.BlockSpec((N, Dn), lambda i, *_: (0, 0)),     # A, fetched once
                pl.BlockSpec((per, Dn), a_block(0)),           # rows as they lie: [L, 1, D] would be a relayout
                pl.BlockSpec((per, Dn), a_block(0)),           # of 42 MB a row array, three a layer (PR 50)
                pl.BlockSpec((per, N, 1), a_block(0, 0)),
                pl.BlockSpec((per, N, 1), a_block(0, 0)),
                pl.BlockSpec((per, N, Dn), a_block(0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((per, Dn), a_block(0)),
                pl.BlockSpec((per, N, Dn), a_block(0, 0)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((L, Dn), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={8: 1},  # counting the three prefetched: the states
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_BYTES),
        name="mamba1_decode_step",
        interpret=interpret,
    )(order, n.reshape(1), active.astype(jnp.int32),
      A.astype(jnp.float32), dtf, dtf * xf, column(B), column(C), state)
    y = jnp.where(active[:, None], y, 0.0)  # a block that holds no running lane was never written
    return (y + D.astype(jnp.float32) * xf).astype(x.dtype), state


# ----------------------------------------------------------------------
# prefill: a chunk's positions, the state resident
# ----------------------------------------------------------------------
def _chunk_kernel(a_ref, dt_ref, u_ref, b_ref, c_ref, s_ref,     # inputs
                  y_ref, so_ref,                                  # outputs
                  h_ref):                                         # scratch: the tile's state between grid steps
    t = pl.program_id(1)
    positions, cols = dt_ref.shape

    @pl.when(t == 0)
    def _():
        h_ref[...] = s_ref[...]

    A = a_ref[...]
    row_of = jax.lax.broadcasted_iota(jnp.int32, (8, cols), 0)

    def eight(i, h):
        at = pl.ds(pl.multiple_of(i * 8, 8), 8)
        dt, u = dt_ref[at, :], u_ref[at, :]                           # [8, cols]
        b, c = b_ref[i], c_ref[i]                                     # [N, 8]
        y = jnp.zeros((8, cols), jnp.float32)
        for j in range(8):
            h = jnp.exp(dt[j:j + 1] * A) * h + b[:, j:j + 1] * u[j:j + 1]
            y = jnp.where(row_of == j, (h * c[:, j:j + 1]).sum(axis=0, keepdims=True), y)
        y_ref[at, :] = y
        return h

    h = jax.lax.fori_loop(0, positions // 8, eight, h_ref[...])
    h_ref[...] = h

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        so_ref[...] = h


@functools.partial(jax.jit, static_argnames=("interpret",))
def mamba1_chunk_scan(x, dt, A, B, C, D, state, n_valid, *, interpret=False):
    """The arguments and results of ``ops.mamba1.selective_scan_chunk``:
    x [T, D], dt [T, D] float32, A [N, D], B, C [T, N], D [D], state [N,
    D] float32, n_valid a traced scalar -> (y [T, D] in x's dtype, the
    state after position ``n_valid - 1``).  ``interpret=True`` runs the
    same kernel on the CPU for tests."""
    T, Dn = x.shape
    N = state.shape[0]
    cols = _cols(Dn, _CHUNK_COLS)
    positions = min(T, _POSITIONS_A_BLOCK)
    xf = x.astype(jnp.float32)
    dtf = jnp.where((jnp.arange(T) < n_valid)[:, None], dt.astype(jnp.float32), 0.0)

    def eights(v):  # [T, N] -> [T / 8, N, 8]: a loop step's eight columns side by side
        return v.astype(jnp.float32).reshape(T // 8, 8, N).transpose(0, 2, 1)

    def a_tile(c, t):
        return (0, c)

    def rows(c, t):
        return (t, c)

    def columns(c, t):
        return (t, 0, 0)

    y, state = pl.pallas_call(
        _chunk_kernel,
        grid=(Dn // cols, T // positions),
        in_specs=[
            pl.BlockSpec((N, cols), a_tile),
            pl.BlockSpec((positions, cols), rows),
            pl.BlockSpec((positions, cols), rows),
            pl.BlockSpec((positions // 8, N, 8), columns),
            pl.BlockSpec((positions // 8, N, 8), columns),
            pl.BlockSpec((N, cols), a_tile),
        ],
        out_specs=[
            pl.BlockSpec((positions, cols), rows),
            pl.BlockSpec((N, cols), a_tile),
        ],
        out_shape=[jax.ShapeDtypeStruct((T, Dn), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((N, cols), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        name="mamba1_chunk_scan",
        interpret=interpret,
    )(A.astype(jnp.float32), dtf, dtf * xf, eights(B), eights(C), state.astype(jnp.float32))
    return (y + D.astype(jnp.float32) * xf).astype(x.dtype), state

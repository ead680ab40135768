"""One decode step of a Mamba-2 layer's scan over the lanes' states,
updated IN PLACE (Pallas TPU): ``ops.mamba2.ssm_step`` is its
definition and the path the CPU takes.

The states are the layer's lane-state array ``[lanes, H, P, N]`` float32
(2 MB a lane at 64 heads of 64 x 128): a decode step reads and writes
every running lane's, and that is all it does, a handful of operations
a value.  So the kernel is one program a layer whose grid walks the
RUNNING lanes (their numbers arrive as scalar prefetch, the idle lanes
behind them repeat the last one's block index, which copies nothing and
runs nothing): a step copies the lane's state to VMEM, and for each
head applies the decay, adds the rank-one update ``(dt x) B^T`` and
writes the new state back INTO THE BUFFER IT CAME FROM
(``input_output_aliases``): no second pool of states, no copy of one.
The next lane's state is fetched and the last one's written behind this
one's arithmetic (the grid's own double buffering), and the kernel is
meant to run at the pace of those copies.  An idle lane's state is left
as it is.

Layout: a head's state is ``[P, N]`` with N the 128 lanes of a vector
register; ``B`` and ``C`` are rows along it, and the token's ``dt x``,
which varies along P, arrives transposed ``[P, H]`` so that a head's
column broadcasts along the registers' lanes.  The decay is one scalar a
head, read from SMEM.

The contraction with ``C`` runs over N, the registers' lanes.  Summed
on the vector units it is a cross-lane reduction for every register of
state (eight a head, each for eight useful numbers, and a select to put
them into ``y``), and that, not the copies, set the kernel's pace; a
packed butterfly of lane rotations costs more still, because a rotation
shares its unit with the broadcast of ``dt x``.  So the matrix unit,
which this kernel otherwise leaves idle, takes it: the new states of as
many heads of one group as fill 128 rows are the STATIONARY operand
``[rows, N]`` and ``C``'s row is multiplied against them (contracting N
on both, no transpose of a state).  The other operand has eight rows
whatever it holds, so row j holds ``C`` on the j-th eighth of N and
zeros elsewhere: eight short sums, added up after, lie closer to the
true sum than one long one.  Their sum is those heads' ``y``, P values
a head side by side along the lanes: ``y`` leaves as ``[H / t, t P]``,
which is ``[H, P]`` as it lies.  Both operands are float32 and the
product is ``Precision.HIGHEST``, the multi-pass form whose error is
float32's: the state's values are what ``ssm_step`` computes, ``y`` the
same 128 products summed in another order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the states of two lanes in and two out beside the temporaries: past the
# 16 MB a kernel gets unasked
_VMEM_BYTES = 48 * 1024 * 1024


def kernel_takes(H, P, N, G) -> bool:
    """The shapes the kernel's tiling can take: a head's state whole
    (8, 128) float32 tiles, the heads whole groups."""
    return N % 128 == 0 and P % 8 == 0 and H % G == 0


def _heads_a_tile(P, heads_a_group) -> int:
    """The heads whose new states are one stationary operand: of one
    group (they share a C), and no more than fill the matrix unit's 128
    rows."""
    return max(t for t in range(1, heads_a_group + 1) if heads_a_group % t == 0 and t * P <= max(128, P))


def _kernel(order_ref, n_ref,                       # scalar prefetch (SMEM)
            a_ref, u_ref, b_ref, c_ref, s_ref,      # inputs
            y_ref, so_ref,                          # outputs (so_ref is s_ref's buffer)
            *, heads_a_group, heads_a_tile):
    i = pl.program_id(0)
    H, _, N = s_ref.shape[1:]

    @pl.when(i < n_ref[0])
    def _():
        lane = order_ref[i]
        u = u_ref[0]                                 # [P, H]: dt * x, a head a column
        # row j of the product's eight keeps the j-th eighth of N: eight short sums, then theirs
        eighth = (jax.lax.broadcasted_iota(jnp.int32, (8, N), 1) // (N // 8)
                  == jax.lax.broadcasted_iota(jnp.int32, (8, N), 0))
        for t, h0 in enumerate(range(0, H, heads_a_tile)):
            g = h0 // heads_a_group
            b = b_ref[0, g:g + 1, :]
            tile = []
            for h in range(h0, h0 + heads_a_tile):
                new = a_ref[lane, h] * s_ref[0, h] + u[:, h:h + 1] * b   # [P, N]
                so_ref[0, h] = new
                tile.append(new)
            rows = jax.lax.dot_general(                                   # [8, heads_a_tile * P]
                jnp.where(eighth, c_ref[0, g:g + 1, :], 0.0), jnp.concatenate(tile, axis=0),
                (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
            y_ref[0, t:t + 1, :] = rows.sum(axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mamba2_decode_step(x, dt, A, B, C, D, state, active, *, interpret=False):
    """The arguments and results of ``ops.mamba2.ssm_step`` (active [B]
    bool given): x [B, H, P], dt [B, H] float32, A, D [H], B, C [B, G,
    N], state [B, H, P, N] float32 -> (y [B, H, P] in x's dtype, which
    means nothing for a lane that is not active; the new states, in the
    buffer the old ones came in where the caller donates it).
    ``interpret=True`` runs the same kernel on the CPU for tests."""
    Bn, H, P = x.shape
    G, N = B.shape[1:]
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    tile = _heads_a_tile(P, H // G)
    decay = jnp.exp(dtf * A.astype(jnp.float32))                   # [B, H]
    u = (dtf[..., None] * xf).transpose(0, 2, 1)                    # [B, P, H]
    # the running lanes first, in order; behind them the last of them again
    n = active.sum(dtype=jnp.int32)
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)
    order = jnp.where(jnp.arange(Bn) < n, order, order[jnp.maximum(n - 1, 0)])

    def a_lane(*tail):
        return lambda i, order, n: (order[i], *tail)

    y, state = pl.pallas_call(
        functools.partial(_kernel, heads_a_group=H // G, heads_a_tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(Bn,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),          # every lane's decays, a scalar a head
                pl.BlockSpec((1, P, H), a_lane(0, 0)),
                pl.BlockSpec((1, G, N), a_lane(0, 0)),
                pl.BlockSpec((1, G, N), a_lane(0, 0)),
                pl.BlockSpec((1, H, P, N), a_lane(0, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, H // tile, tile * P), a_lane(0, 0)),
                pl.BlockSpec((1, H, P, N), a_lane(0, 0, 0)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((Bn, H // tile, tile * P), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={6: 1},  # counting the two prefetched: the states
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_BYTES),
        name="mamba2_decode_step",
        interpret=interpret,
    )(order, n.reshape(1), decay, u, B.astype(jnp.float32), C.astype(jnp.float32), state)
    y = jnp.where(active[:, None, None], y.reshape(Bn, H, P), 0.0)  # an idle lane's block was never written
    return (y + D.astype(jnp.float32)[:, None] * xf).astype(x.dtype), state

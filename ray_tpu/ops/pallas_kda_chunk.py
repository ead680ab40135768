"""A prompt chunk of a gated-delta-rule (KDA) layer in one kernel (Pallas
TPU): ``ops.kda.kda_chunk`` is its definition, the CPU's path and what
the tests compare it with.

The chunked form (``ops/kda.py``'s docstring) a block of ``BLOCK`` = 64
positions at a time, with everything a block needs beside its q, k, v, a,
beta kept in VMEM: the running sum ``g`` of the log-decays, the powers of
a decay, the ``[64, 64]`` products ``A`` (k with k) and ``P`` (q with
k), the unit-triangular system's inverse, and the carried state.  HBM
sees q, k, v, a, beta once on the way in, ``o`` once on the way out and
the state once each way.

The grid is (groups of ``heads`` heads; blocks of positions, in order).
A group's states lie TRANSPOSED ``[dv, dk]`` in a float32 scratch from
the first block to the last: a channel's decay is then a row broadcast
along the sublanes, and the three products with the state contract the
lanes of both operands.  A step first makes, for each of its heads, what
does not depend on the state (the first three items below), then carries
each head's state through its block (the fourth): the heads' chains of
dependent steps, the substitution's and the state's, fill each other's
waits (one piece of straight-line code; 2.34 ms a layer of 2,048 tokens
with four heads a step and each head's carry behind its own operands,
2.17 with eight and the carries last: PERF.md section 6, PR 62):

- ``g``, the inclusive running sum of ``a`` down the block's rows, on the
  vector units: inside a tile of 8 rows by three shifted adds, then the
  tiles' totals (as a matmul with a triangle of ones it cost an eighth of
  the kernel's time: PERF.md section 6, PR 62).
- POWERS OF alpha are ``exp`` of differences that are never positive, as
  the definition's: inside a sub-block of ``SUB`` = 16 positions ``exp(g_j
  - g_i)`` directly, a column i of all four sub-blocks at a time (ONE
  tensor of powers for ``A`` and ``P``: the gap depends on ``g`` alone),
  summed over the channels along the lanes; between sub-blocks the gap is
  split at the last position before the row's sub-block and the two
  bounded factors meet in a matmul.
- The system ``I + Diag(beta) strict_lower(A)`` is inverted by FORWARD
  SUBSTITUTION: the four diagonal sub-blocks row by row (fifteen rank-one
  steps on all four at once, no power of the system taken), then merged
  two and two by the exact block formula ``[[X, 0], [-Z F X, Z]]`` (which
  is substitution a block at a time), and the inverse meets ``Diag(beta)
  [V | K exp(G)]`` in one matmul.
- ``U = T V - (T K) S``, ``o = (Q exp(G)) S + P U``, ``S = exp(g_last) S
  + (K exp(g_last - G))^T U`` against the scratch.

Every matmul is float32 at ``Precision.HIGHEST`` (the multi-pass form
whose error is float32's), as the definition's.  Rows past ``n_valid``
take ``a`` = ``beta`` = 0 and so leave the state alone; a block that holds
no real row is not computed and its outputs are zeros.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.kda import BLOCK, SUB

_HI = jax.lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_N_SUB = BLOCK // SUB
# heads a grid step: sixteen are 5% faster still and take three times as long to compile
_HEADS = 8
_VMEM_BYTES = 64 * 1024 * 1024


def kernel_takes(T, H, dk, dv) -> bool:
    """The shapes the kernel's tiling can take: whole blocks of positions,
    a head's q, k, a and v whole lane tiles, the heads whole groups."""
    return T % BLOCK == 0 and T >= BLOCK and dk % 128 == 0 and dv % 128 == 0 and H % min(H, _HEADS) == 0


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, precision=_HI, preferred_element_type=jnp.float32)


def _running_sum(a):
    """The inclusive sums down the rows of a [BLOCK, w]."""
    w = a.shape[-1]
    x = a.reshape(BLOCK // 8, 8, w)
    at = jax.lax.broadcasted_iota(jnp.int32, (1, 8, w), 1)
    for s in (1, 2, 4):  # inside a tile of 8 rows
        x = x + jnp.where(at >= s, pltpu.roll(x, s, axis=1), 0.0)
    total = x[:, 7:, :]
    through = total  # the tiles' totals, summed the same way
    for s in (1, 2, 4):
        through = through + jnp.concatenate([jnp.zeros((s, 1, w), jnp.float32), through[:-s]], axis=0)
    return (x + (through - total)).reshape(BLOCK, w)


def _operands(q, k, v, a, beta, row, col):
    """What one head's block is before the state comes into it: q, k, a
    [BLOCK, dk], v [BLOCK, dv] float32 (a and beta 0 on pads), beta [BLOCK,
    1]; row, col [N_SUB, SUB, BLOCK] a position's place in the block as a
    row and as a column -> (``T [V | K exp(G)]`` [BLOCK, dv + dk], ``P``
    [BLOCK, BLOCK], ``Q exp(G)``, ``K exp(g_last - G)`` [BLOCK, dk],
    ``exp(g_last)`` [1, dk])."""
    dk = q.shape[-1]

    def subs(x):  # [BLOCK, w] -> [N_SUB, SUB, w]
        return x.reshape(_N_SUB, SUB, x.shape[-1])

    g = _running_sum(a)  # the log-decay from the block's start through position i
    eg = jnp.exp(g)
    g3, k3, q3 = subs(g), subs(k), subs(q)

    # between sub-blocks: split at the last position before the row's sub-block
    ref = jnp.concatenate([jnp.zeros((1, 1, dk), jnp.float32), g3[:-1, SUB - 1:, :]], axis=0)  # [N_SUB, 1, dk]
    left = jnp.exp(g3 - ref)  # g_j <= g_ref
    rows = jnp.concatenate([q3 * left, k3 * left], axis=1)  # [N_SUB, 2 SUB, dk]
    before = jax.lax.broadcasted_iota(jnp.int32, (BLOCK, 1), 0)
    far = [jnp.zeros((2 * SUB, BLOCK), jnp.float32)]
    for J in range(1, _N_SUB):
        right = jnp.where(before < J * SUB, k * jnp.exp(jnp.minimum(ref[J] - g, 0.0)), 0.0)  # g_ref <= g_i
        far.append(_dot(rows[J], right, _NT))  # [2 SUB, BLOCK]
    p = jnp.stack([f[:SUB] for f in far])  # [N_SUB, SUB, BLOCK]
    system = jnp.stack([f[SUB:] for f in far])

    # inside a sub-block, directly: column i of every sub-block at once
    start = jax.lax.broadcasted_iota(jnp.int32, (_N_SUB, 1, BLOCK), 0) * SUB
    lane = jax.lax.broadcasted_iota(jnp.int32, (_N_SUB, 1, BLOCK), 2)
    for i in range(SUB):
        lo = i // 8 * 8  # the rows before it lie above the diagonal: whole tiles of them are left out
        w = k3[:, i:i + 1, :] * jnp.exp(jnp.minimum(g3[:, lo:, :] - g3[:, i:i + 1, :], 0.0))
        kk = (w * k3[:, lo:, :]).sum(-1, keepdims=True)  # [N_SUB, SUB - lo, 1]
        qk = (w * q3[:, lo:, :]).sum(-1, keepdims=True)
        if lo:
            above = jnp.zeros((_N_SUB, lo, 1), jnp.float32)
            kk, qk = jnp.concatenate([above, kk], axis=1), jnp.concatenate([above, qk], axis=1)
        here = lane == start + i
        system = jnp.where(here, kk, system)
        p = jnp.where(here, qk, p)
    p = jnp.where(col <= row, p, 0.0).reshape(BLOCK, BLOCK)
    lower = jnp.where(col < row, system, 0.0) * subs(beta)  # Diag(beta) strict_lower(A)

    # the four diagonal sub-blocks' inverses by forward substitution, a row of all four a step
    x = jnp.where(col == row, 1.0, 0.0)
    for i in range(SUB - 1):
        column = jnp.stack([lower[J, :, J * SUB + i:J * SUB + i + 1] for J in range(_N_SUB)])  # [N_SUB, SUB, 1]
        x = x - column * x[:, i:i + 1, :]
    # merged two and two: (D + F)^-1 = D^-1 - D^-1 F D^-1 where F takes the first half to the second
    inverse = x.reshape(BLOCK, BLOCK)
    size = SUB
    while size < BLOCK:
        f = jnp.where((row // size % 2 == 1) & (col // size == row // size - 1), lower, 0.0).reshape(BLOCK, BLOCK)
        inverse = inverse - _dot(_dot(inverse, f), inverse)
        size *= 2
    tv_tk = _dot(inverse, beta * jnp.concatenate([v, k * eg], axis=1))
    g_last = g[BLOCK - 1:, :]
    return tv_tk, p, q * eg, k * jnp.exp(g_last - g), jnp.exp(g_last)


def _carry(tv_tk, p, q_in, k_out, decay, st):
    """A head's block against its state st [dv, dk], transposed -> (o
    [BLOCK, dv] unscaled, the state after the block)."""
    dv = st.shape[0]
    from_state = _dot(jnp.concatenate([tv_tk[:, dv:], q_in], axis=0), st, _NT)  # [2 BLOCK, dv]
    u = tv_tk[:, :dv] - from_state[:BLOCK]
    return from_state[BLOCK:] + _dot(p, u), decay * st + _dot(u.T, k_out)


def _kernel(n_ref,                                          # scalar prefetch (SMEM)
            q_ref, k_ref, v_ref, a_ref, beta_ref, s_ref,    # inputs
            o_ref, so_ref,                                  # outputs
            st_scr,                                         # the group's states, transposed
            *, heads, dk, dv, scale):
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _():
        for h in range(heads):
            st_scr[h] = s_ref[h].T

    first = b * BLOCK

    @pl.when(first >= n_ref[0])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(first < n_ref[0])
    def _():
        real = first + jax.lax.broadcasted_iota(jnp.int32, (BLOCK, 1), 0) < n_ref[0]
        shape = (_N_SUB, SUB, BLOCK)
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0) * SUB + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
        operands = []
        for h in range(heads):
            at_k, at_v = slice(h * dk, (h + 1) * dk), slice(h * dv, (h + 1) * dv)
            operands.append(_operands(
                q_ref[:, at_k].astype(jnp.float32), k_ref[:, at_k].astype(jnp.float32), v_ref[:, at_v].astype(jnp.float32),
                jnp.where(real, a_ref[:, at_k].astype(jnp.float32), 0.0), jnp.where(real, beta_ref[:, h:h + 1], 0.0),
                row, col))
        for h in range(heads):
            o, st_scr[h] = _carry(*operands[h], st_scr[h])
            o_ref[:, h * dv:(h + 1) * dv] = (o * scale).astype(o_ref.dtype)

    @pl.when(b == pl.num_programs(1) - 1)
    def _():
        for h in range(heads):
            so_ref[h] = st_scr[h].T


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_chunk_scan(q, k, v, a, beta, state, n_valid, *, interpret=False):
    """The arguments and results of ``ops.kda.kda_chunk``: q, k, a [T, H,
    dk], v [T, H, dv], beta [T, H], state [H, dk, dv] float32, n_valid a
    traced scalar -> (o [T, H, dv] in v's dtype, the state after position
    ``n_valid - 1``).  ``interpret=True`` runs the same kernel on the CPU
    for tests."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    heads = min(H, _HEADS)
    groups, nb = H // heads, T // BLOCK

    def wide(w):
        return pl.BlockSpec((BLOCK, heads * w), lambda g, b, n: (b, g))

    a_group = pl.BlockSpec((heads, dk, dv), lambda g, b, n: (g, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_kernel, heads=heads, dk=dk, dv=dv, scale=dk ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(groups, nb),
            in_specs=[wide(dk), wide(dk), wide(dv), wide(dk),
                      pl.BlockSpec((None, BLOCK, heads), lambda g, b, n: (g, b, 0)), a_group],
            out_specs=[wide(dv), a_group],
            scratch_shapes=[pltpu.VMEM((heads, dv, dk), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((T, H * dv), v.dtype), jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_BYTES),
        name="kda_chunk_scan",
        interpret=interpret,
    )(jnp.asarray(n_valid, jnp.int32).reshape(1), q.reshape(T, H * dk), k.reshape(T, H * dk), v.reshape(T, H * dv),
      a.reshape(T, H * dk), beta.astype(jnp.float32).reshape(T, groups, heads).transpose(1, 0, 2),
      state.astype(jnp.float32))
    return o.reshape(T, H, dv), state

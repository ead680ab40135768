"""Sparse experts without drops: sort, one grouped matmul, weighted sum.

A token goes to ``k`` of ``E`` experts.  ``moe_experts`` sorts the
``tokens * k`` (token, expert) pairs by expert, gathers the tokens' rows
in that order, multiplies each group of rows by its expert's weights in
ONE grouped matmul over the sorted rows (gate and up side by side, then
down; for experts without a gate, up then down), gathers the rows back
ONCE, as the kernel wrote them, a token's j-th pair at row ``j * tokens
+ t``, and adds a token's ``k`` rows, each times the token's weight for
that expert, in float32 in one fused pass (one rounding at the end).
Every pair is computed: there is no capacity, no token is dropped, and
nothing of shape ``[tokens, experts, ...]`` is built (``models/moe.py``'s
one-hot dispatch does both).

On a TPU the grouped matmul is the megablox Pallas kernel
(``jax.experimental.pallas.ops.tpu.megablox``): it visits only the
(row tile, expert) pairs that exist and reads an expert's weights once
for each row tile that touches it, so a decode step of 256 rows over 64
experts reads each expert that was hit about once, and a long prefill
once for every 256 rows or so.  It runs under the name ``moe_gmm`` (the device trace
shows ``moe_gmm tpu_custom_call``).  Elsewhere ``jax.lax.ragged_dot``
computes the same products; the CPU tests take that path, as
``ops.attention.paged_decode_attention`` does with its gather.

A SHARE of the experts (``held``): where the chips of a deployment
divide a layer's experts among them (expert parallelism), each routes
over ALL the experts, as the router was trained, and is given the
weights of its own ``count`` experts from ``first`` on.  The pairs whose
expert is held are sorted, grouped and multiplied as above, none of them
dropped; the other pairs sort behind every group, take no row tile of
the grouped matmul and add nothing.  What comes back is then a PARTIAL
sum: the part of ``sum_k p * expert(h)`` that the held experts give.
The chips' parts add up to the whole layer's (the exchange that sums
them across chips is not here: on one chip the layer runs without it),
and what every chip computes alike, such as a shared expert, is counted
once (``tests/test_mistral4.py`` adds four shares up).  With no share
stated nothing of a share is traced: no select, no row behind a group.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Rows a tile of the kernel covers.  A visit multiplies a WHOLE tile by
# the expert's weights, so a tile of tm rows over groups of g does
# tm / g times the needed operations; an expert whose rows straddle two
# tiles has its weights read twice, so rows / tm + E - 1 visits read up
# to that many experts' weights.  Few large tiles read least, many small
# ones compute least.  On a v5e chip at OLMoE's widths (ms a layer, gate
# and up then down, PR 26): 256 rows 1.11 at 64; 4,096 rows 1.63 / 1.47
# / 2.57 at 128 / 256 / 512; 16,384 rows 3.55 / 2.79 / 3.67.
_TILE_ROWS = (64, 256)
# k and n extents of a weight tile: 4 MB of bf16, double-buffered (1.11
# against 1.14 ms for 2 MB tiles at 256 rows, 1.47 against 1.59 at 4,096;
# PR 26).  They are CEILINGS, and a tile never hangs over an extent's
# end (`gmm_tiling`, PR 40).  Where tk does not divide k the kernel masks
# its last k step: a float32 round trip, an iota and a select over the
# WHOLE loaded tile of both operands, to zero columns past k.  At
# Nemotron-H's widths (k 2,688 up, n 2,688 down; 32 experts, ms a call
# on a v5e chip, `scripts/gmm_tile_check.py`, PR 40) the up matmul under
# (2048, 1024), k in 2,048 + 640 masked: 0.594 at 768 rows, 0.974 at
# 6,144, 1.127 at 12,288; under (896, 1856), three whole k steps and n
# whole: 0.455, 0.598, 0.692.  An n tile that hangs over costs no mask,
# only steps: the down matmul in 1,024 + 1,024 + 640 against three of
# 896: 0.432 / 0.430, 0.635 / 0.567, 0.741 / 0.667.  Tiles under 1 MB
# lose what the mask does (896 x 512: 0.551 at 768 rows).
_TILE_K, _TILE_N = 2048, 1024


def _tile_rows(m: int) -> int:
    """Rows a tile: an eighth of the rows, within _TILE_ROWS, a power
    of two (rows are padded to whole tiles)."""
    lo, hi = _TILE_ROWS
    tm = lo
    while tm < hi and tm * 8 < m:
        tm *= 2
    return tm


def gmm_tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """(tm, tk, tn) of the kernel for rows [m, k] and weights [., k, n],
    from the shapes alone.  tk divides k, so that no k step is masked:
    k whole up to _TILE_K, else the largest multiple of 128 that divides
    k from _TILE_K down to a quarter of it (none: _TILE_K, and the mask).
    tn is _TILE_N where n is whole tiles of it or fewer than one; else n
    whole where tk x n is no more than _TILE_K x _TILE_N, else n in as many
    tiles as _TILE_N would make of it, all alike (whole lanes of 128)."""
    tk = min(_TILE_K, k)
    if k % tk:
        tk = next((t for t in range(_TILE_K, _TILE_K // 4 - 1, -128) if k % t == 0), tk)
    tn = min(_TILE_N, n)
    if n % tn:
        tiles = -(-n // _TILE_N)
        tn = n if tk * n <= _TILE_K * _TILE_N else -(-n // (tiles * 128)) * 128
    return _tile_rows(m), tk, tn


@functools.partial(jax.jit, static_argnames=("interpret", "tiling", "transposed"))
def moe_gmm(rows, weights, group_sizes, *, interpret=False, tiling=None, transposed=False):
    """rows [m, k] sorted by group, weights [groups, k, n], group_sizes
    [groups] int32 summing to m -> [m, n] in rows' dtype: each group's
    rows times its own weights, float32 accumulation.  `transposed`: the
    weights are [groups, n, k], a group's matrix the other way round.
    The megablox kernel under this function's name; `tiling` (tm, tk,
    tn) is for tests and tuning."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    m, k = rows.shape
    n = weights.shape[1 if transposed else 2]
    tm, tk, tn = tiling or gmm_tiling(m, k, n)
    pad = -m % tm  # the kernel takes whole row tiles; the pad belongs to no group
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    # the undecorated function: its pallas_call takes the name of the
    # innermost jit around it, which is this one
    out = gmm.__wrapped__(
        rows, weights, group_sizes, preferred_element_type=rows.dtype,
        tiling=(tm, tk, tn), interpret=interpret, transpose_rhs=transposed,
    )
    return out[:m] if pad else out


def grouped_matmul(rows, weights, group_sizes, transposed=False):
    """As ``moe_gmm``, by the backend: the Pallas kernel on a TPU,
    ``jax.lax.ragged_dot`` elsewhere."""
    if jax.default_backend() == "tpu":
        return moe_gmm(rows, weights, group_sizes, transposed=transposed)
    if transposed:
        weights = jnp.swapaxes(weights, 1, 2)
    return jax.lax.ragged_dot(rows, weights, group_sizes).astype(rows.dtype)


@jax.jit
def _combine(out, order, top_p, written):
    """out [T * k, d] the second grouped matmul's rows, sorted by expert as
    `order` (a permutation of the T * k pairs, token-major) says; top_p
    [T, k] float32; `written` the rows the kernel wrote (the held pairs),
    None where every expert is held -> (y [T, d] in out's dtype: a token's
    k rows, each times its weight, added in float32 and rounded once; the
    rows that are not all zero, int32).  A jit of its own: a model's
    layers and a program's chunk buckets trace and lower it once a shape,
    not once a layer (its k slices are k times the operations; PR 43)."""
    T, k = top_p.shape
    with jax.named_scope("moe.combine"):
        nonzero = (out != 0).any(axis=-1)
        if written is not None:
            # rows behind the groups were never written: whatever lies there (NaN
            # too) is not a result, and is selected out, never multiplied by zero
            nonzero &= jnp.arange(T * k) < written
        computed = nonzero.sum(dtype=jnp.int32)
        back = jnp.zeros(T * k, order.dtype).at[order].set(jnp.arange(T * k, dtype=order.dtype))
        # ONE gather of the rows as the kernel wrote them (bf16), a token's j-th
        # pair at row j * T + t: the k blocks of T rows are slices of the major
        # dim (no copy, no pad whatever k is), each widened, weighed and added
        # into the running float32 sum in one fused pass over them
        slot = back.reshape(T, k).T
        got = out[slot.reshape(k * T)]
        y = None
        for j in range(k):
            part = got[j * T:(j + 1) * T].astype(jnp.float32) * top_p[:, j, None]
            if written is not None:
                part = jnp.where((slot[j] < written)[:, None], part, 0.0)
            y = part if y is None else y + part
        return y.astype(out.dtype), computed


def moe_experts(h, top_p, top_e, wgu, wd, held=None, gated=True):
    """The expert layer of a token batch.

    h [T, d] the tokens; top_p [T, k] float32 and top_e [T, k] int32 a
    token's weights and experts; wgu [E, d, 2 * f] every expert's gate
    and up projections side by side; wd [E, f, d] its down projection.
    `held` (first, count), static: wgu and wd are the weights of experts
    ``first .. first + count - 1`` alone, of the more that top_e ranges
    over; None: of all of them.  `gated` False, static: the experts have
    no gate and an expert is ``relu(h Wu)^2 Wd``; wgu is then the up
    projection alone and TRANSPOSED, [E, f, d] as wd is: an expert width
    that is not whole lane tiles of 128 (1,856) as the minor dim makes
    the chip lay the tensor out with d minor, and the kernel, which
    takes row-major operands, is then handed a copy of it every call.
    Returns (y [T, d] in h's dtype: ``sum_k p * (silu(h Wg) * (h Wu)) Wd``
    over those of a token's k experts that are held; counters int32 [3]:
    the pairs computed (rows of the second grouped matmul's output that
    are not all zero: the pairs whose expert is held, T * k where all
    are, unless a pair was dropped or the kernel skipped a row), the
    experts that received at least one row, and the rows of the largest
    group)."""
    T, d = h.shape
    k = top_e.shape[1]
    E = wgu.shape[0]
    with jax.named_scope("moe.route"):
        expert = top_e.reshape(T * k)
        if held is not None:
            first, count = held
            assert count == E, f"{E} experts' weights for a share of {count}"
            # a pair of an absent expert sorts behind every group, into none
            expert = jnp.where((expert >= first) & (expert < first + count), expert - first, E)
        order = jnp.argsort(expert, stable=True)  # pairs by expert, tokens in order within one
        group_sizes = jnp.bincount(expert, length=E if held is None else E + 1).astype(jnp.int32)
        if held is not None:
            group_sizes = group_sizes[:E]  # less the absent pairs' own count
        rows = h[order // k]
    with jax.named_scope("moe.experts"):
        if gated:
            gate, up = jnp.split(grouped_matmul(rows, wgu, group_sizes), 2, axis=-1)
            mid = jax.nn.silu(gate) * up
        else:
            mid = jnp.square(jax.nn.relu(grouped_matmul(rows, wgu, group_sizes, transposed=True)))
        out = grouped_matmul(mid, wd, group_sizes)
    y, computed = _combine(out, order, top_p, None if held is None else group_sizes.sum())
    return y, jnp.stack([computed, (group_sizes > 0).sum(dtype=jnp.int32), group_sizes.max()])

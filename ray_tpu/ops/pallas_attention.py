"""Flash attention (forward + backward) as Pallas TPU kernels.

Two levels of tiling.  The grid, (batch*heads, blocks, blocks) with the
last axis sequential, moves square blocks of `block` positions through
VMEM.  One block holds a head's whole sequence up to T = 1024: Q, K, V
(and dO) resident, one grid step a head, nothing streamed.  Longer
sequences stream K/V blocks past a resident Q block (forward, dq) or
Q/dO blocks past a resident K/V block (dk/dv), with m/l/o or the
gradients carried in float32 VMEM scratch.  Inside a grid step the block
is walked in chunks of `chunk` positions by loops unrolled at trace
time, so every slice is static: q-major in the forward and dq, k-major
in dk/dv, each chunk against the STRIP of sub-tiles it can see.

Causal skipping happens at both levels.  A grid block above the diagonal
does nothing (`pl.when`); one below it is computed whole; one ON the
diagonal, which at T <= 1024 is the only one, computes of its n x n
sub-tiles of `chunk` x `chunk` the n(n+1)/2 on or under the diagonal and
never touches the rest (`visited_sub_tiles`: 10 of 16 at T = 1024).  A
strip is two pieces: the sub-tiles wholly under the diagonal as ONE
matmul (no mask at all: max, subtract, exp, sum), and the one on the
diagonal, which alone builds a mask (two iotas, a compare, a select).  A
masked score is -1e30, whose exp is 0 beside the query's own diagonal
entry, so the probabilities need no second select.  The softmax of a
strip is exact (one max over the strip, no rescaling between its
sub-tiles); the running max and its rescale act once a block.

Every sub-tile is [keys, queries] (s^T = k q^T).  Per-query statistics
(m, l, the logsumexp L and delta, [1, T] rows in memory) then lie along
lanes as they are stored, broadcast down the sublanes for free, and are
reduced by elementwise maxima and sums across vregs, not across lanes;
neither p nor dS is transposed for a matmul.  What is transposed, in the
kernel, is small: V (forward) or K (backward) a block, and o or dq a
chunk, [D, chunk], on the way out.

The matmuls take the arrays' own dtype (bf16 wherever a model trains)
and accumulate in float32 (`preferred_element_type`); p and dS are cast
to that dtype for the second matmul, as `reference_causal_attention`
does.  Scores, statistics, L, delta and the o/dq/dk/dv accumulators are
float32.  The softmax scale is folded into the operand of the outer loop
where that is exact (a power of two, as at D = 64), else applied to the
float32 scores.

Backward: delta = rowsum(do * o) is computed in XLA, and the kernels
recompute p = exp(s - L).  A resident head takes ONE kernel: s and dS
of a strip are computed once and feed dq, dk and dv (five matmuls a
sub-tile, one exp).  A streamed head takes two (dq, and dk/dv: seven
matmuls, two exps), because dq gathers over k blocks and dk/dv over q
blocks, and the grid has one sequential axis.

`flash_tiles` chooses `block` and `chunk` from (T, D, itemsize) alone;
`flash_attention` wires the kernels into jax.custom_vjp; interpret=True
runs the same kernels on CPU for tests.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# What one grid step may hold of Mosaic's default 16 MiB of scoped VMEM.
_VMEM_BUDGET = 12 * 2**20
# Largest grid block: the loops over its chunks are unrolled, and a strip
# of scores is [chunk, block] float32.
_MAX_BLOCK = 1024
# Preferred chunk.  At 128 more sub-tiles are skipped (36 of 64 visited
# for 10 of 16) but every matmul is narrower and there are twice the
# strips: measured slower in all three kernels (PERF.md section 6, PR 31).
_CHUNK = 256

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b


def _step_vmem_bytes(block: int, chunk: int, D: int, itemsize: int) -> int:
    """VMEM of the hungriest grid step, the backward of a resident head:
    q, k, v, do in and dq, dk, dv out, double-buffered, lanes padded to
    128; dq^T in float32; L and delta rows padded to 8 sublanes; four
    float32 strips [chunk, block] of temporaries (s, p, dP, dS)."""
    lanes = -(-D // 128) * 128
    tiles = 7 * 2 * block * lanes * itemsize + D * block * 4
    rows = 2 * 2 * 8 * block * 4
    return tiles + rows + 4 * chunk * block * 4


def flash_tiles(T: int, D: int, itemsize: int, block_q: int = _MAX_BLOCK,
                block_k: int = _MAX_BLOCK) -> Tuple[int, int]:
    """(block, chunk) of a [T, D] head: the grid's square block and the
    chunk it is walked in (a sub-tile is chunk x chunk).

    `block` is the largest power-of-two fraction of min(T, block_q,
    block_k, 1024) that divides T and whose grid step fits the VMEM
    budget, so a head up to 1024 positions is resident whole.  `chunk`
    is 256 where that divides the block, else 128, else the block itself
    (blocks under 128: tests in interpret mode)."""
    block = min(block_q, block_k, _MAX_BLOCK, T)
    while block > 128 and T % block:
        block //= 2
    if T % block:
        raise ValueError(f"seq len {T} is not a multiple of its block {block}")

    def chunk_of(block):
        return next((c for c in (_CHUNK, 128) if block % c == 0), block)

    while block > 128 and block % 2 == 0 and (
            _step_vmem_bytes(block, chunk_of(block), D, itemsize) > _VMEM_BUDGET):
        block //= 2
    return block, chunk_of(block)


def visited_sub_tiles(T: int, chunk: int) -> Tuple[int, int]:
    """(visited, all) sub-tiles of a causal [T, T] square walked in
    sub-tiles of `chunk`: those on or under the diagonal, whatever the
    grid's block.  10 of 16 at T = 1024, chunk = 256."""
    n = T // chunk
    return n * (n + 1) // 2, n * n


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")
    )


def _exact_scale(scale: float) -> bool:
    """A power of two scales any float exactly."""
    return math.frexp(scale)[0] == 0.5


def _scaled(x, scale):
    """x * scale in x's dtype where that is exact, else x (the scores
    take the scale)."""
    return (x * scale).astype(x.dtype) if _exact_scale(scale) else x


def _scores(k, q, scale, diagonal):
    """k @ q.T in float32, [keys, queries]: scaled unless `_scaled`
    already did; under the causal mask if the strip lies ON the diagonal
    (its first key is its first query)."""
    st = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
    if not _exact_scale(scale):
        st = st * scale
    if diagonal:
        key = jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
        query = jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
        st = jnp.where(key <= query, st, NEG_INF)
    return st


def _keys_seen(i, n, chunk, diagonal):
    """The keys of a block that q chunk i sees, as strips (first key,
    keys, on the diagonal): the whole block if it lies under the
    diagonal; else the i chunks before its own in one piece, then its
    own under the mask."""
    if not diagonal:
        return [(0, n * chunk, False)]
    return ([(0, i * chunk, False)] if i else []) + [(i * chunk, chunk, True)]


def _queries_seeing(j, n, chunk, diagonal):
    """The queries of a block that see k chunk j, the same way: its own
    q chunk under the mask, then all later ones in one piece."""
    if not diagonal:
        return [(0, n * chunk, False)]
    later = [((j + 1) * chunk, (n - 1 - j) * chunk, False)] if j < n - 1 else []
    return [(j * chunk, chunk, True)] + later


def _over_blocks(tile, causal, q_block, k_block):
    """Run `tile(diagonal)` for this grid step's pair of blocks: under
    the causal mask not at all where the keys come after the queries."""
    if not causal:
        tile(False)
        return
    pl.when(k_block < q_block)(functools.partial(tile, False))
    pl.when(k_block == q_block)(functools.partial(tile, True))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                chunk, n, num_kb, scale, causal):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    resident = num_kb == 1

    def tile(diagonal):
        vt = v_ref[...].T  # [D, keys]
        for i in range(n):
            rows = slice(i * chunk, (i + 1) * chunk)
            q = _scaled(q_ref[rows, :], scale)
            strips = [(first, _scores(k_ref[first:first + count, :], q, scale, on))
                      for first, count, on in _keys_seen(i, n, chunk, diagonal)]
            m = functools.reduce(jnp.maximum, [st.max(axis=0, keepdims=True) for _, st in strips])
            if resident:
                l = acc = 0.0
            else:
                m_old = m_scr[:, rows]
                m = jnp.maximum(m_old, m)
                alpha = jnp.exp(m_old - m)
                l, acc = l_scr[:, rows] * alpha, acc_scr[:, rows] * alpha
            for first, st in strips:
                pt = jnp.exp(st - m)
                l = l + pt.sum(axis=0, keepdims=True)
                acc = acc + jax.lax.dot_general(
                    vt[:, first:first + st.shape[0]], pt.astype(vt.dtype), _NN,
                    preferred_element_type=jnp.float32)
            if resident:
                o_ref[rows, :] = (acc / l).T.astype(o_ref.dtype)
                lse_ref[:, rows] = m + jnp.log(l)
            else:
                m_scr[:, rows], l_scr[:, rows], acc_scr[:, rows] = m, l, acc

    if resident:
        tile(causal)
        return

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    _over_blocks(tile, causal, qi, ki)

    @pl.when(ki == num_kb - 1)
    def _finish():
        o_ref[...] = (acc_scr[...] / l_scr[...]).T.astype(o_ref.dtype)
        lse_ref[...] = m_scr[...] + jnp.log(l_scr[...])


def _block_specs(block, D):
    """(held, moved): the BlockSpecs (of a [BH, T, D] array, of a
    [BH, 1, T] row) of a block that follows grid axis 1 and so is held
    while the sequential axis runs, and of one that moves with axis 2."""
    def pair(at):
        return (pl.BlockSpec((None, block, D), lambda b, i, j: (b, at(i, j), 0)),
                pl.BlockSpec((None, 1, block), lambda b, i, j: (b, 0, at(i, j))))

    return pair(lambda i, j: i), pair(lambda i, j: j)


def _flash_fwd_impl(qf, kf, vf, *, block, chunk, scale, causal, interpret):
    BH, T, D = qf.shape
    nb = T // block
    (held, held_row), (moved, _) = _block_specs(block, D)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, n=block // chunk, num_kb=nb, scale=scale,
                          causal=causal),
        grid=(BH, nb, nb),
        in_specs=[held, moved, moved],  # q; k, v
        out_specs=[held, held_row],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), qf.dtype),
            jax.ShapeDtypeStruct((BH, 1, T), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, block), jnp.float32),
            pltpu.VMEM((1, block), jnp.float32),
            pltpu.VMEM((D, block), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(qf, kf, vf)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _ds_t(k, v, q, do, lse, delta, scale, diagonal):
    """p^T and dS^T (less the scale) of one strip, [keys, queries], in the
    arrays' dtype for the matmuls that take them."""
    pt = jnp.exp(_scores(k, q, scale, diagonal) - lse)
    dpt = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
    return pt.astype(do.dtype), (pt * (dpt - delta)).astype(q.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dqt_scr, *,
               chunk, n, num_kb, scale, causal):
    """dq of a head too long to be resident: K/V blocks stream past a q
    block, dq^T [D, queries] gathers in scratch."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dqt_scr[...] = jnp.zeros_like(dqt_scr)

    def tile(diagonal):
        kt = k_ref[...].T  # [D, keys]
        for i in range(n):
            rows = slice(i * chunk, (i + 1) * chunk)
            q = _scaled(q_ref[rows, :], scale)
            dqt = dqt_scr[:, rows]
            for first, count, on in _keys_seen(i, n, chunk, diagonal):
                keys = slice(first, first + count)
                _, dst = _ds_t(k_ref[keys, :], v_ref[keys, :], q, do_ref[rows, :],
                               lse_ref[:, rows], delta_ref[:, rows], scale, on)
                dqt = dqt + jax.lax.dot_general(kt[:, keys], dst, _NN,
                                                preferred_element_type=jnp.float32)
            dqt_scr[:, rows] = dqt

    _over_blocks(tile, causal, qi, ki)

    @pl.when(ki == num_kb - 1)
    def _finish():
        dq_ref[...] = (dqt_scr[...] * scale).T.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *out, chunk, n, num_qb,
                scale, causal):
    """dk and dv, k-major: Q/dO blocks stream past a K/V block.  A head
    that is resident whole (one block) yields dq in the same walk: s and
    dS of a strip are computed once and feed all three gradients (five
    matmuls where `_dq_kernel` beside this one make seven, one exp for
    two), dq^T gathering in scratch across the k chunks."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    resident = num_qb == 1
    if resident:
        dq_ref, dk_ref, dv_ref, dqt_scr = out
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = out

    def tile(diagonal):
        for j in range(n):
            keys = slice(j * chunk, (j + 1) * chunk)
            k = _scaled(k_ref[keys, :], scale)
            v = v_ref[keys, :]
            kt = k_ref[keys, :].T if resident else None  # [D, keys], for dq^T
            dk, dv = (0.0, 0.0) if resident else (dk_scr[keys, :], dv_scr[keys, :])
            for first, count, on in _queries_seeing(j, n, chunk, diagonal):
                rows = slice(first, first + count)
                q = q_ref[rows, :]
                do = do_ref[rows, :]
                pt, dst = _ds_t(k, v, q, do, lse_ref[:, rows], delta_ref[:, rows], scale, on)
                dv = dv + jax.lax.dot_general(pt, do, _NN, preferred_element_type=jnp.float32)
                dk = dk + jax.lax.dot_general(dst, q, _NN, preferred_element_type=jnp.float32)
                if not resident:
                    continue
                dqt = jax.lax.dot_general(kt, dst, _NN, preferred_element_type=jnp.float32)
                if j > 0:
                    dqt = dqt_scr[:, rows] + dqt
                if on or j == n - 1:  # these queries see no later k chunk
                    dq_ref[rows, :] = (dqt * scale).T.astype(dq_ref.dtype)
                else:
                    dqt_scr[:, rows] = dqt
            if resident:
                dk_ref[keys, :] = (dk * scale).astype(dk_ref.dtype)
                dv_ref[keys, :] = dv.astype(dv_ref.dtype)
            else:
                dk_scr[keys, :], dv_scr[keys, :] = dk, dv

    if resident:
        tile(causal)
        return

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    _over_blocks(tile, causal, qi, ki)

    @pl.when(qi == num_qb - 1)
    def _finish():
        dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_impl(qf, kf, vf, do, out, lse, *, block, chunk, scale, causal, interpret):
    """-> (dq, dk, dv).  One kernel where a head is resident, two where
    it streams."""
    BH, T, D = qf.shape
    nb = T // block
    delta = (do.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)[:, None, :]  # [BH, 1, T]
    (held, held_row), (moved, moved_row) = _block_specs(block, D)
    like_q = jax.ShapeDtypeStruct((BH, T, D), qf.dtype)
    kw = dict(chunk=chunk, n=block // chunk, scale=scale, causal=causal)
    call = functools.partial(pl.pallas_call, grid=(BH, nb, nb),
                             compiler_params=_compiler_params(), interpret=interpret)
    args = (qf, kf, vf, do, lse, delta)
    # dk/dv: K and V held, Q, dO, L and delta moving past them
    dkv_in = [moved, held, held, moved, moved_row, moved_row]
    if nb == 1:
        return call(
            functools.partial(_dkv_kernel, num_qb=1, **kw),
            in_specs=dkv_in, out_specs=[held] * 3, out_shape=[like_q] * 3,
            scratch_shapes=[pltpu.VMEM((D, block), jnp.float32)],
        )(*args)
    dq = call(
        functools.partial(_dq_kernel, num_kb=nb, **kw),
        in_specs=[held, moved, moved, held, held_row, held_row],
        out_specs=held, out_shape=like_q,
        scratch_shapes=[pltpu.VMEM((D, block), jnp.float32)],
    )(*args)
    dk, dv = call(
        functools.partial(_dkv_kernel, num_qb=nb, **kw),
        in_specs=dkv_in, out_specs=[held] * 2, out_shape=[like_q] * 2,
        scratch_shapes=[pltpu.VMEM((block, D), jnp.float32)] * 2,
    )(*args)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API with custom vjp
# ---------------------------------------------------------------------------
def _to_bh(t):
    B, T, H, D = t.shape
    return t.transpose(0, 2, 1, 3).reshape(B * H, T, D)


def _from_bh(t, B, H):
    BH, T, D = t.shape
    return t.reshape(B, H, T, D).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block, chunk, interpret):
    out, _ = _fwd(q, k, v, causal, block, chunk, interpret)
    return out


def _fwd(q, k, v, causal, block, chunk, interpret):
    B, T, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    out, lse = _flash_fwd_impl(
        _to_bh(q), _to_bh(k), _to_bh(v),
        block=block, chunk=chunk, scale=scale, causal=causal, interpret=interpret,
    )
    return _from_bh(out, B, H), (q, k, v, _from_bh(out, B, H), lse)


def _bwd(causal, block, chunk, interpret, res, g):
    q, k, v, out, lse = res
    B, T, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    grads = _flash_bwd_impl(
        _to_bh(q), _to_bh(k), _to_bh(v), _to_bh(g), _to_bh(out), lse,
        block=block, chunk=chunk, scale=scale, causal=causal, interpret=interpret,
    )
    return tuple(_from_bh(x, B, H) for x in grads)


_flash.defvjp(_fwd, _bwd)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = _MAX_BLOCK,
                    block_k: int = _MAX_BLOCK, interpret: bool = False):
    """[B, T, H, D] flash attention (differentiable, Pallas fwd+bwd).

    Blocks and sub-tiles come from the shape (`flash_tiles`); `block_q`
    and `block_k` only cap them, for tests that want several blocks or
    small sub-tiles at a small T."""
    B, T, H, D = q.shape
    block, chunk = flash_tiles(T, D, q.dtype.itemsize, block_q, block_k)
    return _flash(q, k, v, causal, block, chunk, interpret)

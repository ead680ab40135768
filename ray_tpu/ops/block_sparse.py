"""Block-sparse attention by learned selection (InfLLM-V2, as MiniCPM4
publishes it): which blocks of the cache a query reads.

Keys are compressed over windows of ``kernel_size`` positions every
``kernel_stride`` (the mean).  A query scores every whole window at or
before its position; the probabilities of the query heads that share a
K/V head are summed; a block of ``block_size`` positions scores the
largest of the windows that overlap it; the first ``init_blocks`` blocks
and those that hold the last ``window_size`` positions are always kept;
the ``topk`` highest blocks are read.  A query at a position under
``dense_len`` reads every position before it.

``sp`` below is anything hashable with those seven attributes (the
model's config; ``sparse_chunk_attention`` takes it as a static argument).  ``block_keep`` gives a mask (prefill, and the reference of the
tests), ``block_choice`` a list of block numbers (decode: the kernel
walks it), ``sparse_chunk_attention`` is a prompt chunk's attention over
the sequence's cached positions under that mask.  Plain ``jax.numpy``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG = -1e30
# a prompt chunk's queries are taken _Q_TILE at a time, and the keys
# before them K_BLOCK at a time under an online softmax: the scores of
# 4,096 queries over 32k keys would be 17 GB
_Q_TILE, K_BLOCK = 512, 1024
# and a tile's selection scores the compressed keys _W_BLOCK windows at a time
_W_BLOCK = 512


def compress_keys(k, sp):
    """k [C, G, d] at positions 0..C-1 (C a multiple of the stride) ->
    [C / stride, G, d] float32: window j is the mean of positions
    ``stride * j .. stride * j + kernel_size - 1`` (a window that runs
    past C is short of keys and never valid)."""
    stride, parts = sp.kernel_stride, sp.kernel_size // sp.kernel_stride
    C = k.shape[0]
    part = k.astype(jnp.float32).reshape(C // stride, stride, *k.shape[1:]).sum(1)
    part = jnp.concatenate([part, jnp.zeros((parts - 1, *part.shape[1:]), part.dtype)])
    total = sum(part[i:i + C // stride] for i in range(parts))
    return total / sp.kernel_size


def _whole(j, t, sp):
    """Windows j [NW] against queries at t [...] -> [..., 1, 1, NW]: the
    window's last position lies at or before the query's."""
    return (sp.kernel_stride * j + sp.kernel_size - 1 <= t[..., None])[..., None, None, :]


def _block_scores(p, n_blocks, sp, before=None):
    """p [..., NW] a window's probability (0 where it is not valid), the
    first of them the first window that starts in a block; before
    [..., lead] the windows ahead of them (none: p starts at window 0)
    -> [..., n_blocks]: the largest of the windows that overlap a block."""
    r = sp.block_size // sp.kernel_stride  # windows that start in a block
    lead = sp.kernel_size // sp.kernel_stride - 1  # and those that start before it and reach in
    assert r * sp.kernel_stride == sp.block_size and lead <= r, (sp.block_size, sp.kernel_size, sp.kernel_stride)
    if before is None:
        before = jnp.zeros((*p.shape[:-1], lead), p.dtype)
    pad = lead + r * (n_blocks + 1) - p.shape[-1]
    p = jnp.concatenate([before, p, jnp.zeros((*p.shape[:-1], max(pad, 0)), p.dtype)], axis=-1)
    # block b reads padded windows r*b .. r*b + r + lead - 1
    first = p[..., :r * n_blocks].reshape(*p.shape[:-1], n_blocks, r).max(-1)
    if not lead:
        return first
    after = p[..., r:r * (n_blocks + 1)].reshape(*p.shape[:-1], n_blocks, r)[..., :lead].max(-1)
    return jnp.maximum(first, after)


def block_scores(s, t, n_blocks, sp):
    """s [..., G, R, NW] float32, the R query heads of each K/V head
    against the compressed keys (already scaled); t [...] the query's
    position.  -> [..., G, n_blocks] float32: infinity for blocks always
    kept, -1 for blocks that start after t, else the selection score."""
    valid = _whole(jnp.arange(s.shape[-1]), t, sp)
    s = jnp.where(valid, s, NEG)
    e = jnp.where(valid, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
    p = (e / jnp.maximum(e.sum(-1, keepdims=True), 1e-30)).sum(-2)  # [..., G, NW]
    return _forced(_block_scores(p, n_blocks, sp), t, sp)


def _forced(score, t, sp):
    """score [..., G, n_blocks] by the windows' probabilities -> the same
    with infinity for blocks always kept and -1 for blocks that start after t."""
    b = jnp.arange(score.shape[-1])
    tt = t[..., None, None]
    recent = jnp.maximum(tt - (sp.window_size - 1), 0) // sp.block_size
    always = (b < sp.init_blocks) | (b >= recent) | (tt < sp.dense_len)
    score = jnp.where(always, jnp.inf, score)
    return jnp.where(sp.block_size * b <= tt, score, -1.0)


def blocks_cached(t, sp):
    """Blocks that hold a position at or before t."""
    return t // sp.block_size + 1


def block_keep(score, sp):
    """score [..., G, n_blocks] of ``block_scores`` -> the mask of the
    blocks the query reads: the ``topk`` highest (all that there are
    under ``dense_len``), ties with the last of them included.  The mask
    is ``score >= lax.top_k(score, k)[0][..., -1:]`` exactly: the k-th
    highest value is one of the scores, whatever finds it.  It comes
    from a sort along the FIRST axis of a two-dimensional copy, so that
    the v5e compiler sorts with a query a lane: it sorts an array along
    whatever axis its producer left minor, and along the minor one a
    tile's 1,024 rows of 592 take 2.5 ms for 0.2 (PR 46;
    ``scripts/sparse_chunk_check.py listing`` fails where the compiled
    sort has another layout)."""
    n = score.shape[-1]
    kth = jnp.sort(score.reshape(-1, n).T, axis=0)[n - min(sp.topk, n)].reshape(score.shape[:-1])
    return (score >= kth[..., None]) & (score >= 0)


def block_choice(score, t, n_sel, sp):
    """As ``block_keep``, as a list: (block numbers [..., G, n_sel], of
    which the first ``count`` [..., G] are read)."""
    _, ids = jax.lax.top_k(score, n_sel)
    cached = blocks_cached(t, sp)
    count = jnp.where(t < sp.dense_len, cached, jnp.minimum(sp.topk, cached))
    return ids.astype(jnp.int32), jnp.broadcast_to(count[..., None], ids.shape[:-1]).astype(jnp.int32)


def max_choice(sp):
    """The longest list ``block_choice`` can give: ``topk`` blocks, or
    every block under ``dense_len``."""
    return max(sp.topk, -(-sp.dense_len // sp.block_size))


@functools.partial(jax.jit, static_argnames="sp")
def sparse_chunk_attention(q, ctx_k, ctx_v, ck, start, n_valid, sp):
    """A prompt chunk's queries over the sequence's positions so far.

    q [T, G, R, d] at positions ``start .. start + T - 1`` (the first
    ``n_valid`` real); ctx_k, ctx_v [C, G, d] the sequence's keys and
    values by position, this chunk's among them (C a multiple of
    ``K_BLOCK``, at least ``start + T``); ck [C / stride, G, d] the
    compressed keys.  -> (o [T, G, R, d] in q's dtype, int32 [4]: blocks
    kept and blocks cached, both summed over real queries and K/V heads;
    tiles that selected and tiles, of those with a real query).

    A tile's selection does what its queries' positions call for: it
    scores the windows that are whole at its last query, ``_W_BLOCK`` at
    a time (a pass for the softmax's normaliser, a pass for the blocks'
    scores), and none at all where that query lies under ``dense_len``:
    there every block before a query is read whatever it scores.  A jit
    of its own: a model's sparse layers trace and lower it once a
    chunk's shape, not once a layer."""
    T, G, R, d = q.shape
    C = ctx_k.shape[0]
    tq = min(T, _Q_TILE)
    kb, per = K_BLOCK, K_BLOCK // sp.block_size
    n_blocks = C // sp.block_size
    scale = 1.0 / (d ** 0.5)
    wb = min(_W_BLOCK, ck.shape[0])
    per_w, lead = sp.block_size // sp.kernel_stride, sp.kernel_size // sp.kernel_stride - 1
    assert wb % per_w == 0, (wb, per_w)  # a step's windows are whole blocks'
    w_steps = -(-ck.shape[0] // wb)
    ck = jnp.concatenate([ck.astype(q.dtype), jnp.zeros((w_steps * wb - ck.shape[0], G, d), q.dtype)])

    def tile(xs):
        qt, off = xs  # [tq, G, R, d]
        t = start + off + jnp.arange(tq)
        last = start + off + tq - 1

        def windows(i):
            """A step's windows against the tile's queries: (s [tq, G, R,
            wb], NEG where a window is not whole at a query; which are)."""
            s = jnp.einsum("tgrd,jgd->tgrj", qt, jax.lax.dynamic_slice_in_dim(ck, i * wb, wb),
                           preferred_element_type=jnp.float32) * scale
            valid = _whole(i * wb + jnp.arange(wb), t, sp)
            return jnp.where(valid, s, NEG), valid

        def select():
            # windows 0 .. whole - 1 are whole at the tile's last query; the step that holds
            # window `whole` is taken too: its first block may reach back into the one before
            whole = (last + 1 - sp.kernel_size) // sp.kernel_stride + 1
            steps = jnp.minimum(whole // wb + 1, w_steps)

            def normaliser(i, carry):
                top, total = carry
                s, valid = windows(i)
                new = jnp.maximum(top, s.max(-1, keepdims=True))
                e = jnp.where(valid, jnp.exp(s - new), 0.0)
                return new, jnp.exp(top - new) * total + e.sum(-1, keepdims=True)

            top, total = jax.lax.fori_loop(0, steps, normaliser, (
                jnp.full((tq, G, R, 1), NEG, jnp.float32), jnp.zeros((tq, G, R, 1), jnp.float32)))
            total = jnp.maximum(total, 1e-30)

            def blocks(i, carry):
                score, before = carry  # before [tq, G, lead]: the last windows of the step before
                s, valid = windows(i)
                p = (jnp.where(valid, jnp.exp(s - top), 0.0) / total).sum(-2)  # [tq, G, wb]
                step = _block_scores(p, wb // per_w, sp, before)
                return jax.lax.dynamic_update_slice_in_dim(score, step, i * (wb // per_w), axis=2), p[..., wb - lead:]

            score, _ = jax.lax.fori_loop(0, steps, blocks, (
                jnp.zeros((tq, G, w_steps * (wb // per_w)), jnp.float32), jnp.zeros((tq, G, lead), jnp.float32)))
            return block_keep(_forced(score[..., :n_blocks], t, sp), sp)

        def all_before():
            return jnp.broadcast_to((sp.block_size * jnp.arange(n_blocks) <= t[:, None])[:, None], (tq, G, n_blocks))

        selects = last >= sp.dense_len
        with jax.named_scope("sala.select"):
            keep = jax.lax.cond(selects, select, all_before)  # [tq, G, n_blocks]
        real = off + jnp.arange(tq) < n_valid
        kept = jnp.where(real[:, None], keep.sum(-1), 0).sum()
        cached = G * jnp.where(real, blocks_cached(t, sp), 0).sum()
        counts = jnp.stack([kept, cached, selects & real[0], real[0]]).astype(jnp.int32)

        def block(i, carry):
            m, l, acc = carry
            kblk = jax.lax.dynamic_slice_in_dim(ctx_k, i * kb, kb)
            vblk = jax.lax.dynamic_slice_in_dim(ctx_v, i * kb, kb)
            sc = jnp.einsum("tgrd,kgd->tgrk", qt, kblk, preferred_element_type=jnp.float32) * scale
            mask = jnp.repeat(jax.lax.dynamic_slice_in_dim(keep, i * per, per, axis=2), sp.block_size, axis=2)
            mask = (mask & (i * kb + jnp.arange(kb) <= t[:, None, None]))[:, :, None, :]
            sc = jnp.where(mask, sc, NEG)
            m_new = jnp.maximum(m, sc.max(-1, keepdims=True))
            p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
            alpha = jnp.exp(m - m_new)
            acc = alpha * acc + jnp.einsum("tgrk,kgd->tgrd", p.astype(vblk.dtype), vblk,
                                           preferred_element_type=jnp.float32)
            return m_new, alpha * l + p.sum(-1, keepdims=True), acc

        init = (jnp.full((tq, G, R, 1), NEG, jnp.float32), jnp.zeros((tq, G, R, 1), jnp.float32),
                jnp.zeros((tq, G, R, d), jnp.float32))
        # key blocks up to the tile's last query; a query always reads itself, so l > 0
        with jax.named_scope("sala.sparse"):
            _, l, acc = jax.lax.fori_loop(0, (start + off + tq + kb - 1) // kb, block, init)
        return (acc / l).astype(q.dtype), counts

    offs = jnp.arange(T // tq, dtype=jnp.int32) * tq
    o, counts = jax.lax.map(tile, (q.reshape(T // tq, tq, G, R, d), offs))
    return o.reshape(T, G, R, d), counts.sum(0)

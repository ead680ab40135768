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

``sp`` below is anything with those seven attributes (the model's
config).  ``block_keep`` gives a mask (prefill, and the reference of the
tests), ``block_choice`` a list of block numbers (decode: the kernel
walks it), ``sparse_chunk_attention`` is a prompt chunk's attention over
the sequence's cached positions under that mask.  Plain ``jax.numpy``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG = -1e30
# a prompt chunk's queries are taken _Q_TILE at a time, and the keys
# before them _K_BLOCK at a time under an online softmax: the scores of
# 4,096 queries over 32k keys would be 17 GB
_Q_TILE, _K_BLOCK = 512, 1024


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


def _block_scores(p, n_blocks, sp):
    """p [..., NW] a window's probability (0 where it is not valid) ->
    [..., n_blocks]: the largest of the windows that overlap a block."""
    r = sp.block_size // sp.kernel_stride  # windows that start in a block
    lead = sp.kernel_size // sp.kernel_stride - 1  # and those that start before it and reach in
    pad = lead + r * (n_blocks + 1) - p.shape[-1]
    p = jnp.concatenate([jnp.zeros((*p.shape[:-1], lead), p.dtype), p,
                         jnp.zeros((*p.shape[:-1], max(pad, 0)), p.dtype)], axis=-1)
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
    NW = s.shape[-1]
    j = jnp.arange(NW)
    valid = (sp.kernel_stride * j + sp.kernel_size - 1 <= t[..., None])[..., None, None, :]
    s = jnp.where(valid, s, NEG)
    e = jnp.where(valid, jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
    p = (e / jnp.maximum(e.sum(-1, keepdims=True), 1e-30)).sum(-2)  # [..., G, NW]
    score = _block_scores(p, n_blocks, sp)
    b = jnp.arange(n_blocks)
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
    under ``dense_len``)."""
    k = min(sp.topk, score.shape[-1])
    kth = jax.lax.top_k(score, k)[0][..., -1:]
    return (score >= kth) & (score >= 0)


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


def sparse_chunk_attention(q, ctx_k, ctx_v, ck, start, n_valid, sp):
    """A prompt chunk's queries over the sequence's positions so far.

    q [T, G, R, d] at positions ``start .. start + T - 1`` (the first
    ``n_valid`` real); ctx_k, ctx_v [C, G, d] the sequence's keys and
    values by position, this chunk's among them (C a multiple of
    ``_K_BLOCK``, at least ``start + T``); ck [C / stride, G, d] the
    compressed keys.  -> (o [T, G, R, d] in q's dtype, blocks kept,
    blocks cached: both summed over real queries and K/V heads)."""
    T, G, R, d = q.shape
    C = ctx_k.shape[0]
    tq = min(T, _Q_TILE)
    kb, per = _K_BLOCK, _K_BLOCK // sp.block_size
    n_blocks = C // sp.block_size
    scale = 1.0 / (d ** 0.5)
    ck = ck.astype(q.dtype)

    def tile(xs):
        qt, off = xs  # [tq, G, R, d]
        t = start + off + jnp.arange(tq)
        with jax.named_scope("sala.select"):
            s = jnp.einsum("tgrd,jgd->tgrj", qt, ck, preferred_element_type=jnp.float32) * scale
            keep = block_keep(block_scores(s, t, n_blocks, sp), sp)  # [tq, G, n_blocks]
        real = off + jnp.arange(tq) < n_valid
        kept = jnp.where(real[:, None], keep.sum(-1), 0).sum()
        cached = G * jnp.where(real, blocks_cached(t, sp), 0).sum()

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
        return (acc / l).astype(q.dtype), kept, cached

    offs = jnp.arange(T // tq, dtype=jnp.int32) * tq
    o, kept, cached = jax.lax.map(tile, (q.reshape(T // tq, tq, G, R, d), offs))
    return o.reshape(T, G, R, d), kept.sum(), cached.sum()

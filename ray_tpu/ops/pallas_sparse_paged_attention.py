"""Decode attention over CHOSEN blocks of a paged KV pool, grouped
queries, read in place (Pallas TPU): layers that read a selection of
the cache (``ops/block_sparse.py``) and share a K/V head among several
query heads.

One new token a lane; for each (lane, K/V head) pair a list of block
numbers (``sparse_block`` positions each, whole pages of the engine's
pool) and how many of them to read.  The pools are ``[n_layer,
num_blocks * block_size, n_kv * d_head]``, a position one row of all
K/V heads, read by the walk of ``ops/paged_walk.py``: the owner a pair,
a compute block a few of its chosen blocks, a page's number read from
the chosen pages and not from a block table, and THAT K/V head's
columns only copied (``[block_size, d_head]`` a copy; a compute block
that is full of chosen blocks as straight-line copies with one wait a
stream, any other a page at a time).  Pages that were
not chosen are never touched; a pair that reads nothing costs nothing.
Its own is the block's arithmetic: scores are one ``[R, d] x [d,
positions]`` matmul of the pair's R query heads, no block-diagonal
layout needed; a column's position comes from its chosen block's
number, and a chosen block that holds no cached position yet is masked
out of the probabilities too.  The fed token's own key and value, not
in the pool yet, are folded in last.  Operands in the pool's dtype,
float32 scores and softmax state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import paged_walk
from ray_tpu.ops.pallas_attention import NEG_INF

# positions a compute block covers: one lane-width of scores
_BLOCK_POSITIONS = 128


def kernel_takes(n_rep, d_head, block_size, sparse_block, dtype) -> bool:
    """The shapes the kernel's tiling can take: a page is whole sublane
    tiles of the pool's dtype, a chosen block whole pages, a compute
    block whole chosen blocks, a head one lane tile, and the query heads
    of a K/V head whole sublane tiles."""
    return (
        block_size % paged_walk.sublanes(dtype) == 0
        and sparse_block % block_size == 0
        and _BLOCK_POSITIONS % sparse_block == 0
        and d_head % 128 == 0
        and n_rep % 8 == 0
    )


def _kernel(layer_ref, len_ref, cnt_ref, blk_ref, page_ref,    # scalar prefetch (SMEM)
            q_ref, ks_ref, vs_ref, k_hbm, v_hbm,               # inputs
            o_ref,                                             # output
            item_pair, item_blk, kbuf, vbuf, sems,             # scratch
            m_ref, l_ref, acc_ref,
            *, n_kv, block_size, sparse_block, n_sel):
    sb = sparse_block
    bk, d = kbuf.shape[1], kbuf.shape[2]
    per = bk // sb               # chosen blocks a compute block
    ppb = sb // block_size       # pages a chosen block
    scale = 1.0 / (d ** 0.5)
    layer = layer_ref[0]

    # the owner a (lane, K/V head) pair: a compute block of the chosen blocks it reads
    total = paged_walk.list_work(
        cnt_ref.shape[0], lambda pr: (cnt_ref[pr] + (per - 1)) // per, item_pair, item_blk)

    # a pair that reads nothing attends to its own token alone
    o_ref[...] = jnp.broadcast_to(vs_ref[...], o_ref.shape)
    # stale rows of a partly filled compute block meet a probability of 0; keep them finite
    vbuf[...] = jnp.zeros_like(vbuf)

    def chosen_here(j):
        """Chosen blocks of item j (at most `per`)."""
        return jnp.minimum(per, cnt_ref[item_pair[j]] - item_blk[j] * per)

    def pages_of(j):
        """The pages of the item's chosen blocks, that K/V head's columns only."""
        pr = item_pair[j]
        base = (pr * n_sel + item_blk[j] * per) * ppb
        cols = pl.ds(pl.multiple_of((pr % n_kv) * d, d), d)
        return chosen_here(j) * ppb, lambda p: page_ref[base + p], cols

    def item(j):
        pr = item_pair[j]
        blk = item_blk[j]
        length = len_ref[pr // n_kv]
        here = chosen_here(j)

        def fold(slot):
            q = q_ref[pr].astype(kbuf.dtype)                     # [R, d]
            s = jax.lax.dot_general(
                q, kbuf[slot], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                            # [R, bk]
            # the position of each column: chosen block `c` of the item sits in columns c*sb ..
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            pos = jnp.full(s.shape, length, jnp.int32)           # a column of no chosen block: masked
            for c in range(per):
                number = blk_ref[pr * n_sel + jnp.minimum(blk * per + c, n_sel - 1)]
                inside = (col >= c * sb) & (col < (c + 1) * sb) & (c < here)
                pos = jnp.where(inside, number * sb + col - c * sb, pos)
            mask = pos < length
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # a chosen block may hold no cached position yet (the newest): p is masked, not exp(0)
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
            acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
                p.astype(vbuf.dtype), vbuf[slot], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                                    # [R, d]
            m_ref[...] = m_new

            @pl.when((blk + 1) * per >= cnt_ref[pr])
            def _():
                # fold in the fed token's own key and value, normalise
                s_self = (q_ref[pr] * ks_ref[pr]).sum(axis=-1, keepdims=True) * scale
                m_all = jnp.maximum(m_new, s_self)
                a = jnp.exp(m_new - m_all)
                b = jnp.exp(s_self - m_all)
                o_ref[pr] = (acc_ref[...] * a + b * vs_ref[pr]) / (l_ref[...] * a + b)

        return blk, lambda: None, fold

    paged_walk.walk(
        total, item, block_size=block_size, layer=layer, pages_of=pages_of,
        streams=[(k_hbm, kbuf, lambda slot: sems.at[0, slot]), (v_hbm, vbuf, lambda slot: sems.at[1, slot])],
        state=(m_ref, l_ref, acc_ref))


@functools.partial(jax.jit, static_argnames=("block_size", "sparse_block", "interpret"))
def sparse_paged_decode_attention_kernel(q, k_self, v_self, k_pages, v_pages, layer,
                                         chosen_pages, chosen_blocks, counts, lengths, *,
                                         block_size, sparse_block, interpret=False):
    """The arguments of ``ops.attention.sparse_paged_decode_attention``.
    ``interpret=True`` runs the same kernel on the CPU for tests."""
    B, G, R, d = q.shape
    n_sel = chosen_blocks.shape[-1]
    per = _BLOCK_POSITIONS // sparse_block
    dt = k_pages.dtype
    items = B * G * -(-n_sel // per)  # compute blocks the pairs can read

    def pairs(x, rows):
        # [B, G, ...] -> [B * G, rows, d]: a pair is an index of the untiled leading dim
        return x.reshape(B * G, rows, d).astype(jnp.float32)

    def per_pair(rows):
        return pl.BlockSpec((B * G, rows, d), lambda i, *_: (0, 0, 0))

    out = pl.pallas_call(
        functools.partial(_kernel, n_kv=G, block_size=block_size, sparse_block=sparse_block,
                          n_sel=n_sel),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(1,),
            in_specs=[
                per_pair(R), per_pair(1), per_pair(1),
                pl.BlockSpec(memory_space=pl.ANY),   # the pools stay in HBM, whole
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=per_pair(R),
            scratch_shapes=[
                pltpu.SMEM((items,), jnp.int32),                   # item_pair
                pltpu.SMEM((items,), jnp.int32),                   # item_blk
                pltpu.VMEM((2, _BLOCK_POSITIONS, d), dt),          # kbuf: two compute blocks of K
                pltpu.VMEM((2, _BLOCK_POSITIONS, d), dt),          # vbuf
                pltpu.SemaphoreType.DMA((2, 2)),                   # [K or V, buffer]
                pltpu.VMEM((R, 1), jnp.float32),                   # m: running max
                pltpu.VMEM((R, 1), jnp.float32),                   # l: running sum
                pltpu.VMEM((R, d), jnp.float32),                   # acc: unnormalised output
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * G, R, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="sparse_paged_decode_attention",
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        lengths.astype(jnp.int32),
        counts.astype(jnp.int32).reshape(-1),
        chosen_blocks.astype(jnp.int32).reshape(-1),
        chosen_pages.astype(jnp.int32).reshape(-1),
        pairs(q, R), pairs(k_self, 1), pairs(v_self, 1), k_pages, v_pages,
    )
    return out.reshape(B, G, R, d).astype(q.dtype)

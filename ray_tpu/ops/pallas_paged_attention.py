"""Decode attention over a paged KV pool, read in place (Pallas TPU).

One new token a lane attends to the positions that lane has cached in
the serving engine's pool (``serve/llm/kv_cache.py``): ``k_pages`` /
``v_pages`` of shape ``[n_layer, num_blocks * block_size, n_head *
d_head]``, a lane's positions scattered over the pages its block table
names.  ``ops.attention.paged_decode_attention`` picks this kernel on a
TPU when ``kernel_takes`` the shapes, and computes the same attention
from the same block tables in plain ``jax.numpy`` elsewhere.

The kernel is one program a layer, and reads the pool by the walk of
``ops/paged_walk.py``: the owner a lane, a page one contiguous
``[block_size, n_head * d_head]`` slab of all heads, of K and of V (a
whole block's pages written out as straight-line copies with one wait a
stream, a last, partial block's started and awaited a page at a time).
Operands in the pool's dtype, float32 scores and softmax state, every
cached position attended.  Its own is the block's arithmetic:

All heads of a page sit in VMEM as ``[positions, n_head * d_head]``.
To keep the per-head products lane-dense the query is laid out
block-diagonally, ``[n_head, n_head * d_head]`` with head ``h`` in
columns ``h * d_head ..``: scores of all heads are one
``[H, H*Dh] x [H*Dh, positions]`` matmul, the output one
``[H, positions] x [positions, H*Dh]`` matmul whose diagonal blocks are
kept.  That is ``n_head`` times the FLOPs of an attention that is
nowhere near compute-bound.
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


def kernel_takes(n_head, d_head, block_size, dtype) -> bool:
    """The shapes the kernel's tiling can take: a page is whole sublane
    tiles of the pool's dtype (16 rows of bf16, 8 of float32), a compute
    block whole pages, a row of all heads whole lane tiles."""
    return (
        block_size % paged_walk.sublanes(dtype) == 0
        and _BLOCK_POSITIONS % block_size == 0
        and (n_head * d_head) % 128 == 0
    )


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------
def _kernel(layer_ref, len_ref, tab_ref,               # scalar prefetch (SMEM)
            q_ref, ks_ref, vs_ref, k_hbm, v_hbm,       # inputs
            o_ref,                                     # output
            item_lane, item_blk, kbuf, vbuf, sems,     # scratch
            qbd_ref, m_ref, l_ref, acc_ref,
            *, d_head, block_size):
    bk = kbuf.shape[1]           # positions a compute block
    Hp, HD = qbd_ref.shape
    scale = 1.0 / (d_head ** 0.5)
    layer = layer_ref[0]
    blocks_of, pages_of = paged_walk.lane_blocks(len_ref, tab_ref, item_lane, item_blk, block_size, bk // block_size)
    total = paged_walk.list_work(len_ref.shape[0], blocks_of, item_lane, item_blk)

    # a lane with nothing cached attends to its own token alone
    o_ref[...] = vs_ref[...]
    # stale rows of a partly filled block meet a probability of 0; keep
    # them finite (the pool holds finite values only)
    vbuf[...] = jnp.zeros_like(vbuf)

    row_id = jax.lax.broadcasted_iota(jnp.int32, (Hp, HD), 0)
    col_id = jax.lax.broadcasted_iota(jnp.int32, (Hp, HD), 1)
    diag = (col_id >= row_id * d_head) & (col_id < (row_id + 1) * d_head)

    def item(j):
        lane = item_lane[j]
        blk = item_blk[j]
        length = len_ref[lane]

        def first():
            qbd_ref[...] = jnp.where(diag, q_ref[lane], 0.0).astype(qbd_ref.dtype)

        def fold(slot):
            k = kbuf[slot]
            v = vbuf[slot]
            s = jax.lax.dot_general(
                qbd_ref[...], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                            # [Hp, bk]
            pos = blk * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos < length, s, NEG_INF)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # a visited block holds at least one position, so m_new is a
            # real score and a masked one gives exp(-1e30 - m_new) == 0
            p = jnp.exp(s - m_new)
            l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
            acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                                    # [Hp, HD]
            m_ref[...] = m_new

            @pl.when((blk + 1) * bk >= length)
            def _():
                # fold in the fed token's own key and value, normalise, keep
                # each head's own columns
                q32 = qbd_ref[...].astype(jnp.float32)
                s_self = (q32 * ks_ref[lane]).sum(axis=-1, keepdims=True) * scale
                m_all = jnp.maximum(m_new, s_self)
                a = jnp.exp(m_new - m_all)
                b = jnp.exp(s_self - m_all)
                out = (acc_ref[...] * a + b * vs_ref[lane]) / (l_ref[...] * a + b)
                o_ref[lane] = jnp.where(diag, out, 0.0).sum(axis=0, keepdims=True)

        return blk, first, fold

    paged_walk.walk(
        total, item, block_size=block_size, layer=layer, pages_of=pages_of,
        streams=[(k_hbm, kbuf, lambda slot: sems.at[0, slot]), (v_hbm, vbuf, lambda slot: sems.at[1, slot])],
        state=(m_ref, l_ref, acc_ref))


@functools.partial(jax.jit, static_argnames=("block_size", "interpret"))
def paged_decode_attention_kernel(q, k_self, v_self, k_pages, v_pages, layer,
                                  block_tables, lengths, *, block_size, interpret=False):
    """The arguments of ``ops.attention.paged_decode_attention``.
    ``interpret=True`` runs the same kernel on the CPU for tests."""
    B, H, Dh = q.shape
    HD = H * Dh
    pages_per_seq = block_tables.shape[1]
    n = _BLOCK_POSITIONS // block_size  # pages a compute block
    bk = n * block_size
    Hp = -(-H // 16) * 16  # heads padded to whole sublane tiles; the pad rows are zeros
    dt = k_pages.dtype
    items = B * -(-pages_per_seq // n)  # compute blocks the lanes can hold

    def rows(x):
        # [B, H, Dh] -> [B, 1, H*Dh]: a lane is an index of the untiled
        # leading dim; float32, exact from bf16, so that a row is whole tiles
        return x.reshape(B, 1, HD).astype(jnp.float32)

    def per_lane():
        return pl.BlockSpec((B, 1, HD), lambda i, *_: (0, 0, 0))

    out = pl.pallas_call(
        functools.partial(_kernel, d_head=Dh, block_size=block_size),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[
                per_lane(), per_lane(), per_lane(),
                pl.BlockSpec(memory_space=pl.ANY),   # the pools stay in HBM, whole
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=per_lane(),
            scratch_shapes=[
                pltpu.SMEM((items,), jnp.int32),          # item_lane
                pltpu.SMEM((items,), jnp.int32),          # item_blk
                pltpu.VMEM((2, bk, HD), dt),              # kbuf: two compute blocks of K
                pltpu.VMEM((2, bk, HD), dt),              # vbuf
                pltpu.SemaphoreType.DMA((2, 2)),          # [K or V, buffer]
                pltpu.VMEM((Hp, HD), dt),                 # qbd: the block-diagonal query
                pltpu.VMEM((Hp, 1), jnp.float32),         # m: running max
                pltpu.VMEM((Hp, 1), jnp.float32),         # l: running sum
                pltpu.VMEM((Hp, HD), jnp.float32),        # acc: unnormalised output
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, 1, HD), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="paged_decode_attention",
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        lengths.astype(jnp.int32),
        block_tables.astype(jnp.int32).reshape(-1),
        rows(q), rows(k_self), rows(v_self), k_pages, v_pages,
    )
    return out.reshape(B, H, Dh).astype(q.dtype)

"""Decode attention over a paged KV pool of GROUPED-query layers, read
in place (Pallas TPU): layers whose ``R`` query heads share each of
``G`` K/V heads (``models/nemotron_h.py``: 32 query heads over 2;
``models/granite_hybrid.py``: 32 over 8), every cached position
attended.

One new token a lane.  The pools are ``[n_layer, num_blocks *
block_size, G * Dh]``, a position one row of all its K/V heads, read by
the walk of ``ops/paged_walk.py``: the owner a lane, a page one
contiguous ``[block_size, G * Dh]`` slab of K and one of V, each copied
ONCE for the R query heads of every group (a whole block's pages
written out as straight-line copies with one wait a stream, a last,
partial block's started and awaited a page at a time).  A compute block
holds a constant number of BYTES, not of positions (``block_positions``,
read off the pool's row): what a block costs beyond its bytes (the
chain matmul -> max -> exp -> matmul and the state's read-modify-write,
about half a microsecond whatever the row's width) is paid once in
1,024 positions of two K/V heads and once in 2,048 of one, where 512
would leave the copies waiting for it (``scripts/gqa_decode_check.py``;
PERF.md section 6, PR 55).  Its own is the block's arithmetic: a
group's scores are one ``[R, Dh] x [Dh, positions]`` matmul against the
group's own columns of the slab, its output one ``[R, positions] x
[positions, Dh]`` matmul (no block-diagonal query: a group's heads read
the same columns), and no K/V head is repeated for its query heads.  A
group of fewer query heads than a sublane tile of the pool's dtype (4
where bf16 packs 16 rows) is padded to one with heads of zeros, whose
rows are dropped: a group's rows are then whole tiles, and the matmul
unit takes a tile's rows at a time whatever they hold.  Operands in the
pool's dtype, float32 scores and softmax state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import paged_walk
from ray_tpu.ops.pallas_attention import NEG_INF

# positions a compute block covers from `_ROW_BYTES` a row a stream up: whole pages
_BLOCK_POSITIONS = 512
_ROW_BYTES = 1024


def block_positions(k_pages) -> int:
    """Positions a compute block covers (whole pages; two buffers of K
    and two of V in VMEM), read off the pool's row: 512 from 1 KB a row
    a stream up (four K/V heads of 128 in bf16: a buffer is 512 KB
    there and 1 MB at eight), 1,024 at 512 B, 2,048 at 256 B and under:
    a buffer of a narrower row stays 512 KB."""
    row = k_pages.shape[-1] * jnp.dtype(k_pages.dtype).itemsize
    return _BLOCK_POSITIONS * min(4, max(1, _ROW_BYTES // row))


def vmem_scratch(groups, n_rep, d_head, positions, dtype) -> list:
    """The kernel's VMEM scratch, ``(shape, dtype)`` each: two buffers of
    a compute block's rows of K and of V, a lane's queries, the softmax
    state."""
    H, GD = groups * n_rep, groups * d_head
    return [
        ((2, positions, GD), dtype),                  # kbuf: two compute blocks of K
        ((2, positions, GD), dtype),                  # vbuf
        ((H, d_head), dtype),                         # the lane's queries, in the pool's dtype
        ((H, 1), jnp.float32),                        # m: running max
        ((H, 1), jnp.float32),                        # l: running sum
        ((H, d_head), jnp.float32),                   # acc: unnormalised output
    ]


def kernel_takes(n_rep, d_head, block_size, dtype) -> bool:
    """The shapes the kernel's tiling can take: a page is whole sublane
    tiles of the pool's dtype (16 rows of bf16), a compute block whole
    pages, a head whole lane tiles.  A group's query heads are any
    number (padded up to whole sublane tiles)."""
    sublanes = paged_walk.sublanes(dtype)
    return n_rep > 0 and block_size % sublanes == 0 and _BLOCK_POSITIONS % block_size == 0 and d_head % 128 == 0


def _kernel(layer_ref, len_ref, tab_ref,               # scalar prefetch (SMEM)
            q_ref, ks_ref, vs_ref, k_hbm, v_hbm,       # inputs
            o_ref,                                     # output
            item_lane, item_blk, kbuf, vbuf, sems,     # scratch
            qb_ref, m_ref, l_ref, acc_ref,
            *, block_size, groups, scale):
    bk = kbuf.shape[1]           # positions a compute block
    n_lanes = len_ref.shape[0]
    H, Dh = qb_ref.shape
    G, R = groups, H // groups
    layer = layer_ref[0]

    def of_groups(f):
        """f(g, the group's rows, the group's columns) for every group,
        the results one under the other: [H, .]."""
        return jnp.concatenate(
            [f(g, slice(g * R, (g + 1) * R), slice(g * Dh, (g + 1) * Dh)) for g in range(G)], axis=0)

    blocks_of, pages_of = paged_walk.lane_blocks(len_ref, tab_ref, item_lane, item_blk, block_size, bk // block_size)
    total = paged_walk.list_work(n_lanes, blocks_of, item_lane, item_blk)

    # a lane with nothing cached attends to its own token alone
    o_ref[...] = jnp.concatenate(
        [jnp.broadcast_to(vs_ref[:, :, g * Dh:(g + 1) * Dh], (n_lanes, R, Dh)) for g in range(G)], axis=1)
    # stale rows of a partly filled block meet a probability of 0; keep
    # them finite (the pool holds finite values only)
    vbuf[...] = jnp.zeros_like(vbuf)

    def item(j):
        lane = item_lane[j]
        blk = item_blk[j]
        length = len_ref[lane]

        def first():
            qb_ref[...] = q_ref[lane].astype(qb_ref.dtype)

        def fold(slot):
            k = kbuf[slot]                                       # [bk, G * Dh]
            v = vbuf[slot]
            s = of_groups(lambda g, rows, cols: jax.lax.dot_general(
                qb_ref[rows, :], k[:, cols], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)) * scale     # [H, bk]
            pos = blk * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos < length, s, NEG_INF)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # a visited block holds at least one position, so m_new is a
            # real score and a masked one gives exp(-1e30 - m_new) == 0
            p = jnp.exp(s - m_new)
            l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
            pv = of_groups(lambda g, rows, cols: jax.lax.dot_general(
                p[rows, :].astype(v.dtype), v[:, cols], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))             # [H, Dh]
            acc_ref[...] = alpha * acc_ref[...] + pv
            m_ref[...] = m_new

            @pl.when((blk + 1) * bk >= length)
            def _():
                # fold in the fed token's own key and value, normalise
                q32 = qb_ref[...].astype(jnp.float32)
                s_self = of_groups(lambda g, rows, cols: (q32[rows, :] * ks_ref[lane, :, cols]).sum(
                    axis=-1, keepdims=True)) * scale
                v_self = of_groups(lambda g, rows, cols: jnp.broadcast_to(vs_ref[lane, :, cols], (R, Dh)))
                m_all = jnp.maximum(m_new, s_self)
                a = jnp.exp(m_new - m_all)
                b = jnp.exp(s_self - m_all)
                o_ref[lane] = (acc_ref[...] * a + b * v_self) / (l_ref[...] * a + b)

        return blk, first, fold

    paged_walk.walk(
        total, item, block_size=block_size, layer=layer, pages_of=pages_of,
        streams=[(k_hbm, kbuf, lambda slot: sems.at[0, slot]), (v_hbm, vbuf, lambda slot: sems.at[1, slot])],
        state=(m_ref, l_ref, acc_ref))


@functools.partial(jax.jit, static_argnames=("block_size", "scale", "interpret"))
def gqa_paged_decode_attention_kernel(q, k_self, v_self, k_pages, v_pages, layer, block_tables, lengths, *,
                                      block_size, scale=None, interpret=False):
    """The arguments of ``ops.attention.gqa_paged_decode_attention``.
    ``interpret=True`` runs the same kernel on the CPU for tests."""
    heads, tile = q.shape[2], paged_walk.sublanes(k_pages.dtype)
    if heads % tile:  # not whole tiles: heads of zeros up to the next tile, dropped at the end
        pad = -heads % tile
        out = gqa_paged_decode_attention_kernel(
            jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0))), k_self, v_self, k_pages, v_pages, layer, block_tables,
            lengths, block_size=block_size, scale=scale, interpret=interpret)
        return out[:, :, :heads]
    B, G, R, Dh = q.shape
    H, GD = G * R, G * Dh
    pages_per_seq = block_tables.shape[1]
    bk = block_positions(k_pages)
    n = bk // block_size  # pages a compute block
    kbuf, vbuf, *rest = vmem_scratch(G, R, Dh, bk, k_pages.dtype)
    items = B * -(-pages_per_seq // n)  # compute blocks the lanes can hold

    def whole(rows, width):
        return pl.BlockSpec((B, rows, width), lambda i, *_: (0, 0, 0))

    def rows(x):
        # [B, G, Dh] -> [B, 1, G * Dh]: a lane is an index of the untiled
        # leading dim; float32, exact from bf16, so that a row is whole tiles
        return x.reshape(B, 1, GD).astype(jnp.float32)

    out = pl.pallas_call(
        functools.partial(_kernel, block_size=block_size, groups=G,
                          scale=1.0 / (Dh ** 0.5) if scale is None else scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[
                whole(H, Dh), whole(1, GD), whole(1, GD),
                pl.BlockSpec(memory_space=pl.ANY),   # the pools stay in HBM, whole
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=whole(H, Dh),
            scratch_shapes=[
                pltpu.SMEM((items,), jnp.int32),                   # item_lane
                pltpu.SMEM((items,), jnp.int32),                   # item_blk
                pltpu.VMEM(*kbuf),
                pltpu.VMEM(*vbuf),
                pltpu.SemaphoreType.DMA((2, 2)),                   # [K or V, buffer]
                *(pltpu.VMEM(*one) for one in rest),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Dh), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="gqa_paged_decode_attention",
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        lengths.astype(jnp.int32),
        block_tables.astype(jnp.int32).reshape(-1),
        q.reshape(B, H, Dh).astype(jnp.float32), rows(k_self), rows(v_self), k_pages, v_pages,
    )
    return out.reshape(B, G, R, Dh).astype(q.dtype)

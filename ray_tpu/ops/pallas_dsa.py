"""A decode step's first read under a learned token-level index
(``ops/dsa.py``, ``models/glm_moe_dsa.py``), Pallas TPU; its second, the
latent attention under the choice, is
``pallas_mla_paged_attention.mla_sparse_paged_decode_attention_kernel``.

``dsa_index_paged_scores_kernel``: a lane's index queries (``Hi`` heads
of ``Di``) against the index keys in the pages the lane holds of a pool
``[n_layer, num_blocks * block_size, Di]``, read in place by the walk of
``ops/paged_walk.py`` (the owner a lane, a page one contiguous
``[block_size, Di]`` slab, one stream).  It keeps no softmax: a block's
arithmetic is, for each group of 128 positions, ``[Hi, Di] x [Di, 128]``,
the ReLU, the heads' weights and their sum down the sublanes, one row of
the block's ``[positions / 128, 128]`` float32 tile of scores, which goes
to the lane's rows of the result where the block's positions lie.  The
result is positions-by-lanes ``[B, C / 128, 128]`` (a lane the untiled
leading dim: a ``[B, C]`` result would need a store at a sublane the
lane chooses); the caller reshapes it.  Positions past a lane's length
read ``NEG_INF``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import paged_walk
from ray_tpu.ops.pallas_attention import NEG_INF

# positions a compute block of the index scores covers: whole pages, two buffers of it in VMEM
_BLOCK_POSITIONS = 2048
_GROUP = 128  # positions a row of the result
_VMEM_BYTES = 16 * 2**20  # what a kernel gets of VMEM unasked


# ----------------------------------------------------------------------
# index scores over the paged index keys
# ----------------------------------------------------------------------
def index_vmem_scratch(n_head, width, dtype) -> list:
    """The index kernel's VMEM scratch, ``(shape, dtype)`` each: two
    buffers of a compute block's keys, a lane's queries."""
    return [((2, _BLOCK_POSITIONS, width), dtype), ((n_head, width), dtype)]


def index_vmem_scratch_bytes(n_head, width, dtype) -> int:
    return paged_walk.tiled_bytes(index_vmem_scratch(n_head, width, dtype))


def index_result_blocks(pages_per_seq, block_size) -> int:
    """Compute blocks the result has room for a lane."""
    return -(-pages_per_seq // (_BLOCK_POSITIONS // block_size))


def index_kernel_takes(n_lanes, n_head, width, block_size, pages_per_seq, dtype) -> bool:
    """The shapes the index kernel's tiling can take: a page whole
    sublane tiles of the pool's dtype, a compute block whole pages, a key
    whole lane tiles, the heads whole sublane tiles; and the scratch and
    the whole result (every lane's scores, float32) leave half the VMEM
    a kernel gets to the other operands."""
    result = n_lanes * index_result_blocks(pages_per_seq, block_size) * _BLOCK_POSITIONS * 4
    return (
        block_size % paged_walk.sublanes(dtype) == 0
        and _BLOCK_POSITIONS % block_size == 0
        and width % 128 == 0
        and n_head % 8 == 0
        and 2 * (index_vmem_scratch_bytes(n_head, width, dtype) + result) <= _VMEM_BYTES
    )


def _index_kernel(layer_ref, len_ref, tab_ref,          # scalar prefetch (SMEM)
                  q_ref, w_ref, pool_hbm,               # inputs
                  o_ref,                                # output
                  item_lane, item_blk, buf, sems, qb_ref,
                  *, block_size):
    bk = buf.shape[1]
    groups = bk // _GROUP
    layer = layer_ref[0]
    blocks_of, pages_of = paged_walk.lane_blocks(len_ref, tab_ref, item_lane, item_blk, block_size, bk // block_size)
    total = paged_walk.list_work(len_ref.shape[0], blocks_of, item_lane, item_blk)

    # a block no lane holds is never visited
    o_ref[...] = jnp.full(o_ref.shape, NEG_INF, o_ref.dtype)
    # stale keys of a partly filled block are masked below; keep them finite
    buf[...] = jnp.zeros_like(buf)

    def item(j):
        lane = item_lane[j]
        blk = item_blk[j]
        length = len_ref[lane]

        def first():
            qb_ref[...] = q_ref[lane].astype(qb_ref.dtype)

        def fold(slot):
            w = w_ref[lane]                                      # [Hi, 1] float32
            row = jax.lax.broadcasted_iota(jnp.int32, (groups, _GROUP), 0)
            tile = jnp.zeros((groups, _GROUP), jnp.float32)
            for g in range(groups):
                s = jax.lax.dot_general(
                    qb_ref[...], buf[slot, g * _GROUP:(g + 1) * _GROUP, :], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )                                                # [Hi, 128]
                scores = (jnp.maximum(s, 0.0) * w).sum(axis=0, keepdims=True)  # [1, 128]
                tile = jnp.where(row == g, jnp.broadcast_to(scores, tile.shape), tile)
            pos = blk * bk + row * _GROUP + jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
            o_ref[lane, pl.ds(pl.multiple_of(blk * groups, groups), groups), :] = jnp.where(
                pos < length, tile, NEG_INF)

        return blk, first, fold

    paged_walk.walk(
        total, item, block_size=block_size, layer=layer, pages_of=pages_of,
        streams=[(pool_hbm, buf, lambda slot: sems.at[slot])], state=None)


@functools.partial(jax.jit, static_argnames=("block_size", "interpret"))
def dsa_index_paged_scores_kernel(q_i, w, pages, layer, block_tables, lengths, *, block_size, interpret=False):
    """q_i [B, Hi, Di], w [B, Hi] float32, pages [L, P, Di], block_tables
    [B, pages], lengths [B] -> [B, pages * block_size] float32: the index
    scores of a lane's cached positions, ``NEG_INF`` past its length.
    ``interpret=True`` runs the same kernel on the CPU for tests."""
    B, Hi, Di = q_i.shape
    pages_per_seq = block_tables.shape[1]
    nblk = index_result_blocks(pages_per_seq, block_size)
    groups = _BLOCK_POSITIONS // _GROUP
    items = B * nblk
    buf, qb = index_vmem_scratch(Hi, Di, pages.dtype)

    def whole(rows, width):
        return pl.BlockSpec((B, rows, width), lambda i, *_: (0, 0, 0))

    out = pl.pallas_call(
        functools.partial(_index_kernel, block_size=block_size),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[whole(Hi, Di), whole(Hi, 1), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole(nblk * groups, _GROUP),
            scratch_shapes=[
                pltpu.SMEM((items,), jnp.int32),                   # item_lane
                pltpu.SMEM((items,), jnp.int32),                   # item_blk
                pltpu.VMEM(*buf),
                pltpu.SemaphoreType.DMA((2,)),                     # a buffer each
                pltpu.VMEM(*qb),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, nblk * groups, _GROUP), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="dsa_index_paged_scores",
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        lengths.astype(jnp.int32),
        block_tables.astype(jnp.int32).reshape(-1),
        # float32, exact from bf16: a lane is an index of the untiled leading dim
        q_i.astype(jnp.float32), w.astype(jnp.float32).reshape(B, Hi, 1), pages,
    )
    return out.reshape(B, nblk * _BLOCK_POSITIONS)[:, :pages_per_seq * block_size]

"""Decode attention over a paged pool of LATENT rows, read in place
(Pallas TPU): layers that cache one compressed row a position shared by
all their heads (multi-head latent attention, ``models/mistral4.py``).

One new token a lane; every head of the lane attends the SAME rows:
keys are a cached row's ``W`` columns, values its first ``v_width``
columns.  The pool is ``[n_layer, num_blocks * block_size, W]``, read
by the walk of ``ops/paged_walk.py``: the owner a lane, a page one
contiguous ``[block_size, W]`` slab, ONE copy a page serving keys and
values both (one stream, one buffer).  Its own is the block's
arithmetic: scores are one ``[H, W] x [W, positions]`` matmul of all
heads (no block-diagonal layout: the heads share the row), the output
one ``[H, positions] x [positions, v_width]`` matmul over the same
buffer's first columns; nothing is expanded to a key or a value a head.
Operands in the pool's dtype, float32 scores and softmax state.  The
queries arrive with every scale already in them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import paged_walk
from ray_tpu.ops.pallas_attention import NEG_INF

# positions a compute block covers: whole pages, two buffers of it in VMEM
_BLOCK_POSITIONS = 512


def kernel_takes(n_head, width, v_width, block_size, dtype) -> bool:
    """The shapes the kernel's tiling can take: a page is whole sublane
    tiles of the pool's dtype, a compute block whole pages, a row and
    its value part whole lane tiles, the heads whole sublane tiles."""
    return (
        block_size % paged_walk.sublanes(dtype) == 0
        and _BLOCK_POSITIONS % block_size == 0
        and width % 128 == 0
        and v_width % 128 == 0
        and v_width <= width
        and n_head % 8 == 0
    )


def _kernel(layer_ref, len_ref, tab_ref,               # scalar prefetch (SMEM)
            q_ref, self_ref, pool_hbm,                 # inputs
            o_ref,                                     # output
            item_lane, item_blk, buf, sems,            # scratch
            qb_ref, m_ref, l_ref, acc_ref,
            *, block_size, v_width):
    bk = buf.shape[1]            # positions a compute block
    layer = layer_ref[0]
    blocks_of, pages_of = paged_walk.lane_blocks(len_ref, tab_ref, item_lane, item_blk, block_size, bk // block_size)
    total = paged_walk.list_work(len_ref.shape[0], blocks_of, item_lane, item_blk)

    # a lane with nothing cached attends to its own token alone
    o_ref[...] = jnp.broadcast_to(self_ref[:, :, :v_width], o_ref.shape)
    # stale rows of a partly filled block meet a probability of 0; keep
    # them finite (the pool holds finite values only)
    buf[...] = jnp.zeros_like(buf)

    def item(j):
        lane = item_lane[j]
        blk = item_blk[j]
        length = len_ref[lane]

        def first():
            qb_ref[...] = q_ref[lane].astype(qb_ref.dtype)

        def fold(slot):
            rows = buf[slot]                                     # [bk, W]: keys, and in their first columns values
            s = jax.lax.dot_general(
                qb_ref[...], rows, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                                    # [H, bk]
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
                p.astype(rows.dtype), rows[:, :v_width], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                                    # [H, v_width]
            m_ref[...] = m_new

            @pl.when((blk + 1) * bk >= length)
            def _():
                # fold in the fed token's own row, normalise
                own = self_ref[lane]                             # [1, W]
                s_self = (qb_ref[...].astype(jnp.float32) * own).sum(axis=-1, keepdims=True)
                m_all = jnp.maximum(m_new, s_self)
                a = jnp.exp(m_new - m_all)
                b = jnp.exp(s_self - m_all)
                o_ref[lane] = (acc_ref[...] * a + b * own[:, :v_width]) / (l_ref[...] * a + b)

        return blk, first, fold

    paged_walk.walk(
        total, item, block_size=block_size, layer=layer, pages_of=pages_of,
        streams=[(pool_hbm, buf, lambda slot: sems.at[slot])], state=(m_ref, l_ref, acc_ref))


@functools.partial(jax.jit, static_argnames=("block_size", "v_width", "interpret"))
def mla_paged_decode_attention_kernel(q, row_self, pages, layer, block_tables, lengths, *,
                                      block_size, v_width, interpret=False):
    """The arguments of ``ops.attention.mla_paged_decode_attention``.
    ``interpret=True`` runs the same kernel on the CPU for tests."""
    B, H, W = q.shape
    pages_per_seq = block_tables.shape[1]
    n = _BLOCK_POSITIONS // block_size  # pages a compute block
    dt = pages.dtype
    items = B * -(-pages_per_seq // n)  # compute blocks the lanes can hold

    def whole(rows, width):
        return pl.BlockSpec((B, rows, width), lambda i, *_: (0, 0, 0))

    out = pl.pallas_call(
        functools.partial(_kernel, block_size=block_size, v_width=v_width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[
                whole(H, W), whole(1, W),
                pl.BlockSpec(memory_space=pl.ANY),   # the pool stays in HBM, whole
            ],
            out_specs=whole(H, v_width),
            scratch_shapes=[
                pltpu.SMEM((items,), jnp.int32),                   # item_lane
                pltpu.SMEM((items,), jnp.int32),                   # item_blk
                pltpu.VMEM((2, _BLOCK_POSITIONS, W), dt),          # buf: two compute blocks of rows
                pltpu.SemaphoreType.DMA((2,)),                     # a buffer each
                pltpu.VMEM((H, W), dt),                            # the lane's queries, in the pool's dtype
                pltpu.VMEM((H, 1), jnp.float32),                   # m: running max
                pltpu.VMEM((H, 1), jnp.float32),                   # l: running sum
                pltpu.VMEM((H, v_width), jnp.float32),             # acc: unnormalised output
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, v_width), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="mla_paged_decode_attention",
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        lengths.astype(jnp.int32),
        block_tables.astype(jnp.int32).reshape(-1),
        # float32, exact from bf16: a lane is an index of the untiled leading dim
        q.astype(jnp.float32), row_self.reshape(B, 1, W).astype(jnp.float32), pages,
    )
    return out.astype(q.dtype)

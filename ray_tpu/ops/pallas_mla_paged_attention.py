"""Decode attention over a paged pool of LATENT rows, read in place
(Pallas TPU): the sibling of ``pallas_paged_attention.py`` for layers
that cache one compressed row a position shared by all their heads
(multi-head latent attention, ``models/mistral4.py``).

One new token a lane; every head of the lane attends the SAME rows:
keys are a cached row's ``W`` columns, values its first ``v_width``
columns.  The pool stays in HBM, whole: ``[n_layer, num_blocks *
block_size, W]``.  The kernel is one program a layer.  It lists the
compute blocks (``_BLOCK_POSITIONS`` positions) the lanes hold, lanes in
order, and walks that list once: for each block it copies the pages the
lane holds there from HBM to VMEM, a page one contiguous ``[block_size,
W]`` slab, ONE copy a page serving keys and values both, the next
block's copies running behind this block's compute, and folds the block
into an online softmax of the lane's heads: scores are one ``[H, W] x
[W, positions]`` matmul of all heads (no block-diagonal layout: the
heads share the row), the output one ``[H, positions] x [positions,
v_width]`` matmul over the same buffer's first columns.  Nothing of
shape ``[.., B, max_ctx, ..]`` is built and nothing is expanded to a key
or a value a head; a lane of length 0 costs nothing.  Operands in the
pool's dtype, float32 scores and softmax state.  The order of summation
depends on positions only, never on which physical pages a lane was
given.  The queries arrive with every scale already in them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas_attention import NEG_INF

# positions a compute block covers: whole pages, two buffers of it in VMEM
_BLOCK_POSITIONS = 512


def kernel_takes(n_head, width, v_width, block_size, dtype) -> bool:
    """The shapes the kernel's tiling can take: a page is whole sublane
    tiles of the pool's dtype, a compute block whole pages, a row and
    its value part whole lane tiles, the heads whole sublane tiles."""
    sublanes = 8 * 4 // jnp.dtype(dtype).itemsize
    return (
        block_size % sublanes == 0
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
    bs = block_size
    bk = buf.shape[1]            # positions a compute block
    n = bk // bs                 # pages a compute block
    n_lanes = len_ref.shape[0]
    pages_per_seq = tab_ref.shape[0] // n_lanes
    layer = layer_ref[0]

    def lane_pages(lane):
        return (len_ref[lane] + (bs - 1)) // bs

    # -- the work list: one item a compute block a lane holds, lanes in
    # order, so a lane of length 0 costs nothing and the copies of the
    # next lane's first block run behind the last block of this one
    def list_lane(b, total):
        def note(i, _):
            item_lane[total + i] = b
            item_blk[total + i] = i
            return _

        nblk = (lane_pages(b) + (n - 1)) // n
        jax.lax.fori_loop(0, nblk, note, 0)
        return total + nblk

    total = jax.lax.fori_loop(0, n_lanes, list_lane, jnp.int32(0))

    # a lane with nothing cached attends to its own token alone
    o_ref[...] = jnp.broadcast_to(self_ref[:, :, :v_width], o_ref.shape)
    # stale rows of a partly filled block meet a probability of 0; keep
    # them finite (the pool holds finite values only)
    buf[...] = jnp.zeros_like(buf)

    def each_page(j, slot, act):
        """act(copy) for every page the lane holds of item j: HBM page
        -> its rows of buffer ``slot``."""
        lane = item_lane[j]
        first = item_blk[j] * n

        def one(p, _):
            page = tab_ref[lane * pages_per_seq + first + p]
            src = pl.ds(pl.multiple_of(page * bs, bs), bs)
            dst = pl.ds(pl.multiple_of(p * bs, bs), bs)
            act(pltpu.make_async_copy(pool_hbm.at[layer, src, :], buf.at[slot, dst, :], sems.at[slot]))
            return _

        jax.lax.fori_loop(0, jnp.minimum(n, lane_pages(lane) - first), one, 0)

    def start(j, slot):
        each_page(j, slot, lambda c: c.start())

    def wait(j, slot):
        each_page(j, slot, lambda c: c.wait())

    @pl.when(total > 0)
    def _():
        start(0, 0)

    def body(j, carry):
        slot = j % 2
        lane = item_lane[j]
        blk = item_blk[j]
        length = len_ref[lane]

        @pl.when(j + 1 < total)
        def _():
            start(j + 1, 1 - slot)

        @pl.when(blk == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)
            qb_ref[...] = q_ref[lane].astype(qb_ref.dtype)

        wait(j, slot)
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

        return carry

    jax.lax.fori_loop(0, total, body, 0)


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

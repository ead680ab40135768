"""The page walk under the paged-decode kernels (Pallas TPU).

``pallas_paged_attention.py``, ``pallas_gqa_paged_attention.py``,
``pallas_mla_paged_attention.py`` and ``pallas_sparse_paged_attention.py``
are each one program a layer over the serving engine's pool
(``serve/llm/kv_cache.py``): ``[n_layer, num_blocks * block_size,
width]`` in HBM, whole, the layer an index into it (a slice of the pool
as an operand would be a copy of the layer), lengths and page numbers
in SMEM as scalar prefetch.  They read it the same way, and that way is
written here, as plain functions a kernel calls while it is traced:

- ``list_work``: the compute blocks (a kernel's ``_BLOCK_POSITIONS``
  positions: whole pages) the owners hold, one item a block, owners in
  order.  An owner is whoever keeps one online softmax: a lane, or a
  (lane, K/V head) pair.  An owner with nothing cached has no item and
  costs nothing; nothing of shape ``[.., B, max_ctx, ..]`` is built.
- ``walk``: the list walked once.  For each item exactly the pages the
  owner holds there are copied from HBM to their rows of one of two
  VMEM buffers, a page a copy a stream (K and V; a latent pool is one
  stream), the next item's copies running behind this item's compute,
  across owners too; the softmax state is reset at an owner's first
  block and the kernel folds the block into it.

The order of summation depends on positions only, never on which
physical pages an owner was given.  What the kernels do not share is the
block's arithmetic; each module says its own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas_attention import NEG_INF


def sublanes(dtype) -> int:
    """Rows of a sublane tile of `dtype` (16 of bf16, 8 of float32)."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def list_work(n_owners, blocks_of, item_owner, item_blk):
    """Fill the work list (SMEM): for owners 0, 1, .. in order, an item
    for each of the ``blocks_of(owner)`` compute blocks it holds.
    Returns how many items there are."""
    def list_owner(owner, total):
        def note(i, _):
            item_owner[total + i] = owner
            item_blk[total + i] = i
            return _

        nblk = blocks_of(owner)
        jax.lax.fori_loop(0, nblk, note, 0)
        return total + nblk

    return jax.lax.fori_loop(0, n_owners, list_owner, jnp.int32(0))


def lane_blocks(len_ref, tab_ref, item_lane, item_blk, block_size, n):
    """The owner a lane: ``(blocks_of, pages_of)`` for ``list_work`` and
    ``walk`` where a lane of ``len_ref[lane]`` cached positions holds
    the pages its row of the flat block table ``tab_ref`` names, ``n``
    pages a compute block, every column of a row wanted."""
    pages_per_seq = tab_ref.shape[0] // len_ref.shape[0]

    def lane_pages(lane):
        return (len_ref[lane] + (block_size - 1)) // block_size

    def blocks_of(lane):
        return (lane_pages(lane) + (n - 1)) // n

    def pages_of(j):
        lane = item_lane[j]
        first = item_blk[j] * n
        return (jnp.minimum(n, lane_pages(lane) - first),
                lambda p: tab_ref[lane * pages_per_seq + first + p], slice(None))

    return blocks_of, pages_of


def walk(total, item, *, block_size, layer, pages_of, streams, state):
    """Walk the ``total`` items of the work list.

    ``pages_of(j)``: how many pages item ``j`` has, the number of its
    ``p``-th page (a function of ``p``), and the columns of a pool row
    to copy.  ``streams``: for each pool read, its ref in HBM, its
    buffer ``[2, positions, columns]`` in VMEM and its DMA semaphore (a
    function of the buffer's number).  ``state``: the refs of the
    running max, the running sum and the unnormalised output, reset at
    an owner's first block.  ``item(j)`` reads what the kernel needs of
    item ``j`` and returns its block's number within its owner, what
    else to do at a first block, and ``fold(slot)``: fold the block,
    whose rows are in buffer ``slot`` by then, into the state."""
    bs = block_size
    m_ref, l_ref, acc_ref = state

    def each_page(j, slot, act):
        """act(copy) for every page of item j and every stream: HBM
        page -> its rows of buffer ``slot``."""
        count, page_of, cols = pages_of(j)

        def one(p, _):
            page = page_of(p)
            src = pl.ds(pl.multiple_of(page * bs, bs), bs)
            dst = pl.ds(pl.multiple_of(p * bs, bs), bs)
            for hbm, buf, sem in streams:
                act(pltpu.make_async_copy(hbm.at[layer, src, cols], buf.at[slot, dst, :], sem(slot)))
            return _

        jax.lax.fori_loop(0, count, one, 0)

    def start(j, slot):
        each_page(j, slot, lambda copy: copy.start())

    def wait(j, slot):
        each_page(j, slot, lambda copy: copy.wait())

    @pl.when(total > 0)
    def _():
        start(0, 0)

    def body(j, carry):
        slot = j % 2
        blk, first, fold = item(j)

        @pl.when(j + 1 < total)
        def _():
            start(j + 1, 1 - slot)

        @pl.when(blk == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)
            first()

        wait(j, slot)
        fold(slot)
        return carry

    jax.lax.fori_loop(0, total, body, 0)

"""The page walk under the paged-decode kernels (Pallas TPU).

``pallas_paged_attention.py``, ``pallas_gqa_paged_attention.py``,
``pallas_mla_paged_attention.py``, ``pallas_sparse_paged_attention.py``
and the index scores of ``pallas_dsa.py`` are each one program a layer over the serving engine's pool
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
  block and the kernel folds the block into it.  A block that holds
  all the pages its buffer has room for (every block of an owner but
  its last) is copied by ``whole_block``: the copies written out as
  straight-line code and ONE wait a stream for the bytes of them all.
  Starting a copy is scalar work (some 20 ns) that stands in front of
  the block's arithmetic; in a loop of the lane's own trip count, with
  a wait a page, it was a fifth more (PERF.md section 6, PR 55).  An
  owner's last, partial block is copied by
  ``page_loop``, a page at a time, started and awaited in a loop: no
  page past an owner's count is ever copied.
- ``blocks``: what a walk over lanes of given lengths visits, blocks
  and whole blocks, for a family's counters.

The order of summation depends on positions only, never on which
physical pages an owner was given nor on which of the two ways a block
was copied.  What the kernels do not share is the block's arithmetic
(and how many positions a block holds); each module says its own.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas_attention import NEG_INF


def sublanes(dtype) -> int:
    """Rows of a sublane tile of `dtype` (16 of bf16, 8 of float32)."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def tiled_bytes(scratch) -> int:
    """Bytes of a kernel's VMEM scratch, ``(shape, dtype)`` each, as the
    chip lays it out: the last two dimensions in whole (sublane,
    128-lane) tiles of the dtype."""
    def tiled(shape, dt):
        *lead, rows, cols = shape
        sub = sublanes(dt)
        return math.prod(lead) * -(-rows // sub) * sub * -(-cols // 128) * 128 * jnp.dtype(dt).itemsize

    return sum(tiled(shape, dt) for shape, dt in scratch)


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


def blocks(lengths, block_size, block_positions):
    """Compute blocks of ``block_positions`` positions a walk over lanes
    of ``lengths`` cached positions visits, and how many of them are
    WHOLE (hold all their pages, and so take ``walk``'s straight-line
    copies): ``(walked, whole)``, two int32 sums.  ``jnp`` on a
    program's own ``lengths``, for its family's counters."""
    n = block_positions // block_size
    pages = (lengths.astype(jnp.int32) + (block_size - 1)) // block_size
    return ((pages + (n - 1)) // n).sum(), (pages // n).sum()


def _page_copy(page, p, slot, *, block_size, layer, cols, stream):
    """The copy of pool page ``page`` to rows ``p * block_size ..`` of
    buffer ``slot`` of one stream."""
    hbm, buf, sem = stream
    src = pl.ds(pl.multiple_of(page * block_size, block_size), block_size)
    dst = pl.ds(pl.multiple_of(p * block_size, block_size), block_size)
    return pltpu.make_async_copy(hbm.at[layer, src, cols], buf.at[slot, dst, :], sem(slot))


def page_loop(count, page_of, cols, slot, start, *, n, block_size, layer, streams):
    """A block's ``count`` pages a page at a time: start (or, ``start``
    false, await) each page's copy of each stream in a loop of
    ``count`` trips.  Any count takes it; ``walk`` gives it a lane's
    last, partial block."""
    del n

    def one(p, _):
        for stream in streams:
            copy = _page_copy(page_of(p), p, slot, block_size=block_size, layer=layer, cols=cols, stream=stream)
            copy.start() if start else copy.wait()
        return _

    jax.lax.fori_loop(0, count, one, 0)


# pages a run of straight-line copies holds at most: the latent kernel's
# block at the served page (4,096 positions in pages of 64), the longest
# run measured on the chip; a block of more pages is as many runs in a loop
_STRAIGHT_PAGES = 64


def whole_block(count, page_of, cols, slot, start, *, n, block_size, layer, streams):
    """A WHOLE block's ``n`` pages as straight-line code: the page
    numbers read up front, every copy of every stream written out (no
    trip count of the lane's, nothing of a page recomputed in a loop
    body; in runs of ``_STRAIGHT_PAGES`` where ``n`` is more), and ONE
    wait a stream: the ``n`` copies of a stream signal one semaphore,
    which counts bytes, so a wait for the buffer slot's whole
    ``[n * block_size, columns]`` is a wait for them all."""
    del count
    if not start:
        for _, buf, sem in streams:
            pltpu.make_async_copy(buf.at[slot], buf.at[slot], sem(slot)).wait()
        return
    run = next(r for r in range(min(n, _STRAIGHT_PAGES), 0, -1) if n % r == 0)

    def straight(first):
        pages = [page_of(first + p) for p in range(run)]
        for p, page in enumerate(pages):
            for stream in streams:
                _page_copy(page, first + p, slot, block_size=block_size, layer=layer, cols=cols,
                           stream=stream).start()

    if run == n:
        straight(0)
    else:
        def each_run(r, _):
            straight(r * run)
            return _

        jax.lax.fori_loop(0, n // run, each_run, 0)


def walk(total, item, *, block_size, layer, pages_of, streams, state):
    """Walk the ``total`` items of the work list.

    ``pages_of(j)``: how many pages item ``j`` has, the number of its
    ``p``-th page (a function of ``p``), and the columns of a pool row
    to copy.  ``streams``: for each pool read, its ref in HBM, its
    buffer ``[2, positions, columns]`` in VMEM and its DMA semaphore (a
    function of the buffer's number).  ``state``: the refs of the
    running max, the running sum and the unnormalised output, reset at
    an owner's first block (None: the kernel keeps no softmax).  ``item(j)`` reads what the kernel needs of
    item ``j`` and returns its block's number within its owner, what
    else to do at a first block, and ``fold(slot)``: fold the block,
    whose rows are in buffer ``slot`` by then, into the state.

    A block that holds all the ``n`` pages its buffer has room for
    (every block of an owner but its last) is copied by ``whole_block``,
    any other by ``page_loop``: what the item's count says, nothing
    else, decides.  No page past an owner's count is ever copied."""
    n = streams[0][1].shape[1] // block_size     # pages a compute block
    how = dict(n=n, block_size=block_size, layer=layer, streams=streams)

    def copies(j, slot, start):
        """Start, or await, the copies of item j's pages to buffer ``slot``."""
        count, page_of, cols = pages_of(j)

        @pl.when(count == n)
        def _():
            whole_block(count, page_of, cols, slot, start, **how)

        @pl.when(count != n)
        def _():
            page_loop(count, page_of, cols, slot, start, **how)

    @pl.when(total > 0)
    def _():
        copies(0, 0, True)

    def body(j, carry):
        slot = j % 2
        blk, first, fold = item(j)

        @pl.when(j + 1 < total)
        def _():
            copies(j + 1, 1 - slot, True)

        @pl.when(blk == 0)
        def _():
            if state is not None:
                m_ref, l_ref, acc_ref = state
                m_ref[...] = jnp.full_like(m_ref, NEG_INF)
                l_ref[...] = jnp.zeros_like(l_ref)
                acc_ref[...] = jnp.zeros_like(acc_ref)
            first()

        copies(j, slot, False)
        fold(slot)
        return carry

    jax.lax.fori_loop(0, total, body, 0)

"""Decode attention over a paged pool of LATENT rows, read in place
(Pallas TPU): layers that cache one compressed row a position shared by
all their heads (multi-head latent attention, ``models/mistral4.py``).

One new token a lane; every head of the lane attends the SAME rows:
keys are a cached row's ``W`` columns, values its first ``v_width``
columns.  The pool is ``[n_layer, num_blocks * block_size, W]``, read
by the walk of ``ops/paged_walk.py``: the owner a lane, a page one
contiguous ``[block_size, W]`` slab, ONE copy a page serving keys and
values both (one stream, one buffer; a whole block's pages written out
as straight-line copies with one wait, a last, partial block's started
and awaited a page at a time).  Its own is the block's
arithmetic: scores are ``[H, W] x [W, positions]`` matmuls of all heads
(no block-diagonal layout: the heads share the row), the output
``[H, positions] x [positions, v_width]`` matmuls over the same buffer's
first columns; nothing is expanded to a key or a value a head.
Operands in the pool's dtype, float32 scores and softmax state.  The
queries arrive with every scale already in them.

A compute block is LARGE (``_BLOCK_POSITIONS``) and folded in parts
(``_PART_POSITIONS``) under ONE running maximum: the parts' score
matmuls, exponentials and output matmuls are independent of one another
but for that maximum, so the scheduler runs one part's matmul under
another's softmax, and what is paid once a block (the state's
read-modify-write, the chain matmul -> max -> exp -> matmul from its
first operand to its last result, the walk's branches) is paid once in
``_BLOCK_POSITIONS`` positions.  A lane's last block folds only the
parts that hold a position and masks only the last of them, so a long
block costs a short lane nothing.  With that the call runs at the pace
of its page copies (``scripts/mla_decode_check.py``; PERF.md section 6,
PR 47).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import paged_walk
from ray_tpu.ops.pallas_attention import NEG_INF

# positions a compute block covers at most: whole pages, two buffers of it in VMEM
_BLOCK_POSITIONS = 4096
# what the two buffers may take of VMEM: a row of 384 columns gets the whole block, one of 640 half of it
_BUFFER_BYTES = 8 * 2**20
# positions of a block folded in one piece: a score matmul, an output matmul
_PART_POSITIONS = 512
# what a kernel gets of VMEM unasked; its operands and its result live there beside the scratch
_VMEM_BYTES = 16 * 2**20
assert _BLOCK_POSITIONS % _PART_POSITIONS == 0


def block_positions(width, dtype) -> int:
    """Positions a compute block covers for a pool of rows `width`
    columns wide: ``_BLOCK_POSITIONS``, halved until its two buffers
    fit ``_BUFFER_BYTES`` (never under a part)."""
    bk = _BLOCK_POSITIONS
    while bk > _PART_POSITIONS and 2 * bk * width * jnp.dtype(dtype).itemsize > _BUFFER_BYTES:
        bk //= 2
    return bk


def vmem_scratch(n_head, width, v_width, dtype) -> list:
    """The kernel's VMEM scratch, ``(shape, dtype)`` each: two buffers of
    a compute block's rows, a lane's queries, the softmax state."""
    return [
        ((2, block_positions(width, dtype), width), dtype),  # buf: two compute blocks of rows
        ((n_head, width), dtype),                     # the lane's queries, in the pool's dtype
        ((n_head, 1), jnp.float32),                   # m: running max
        ((n_head, 1), jnp.float32),                   # l: running sum
        ((n_head, v_width), jnp.float32),             # acc: unnormalised output
    ]


def vmem_scratch_bytes(n_head, width, v_width, dtype) -> int:
    """Bytes of ``vmem_scratch`` as the chip lays it out."""
    return paged_walk.tiled_bytes(vmem_scratch(n_head, width, v_width, dtype))


def _tiles(n_head, width, v_width, block_size, dtype) -> bool:
    """A page is whole sublane tiles of the pool's dtype, a compute block
    whole pages, a row and its value part whole lane tiles, the heads
    whole sublane tiles."""
    return (
        block_size % paged_walk.sublanes(dtype) == 0
        and block_positions(width, dtype) % block_size == 0
        and width % 128 == 0
        and v_width % 128 == 0
        and v_width <= width
        and n_head % 8 == 0
    )


def kernel_takes(n_head, width, v_width, block_size, dtype) -> bool:
    """The shapes the kernel's tiling can take (``_tiles``) at its WHOLE
    compute block (the block it was measured at, PR 47), or, for a pool
    of 16-bit rows, at the block its width leaves (half of it at 640
    columns, where the walk under a choice has run since PR 57 and the
    dense one since PR 61; a float32 pool whose rows would halve it is
    gathered instead: none is served); and the two buffers of a block
    leave half the VMEM a kernel gets to its operands."""
    return (
        _tiles(n_head, width, v_width, block_size, dtype)
        and (block_positions(width, dtype) == _BLOCK_POSITIONS or jnp.dtype(dtype).itemsize == 2)
        and 2 * vmem_scratch_bytes(n_head, width, v_width, dtype) <= _VMEM_BYTES
    )


def lanes_a_call(n_lanes, n_head, width, v_width, dtype) -> int:
    """The lanes one call of the kernel takes: its operands (every
    lane's queries, own row and output, float32, whole in VMEM and twice
    over, as the grid's pipeline holds them) beside the scratch within
    the VMEM a kernel gets unasked.  All of them where they fit (48
    lanes of 32 heads over 384 columns do); else the largest power of two
    that does and divides them (256 lanes of 32 heads over 640 columns:
    32 a call)."""
    def fits(n):
        operands = n * (n_head * (width + v_width) + 8 * width) * 4  # the own row: a tile of 8 sublanes
        return 2 * operands + vmem_scratch_bytes(n_head, width, v_width, dtype) <= _VMEM_BYTES

    n = n_lanes
    while not fits(n) and n % 2 == 0 and n > 1:
        n //= 2
    return n


def _kernel(layer_ref, len_ref, tab_ref,               # scalar prefetch (SMEM)
            q_ref, self_ref, pool_hbm,                 # inputs
            o_ref,                                     # output
            item_lane, item_blk, buf, sems,            # scratch
            qb_ref, m_ref, l_ref, acc_ref,
            *, block_size, v_width, keep_ref=None, own_ref=None):
    bk = buf.shape[1]            # positions a compute block
    part = _PART_POSITIONS       # positions a part of it
    layer = layer_ref[0]
    blocks_of, pages_of = paged_walk.lane_blocks(len_ref, tab_ref, item_lane, item_blk, block_size, bk // block_size)
    total = paged_walk.list_work(len_ref.shape[0], blocks_of, item_lane, item_blk)

    # a lane with nothing cached attends to its own token alone
    o_ref[...] = jnp.broadcast_to(self_ref[:, :, :v_width], o_ref.shape)
    # stale rows of a partly filled part meet a probability of 0; keep
    # them finite (the pool holds finite values only)
    buf[...] = jnp.zeros_like(buf)

    def item(j):
        lane = item_lane[j]
        blk = item_blk[j]
        length = len_ref[lane]

        def first():
            qb_ref[...] = q_ref[lane].astype(qb_ref.dtype)

        def fold(slot):
            last = (blk + 1) * bk >= length
            # the parts of this block that hold a position
            held = (jnp.minimum(length - blk * bk, bk) + (part - 1)) // part

            def rows_of(c):                                      # [part, W]: keys, and in their first columns values
                return buf[slot, c * part:(c + 1) * part, :]

            def parts(n, ends):
                """Fold the block's first n parts into the state under one
                running maximum; ``ends``: the n-th holds the lane's last
                position, and stale rows past it."""
                ss = []
                for c in range(n):
                    s = jax.lax.dot_general(
                        qb_ref[...], rows_of(c), (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )                                            # [H, part]
                    if ends and c == n - 1:
                        pos = blk * bk + c * part + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                        s = jnp.where(pos < length, s, NEG_INF)
                    if keep_ref is not None:
                        # the lane's choice: a row of the mask a part
                        keep = keep_ref[lane, pl.ds(blk * (bk // part) + c, 1), :]     # [1, part]
                        s = jnp.where(keep > 0, s, NEG_INF)
                    ss.append(s)
                top = functools.reduce(jnp.maximum, ss)
                m_prev = m_ref[...]
                m_new = jnp.maximum(m_prev, top.max(axis=-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                l = alpha * l_ref[...]
                acc = alpha * acc_ref[...]
                # a visited block holds at least one position, so m_new is a
                # real score and a masked one gives exp(-1e30 - m_new) == 0
                for c, s in enumerate(ss):
                    p = jnp.exp(s - m_new)
                    if keep_ref is not None:
                        # under a choice a lane may have kept nothing so far: m_new is
                        # still NEG_INF and a masked score's exponential is 1, not 0
                        p = jnp.where(s > 0.5 * NEG_INF, p, 0.0)
                    l = l + p.sum(axis=-1, keepdims=True)
                    acc = acc + jax.lax.dot_general(
                        p.astype(buf.dtype), rows_of(c)[:, :v_width], (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )                                            # [H, v_width]
                l_ref[...] = l
                acc_ref[...] = acc
                m_ref[...] = m_new
                return m_new

            @pl.when(jnp.logical_not(last))
            def _():
                parts(bk // part, False)

            for n in range(1, bk // part + 1):
                @pl.when(last & (held == n))
                def _(n=n):
                    m_new = parts(n, True)
                    # fold in the fed token's own row, normalise
                    own = self_ref[lane]                         # [1, W]
                    s_self = (qb_ref[...].astype(jnp.float32) * own).sum(axis=-1, keepdims=True)
                    if own_ref is not None:
                        # the choice may have left the fed token's own position out
                        s_self = jnp.where(own_ref[lane] > 0, s_self, NEG_INF)
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
    dt = pages.dtype
    n = block_positions(W, dt) // block_size  # pages a compute block
    items = B * -(-pages_per_seq // n)  # compute blocks the lanes can hold
    buf, *rest = vmem_scratch(H, W, v_width, dt)

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
                pltpu.VMEM(*buf),
                pltpu.SemaphoreType.DMA((2,)),                     # a buffer each
                *(pltpu.VMEM(shape, dtype) for shape, dtype in rest),
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


# ----------------------------------------------------------------------
# the same walk under a lane's CHOICE of positions (``ops/dsa.py``)
# ----------------------------------------------------------------------
# the kernel's operands whole in VMEM beside the scratch: every lane's queries, output and mask in float32
_SPARSE_VMEM_BYTES = 64 * 2**20


def sparse_kernel_takes(n_lanes, n_head, width, v_width, block_size, pages_per_seq, dtype) -> bool:
    """The shapes the kernel under a choice can take: ``_tiles`` at
    whatever block the row's width leaves; a lane's positions whole
    parts (a row of the mask a part); and the operands, the mask among
    them, twice over beside the scratch in the VMEM the kernel asks for."""
    positions = pages_per_seq * block_size
    operands = n_lanes * (n_head * (width + v_width) + width + positions) * 4
    return (
        _tiles(n_head, width, v_width, block_size, dtype)
        and positions % _PART_POSITIONS == 0
        and 2 * operands + vmem_scratch_bytes(n_head, width, v_width, dtype) <= _SPARSE_VMEM_BYTES
    )


def _sparse_kernel(layer_ref, len_ref, tab_ref, own_ref,   # scalar prefetch (SMEM)
                   q_ref, self_ref, keep_ref, pool_hbm, o_ref, *scratch, **sizes):
    """``_kernel`` with a lane's choice: ``keep_ref [B, positions / part,
    part]`` float32, 1 where the lane attends a cached position, and
    ``own_ref [B]``, whether it attends the fed token's own."""
    _kernel(layer_ref, len_ref, tab_ref, q_ref, self_ref, pool_hbm, o_ref, *scratch,
            keep_ref=keep_ref, own_ref=own_ref, **sizes)


@functools.partial(jax.jit, static_argnames=("block_size", "v_width", "interpret"))
def mla_sparse_paged_decode_attention_kernel(q, row_self, keep, own_kept, pages, layer, block_tables, lengths, *,
                                             block_size, v_width, interpret=False):
    """As ``mla_paged_decode_attention_kernel`` over the positions a
    lane CHOSE: keep [B, pages * block_size] bool the cached positions a
    lane attends, own_kept [B] whether it attends the fed token's own.
    Every page a lane holds is still copied (a pool's single row is not
    a copy Mosaic takes: its last two dims are tiled together in HBM);
    the choice masks the scores.  ``interpret=True`` runs the same
    kernel on the CPU for tests."""
    B, H, W = q.shape
    pages_per_seq = block_tables.shape[1]
    dt = pages.dtype
    n = block_positions(W, dt) // block_size  # pages a compute block
    items = B * -(-pages_per_seq // n)  # compute blocks the lanes can hold
    rows = pages_per_seq * block_size // _PART_POSITIONS
    buf, *rest = vmem_scratch(H, W, v_width, dt)

    def whole(rows_, width):
        return pl.BlockSpec((B, rows_, width), lambda i, *_: (0, 0, 0))

    out = pl.pallas_call(
        functools.partial(_sparse_kernel, block_size=block_size, v_width=v_width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(1,),
            in_specs=[
                whole(H, W), whole(1, W), whole(rows, _PART_POSITIONS),
                pl.BlockSpec(memory_space=pl.ANY),   # the pool stays in HBM, whole
            ],
            out_specs=whole(H, v_width),
            scratch_shapes=[
                pltpu.SMEM((items,), jnp.int32),                   # item_lane
                pltpu.SMEM((items,), jnp.int32),                   # item_blk
                pltpu.VMEM(*buf),
                pltpu.SemaphoreType.DMA((2,)),                     # a buffer each
                *(pltpu.VMEM(shape, dtype) for shape, dtype in rest),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, v_width), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                             vmem_limit_bytes=_SPARSE_VMEM_BYTES),
        name="mla_sparse_paged_decode_attention",
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        lengths.astype(jnp.int32),
        block_tables.astype(jnp.int32).reshape(-1),
        own_kept.astype(jnp.int32),
        q.astype(jnp.float32), row_self.reshape(B, 1, W).astype(jnp.float32),
        keep.astype(jnp.float32).reshape(B, rows, _PART_POSITIONS), pages,
    )
    return out.astype(q.dtype)

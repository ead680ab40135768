"""A prompt chunk's attention over a sequence's LATENT rows, keys and
values expanded where they are attended (Pallas TPU): the unmasked path
of ``ops.mla.expanded_attention`` as one kernel.

A grid step is a tile of queries and ONE head; it walks the key blocks
the tile can see (``ops.mla.K_BLOCK`` rows each, the parent loop's
``blocks``: none past the tile's last query, none past the last real
position, none at all for a tile of pads) with two buffers, the next
block's rows on their way while this one is attended.  A block's rows
``[keys, latent_row]`` come from HBM once a head and are expanded in
VMEM: the head's keys ``[keys, nope + rope]`` as ONE matmul of the whole
row with the head's columns of ``W_uk`` beside an identity that passes
the shared rotated part through (products with 0 and 1 accumulated in
float32 and rounded to a dtype that already held them: exact), its
values TRANSPOSED ``[v, keys]`` as ``W_uv^T c^T``.  Nothing expanded,
no score and no probability goes to HBM.

Every sub-tile is ``[keys, queries]`` as in ``ops/pallas_attention.py``:
per-query statistics lie along lanes, are reduced across vregs, and the
output of a head is transposed once, on the way out.  A tile is as large
as the chunk allows (``_Q_TILE``): a block's expansion is paid once a
tile.  Its queries are walked in chunks of ``_CHUNK`` that share nothing
but the block's keys, ``_GROUP`` of them one piece of straight-line
code whose score matmuls come first, so that one chunk's exponentials
run under another's matmuls (alone on the chip: ``scripts/
mla_chunk_check.py``; PERF.md section 6, PR 60).  A group is also what a
block's reach is decided for: a group whose last query comes before the
block's first key, or that holds no real query, is not folded (the
loop's tiles of 1,024 queries at the sizes here), and only a group whose
first query comes before the block's last key builds the causal mask.

The arithmetic and its rounding points are the loop's: operands in the
queries' dtype, expanded keys and values rounded to it, float32 scores,
statistics and accumulator, probabilities rounded to the dtype before
the second matmul, one division at the end.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.mla import K_BLOCK, NEG
from ray_tpu.ops.paged_walk import sublanes

# queries a grid step holds at most: the expansion of a key block is paid once a tile
_Q_TILE = 4096
# queries a chunk of it: scores are [K_BLOCK, _CHUNK] float32
_CHUNK = 512
# chunks a group: folded as one piece of straight-line code; a block's mask, its reach into
# the tile and the tile's pads are decided a group
_GROUP = 2

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b


def kernel_takes(nope, rope, kv, dv, width, dtype) -> bool:
    """The widths the kernel's tiling can take: a head's queries, its
    values, the latent and the stored row whole lane tiles, a key block
    whole sublane tiles."""
    return ((nope + rope) % 128 == 0 and dv % 128 == 0 and kv % 128 == 0 and width % 128 == 0
            and kv + rope <= width and K_BLOCK % sublanes(dtype) == 0)


def head_weights(wukv, n_head, nope, rope, kv, dv, width):
    """``wukv [kv, H * (nope + dv)]`` as the kernel reads it a head:
    ``wk [width, H * (nope + rope)]``, whose head takes a stored row to
    that head's key (``W_uk`` over the latent's columns, an identity
    over the rotated part's, zeros over the pad's), and ``wvt [H * dv,
    kv]``, ``W_uv`` transposed."""
    w = wukv.reshape(kv, n_head, nope + dv)
    eye = jnp.broadcast_to(jnp.eye(rope, dtype=w.dtype)[:, None], (rope, n_head, rope))
    wk = jnp.concatenate([
        jnp.concatenate([w[..., :nope], jnp.zeros((kv, n_head, rope), w.dtype)], axis=-1),
        jnp.concatenate([jnp.zeros((rope, n_head, nope), w.dtype), eye], axis=-1),
        jnp.zeros((width - kv - rope, n_head, nope + rope), w.dtype),
    ]).reshape(width, n_head * (nope + rope))
    return wk, w[..., nope:].transpose(1, 2, 0).reshape(n_head * dv, kv)


def _kernel(start_ref, valid_ref,                      # scalar prefetch (SMEM)
            q_ref, wk_ref, wvt_ref, ctx_hbm,           # inputs
            o_ref,                                     # output
            buf, sems, m_scr, l_scr, acc_scr,          # scratch
            *, kv, chunk, group):
    tq = q_ref.shape[0]
    dt = q_ref.dtype
    tile, head = pl.program_id(0), pl.program_id(1)
    start, n_valid = start_ref[0], valid_ref[0]

    def blocks_of(tile):
        """Key blocks tile `tile` sees: up to its last query, no further than the last real position."""
        first = tile * tq
        seen = jnp.minimum(start + first + tq, start + n_valid)
        return jnp.where(first < n_valid, (seen + K_BLOCK - 1) // K_BLOCK, 0)

    def rows(j, slot):
        return pltpu.make_async_copy(ctx_hbm.at[pl.ds(j * K_BLOCK, K_BLOCK), :], buf.at[slot], sems.at[slot])

    first = start + tile * tq  # the tile's first position
    blocks = blocks_of(tile)
    span = group * chunk
    live = jnp.minimum((n_valid - tile * tq + span - 1) // span, tq // span)  # groups that hold a real query

    # a step's block 0 is started by the step before it (below); the first step starts its own
    @pl.when((tile == 0) & (head == 0) & (blocks > 0))
    def _():
        rows(0, 0).start()

    m_scr[...] = jnp.full_like(m_scr, NEG)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def block(j, _):
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < blocks)
        def _():
            rows(j + 1, 1 - slot).start()

        rows(j, slot).wait()
        c = buf[slot]                                                                      # [keys, width]
        k = jnp.dot(c, wk_ref[...], preferred_element_type=jnp.float32).astype(dt)         # [keys, nope + rope]
        vt = jax.lax.dot_general(wvt_ref[...], c[:, :kv], _NT,
                                 preferred_element_type=jnp.float32).astype(dt)           # [v, keys]

        def fold(g, diagonal):
            """Group g of the tile's chunks over this block's keys, under the causal mask if
            `diagonal`: every chunk's scores first, then a chunk's softmax and its output at a
            time.  The scheduler keeps to the order it is handed: one chunk's exponentials run
            under the next one's matmuls only where the matmuls do not wait behind them."""
            chunks = [g * group + t for t in range(group)]
            scores = []
            for i in chunks:
                at = pl.multiple_of(i * chunk, chunk)
                st = jax.lax.dot_general(k, q_ref[pl.ds(at, chunk), :], _NT, preferred_element_type=jnp.float32)
                if diagonal:
                    key = j * K_BLOCK + jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
                    query = first + at + jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
                    st = jnp.where(key <= query, st, NEG)
                scores.append(st)
            for i, st in zip(chunks, scores):
                m_old = m_scr[i]
                m = jnp.maximum(m_old, st.max(axis=0, keepdims=True))
                alpha = jnp.exp(m_old - m)
                # block 0 holds position 0, which every query sees: m is a real score from there
                # on, a masked score gives exp(-1e30 - m) == 0, and a chunk that sees nothing of a
                # later block (its group's other chunks do) is left as it was: alpha 1, p 0
                pt = jnp.exp(st - m)
                l_scr[i] = alpha * l_scr[i] + pt.sum(axis=0, keepdims=True)
                acc_scr[i] = alpha * acc_scr[i] + jax.lax.dot_general(
                    vt, pt.astype(dt), _NN, preferred_element_type=jnp.float32)
                m_scr[i] = m

        # the groups some query of which sees a key of the block: from the one whose last query
        # reaches the block's first key, up to the last real query's; of those, the ones whose
        # first query lies before the block's last key build the mask
        reach = jnp.maximum(j * K_BLOCK - first, 0) // span

        def of_group(g, _):
            masked = (j + 1) * K_BLOCK - 1 > first + g * span
            pl.when(masked)(functools.partial(fold, g, True))
            pl.when(jnp.logical_not(masked))(functools.partial(fold, g, False))
            return 0

        jax.lax.fori_loop(reach, live, of_group, 0)
        return 0

    jax.lax.fori_loop(0, blocks, block, 0)

    # the next step's first block, under this step's way out
    nxt = jnp.where(head == pl.num_programs(1) - 1, tile + 1, tile)

    @pl.when((nxt < pl.num_programs(0)) & (blocks_of(nxt) > 0))
    def _():
        rows(0, 0).start()

    # what visited nothing (a tile of pads, groups past the last real query's): l is 0 there
    # and its rows are zeros
    for i in range(tq // chunk):
        o_ref[i * chunk:(i + 1) * chunk, :] = (acc_scr[i] / jnp.maximum(l_scr[i], 1e-30)).T.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("nope", "rope", "kv", "dv", "interpret"))
def mla_chunk_attention_kernel(q, ctx, wukv, start, n_valid, *, nope, rope, kv, dv, interpret=False):
    """q [T, H, nope + rope] (scaled, rotated) of the positions ``start
    ..``, of which ``n_valid`` are real, over ``ctx [C, width]`` (position
    p in row p, whole key blocks, the chunk's own rows laid in) with
    ``wukv [kv, H * (nope + dv)]`` -> [T, H * dv].  ``interpret=True``
    runs the same kernel on the CPU for tests."""
    T, H, dk = q.shape
    C, width = ctx.shape
    assert dk == nope + rope and C % K_BLOCK == 0, (q.shape, ctx.shape)
    dt = q.dtype
    # whole lane tiles of queries: a head's output leaves transposed
    Tp = -(-T // 128) * 128
    tq = min(Tp, _Q_TILE)
    chunk = next(c for c in (_CHUNK, 256, 128) if c <= _CHUNK and tq % c == 0)
    group = next(g for g in (_GROUP, 2, 1) if g <= _GROUP and (tq // chunk) % g == 0)
    q2 = q.reshape(T, H * dk)
    if Tp != T:
        q2 = jnp.pad(q2, ((0, Tp - T), (0, 0)))
    # the weights' re-layout waits for the queries: made where it is read and gone after, not
    # every layer's at the program's start and held through the experts' temporaries
    q2, wukv = jax.lax.optimization_barrier((q2, wukv))
    wk, wvt = head_weights(wukv.astype(dt), H, nope, rope, kv, dv, width)

    out = pl.pallas_call(
        functools.partial(_kernel, kv=kv, chunk=chunk, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(-(-Tp // tq), H),
            in_specs=[
                pl.BlockSpec((tq, dk), lambda i, h, *_: (i, h)),
                pl.BlockSpec((width, dk), lambda i, h, *_: (0, h)),
                pl.BlockSpec((dv, kv), lambda i, h, *_: (h, 0)),
                pl.BlockSpec(memory_space=pl.ANY),   # the rows stay in HBM, a block at a time copied
            ],
            out_specs=pl.BlockSpec((tq, dv), lambda i, h, *_: (i, h)),
            scratch_shapes=[
                pltpu.VMEM((2, K_BLOCK, width), dt),               # buf: two key blocks of rows
                pltpu.SemaphoreType.DMA((2,)),                     # a buffer each
                pltpu.VMEM((tq // chunk, 1, chunk), jnp.float32),  # m: running max, a chunk a row
                pltpu.VMEM((tq // chunk, 1, chunk), jnp.float32),  # l: running sum
                pltpu.VMEM((tq // chunk, dv, chunk), jnp.float32),  # acc: unnormalised output, transposed
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((Tp, H * dv), dt),
        # in order: a step starts the copy its successor waits for
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        name="mla_chunk_attention",
        interpret=interpret,
    )(
        jnp.asarray(start, jnp.int32).reshape(1), jnp.asarray(n_valid, jnp.int32).reshape(1),
        q2, wk, wvt, ctx.astype(dt),
    )
    return out[:T] if Tp != T else out

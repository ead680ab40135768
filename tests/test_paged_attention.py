"""Decode attention over the paged KV pool (ops.attention
.paged_decode_attention) against the gathered reference: the lane's
positions gathered from the same pool (``k_pages[layer][idx]``) and
attended by ``reference_decode_attention``; and the whole decode step of
the tiny model against the Flax model's full forward.

Both paths on the CPU: the plain ``jax.numpy`` path the engine takes off
the TPU, and the Pallas kernel in interpret mode.  Nothing here is a
time; tests/test_chip_compile.py compiles the kernel for the chip.

And the page walk the four paged-decode kernels share
(``ops/paged_walk.py``): its work list alone, and each kernel at every
edge of the walk against its own gather in ``ops/attention.py``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention, paged_walk
from ray_tpu.ops import pallas_gqa_paged_attention as gqa_kernel
from ray_tpu.ops import pallas_mla_paged_attention as mla_kernel
from ray_tpu.ops import pallas_paged_attention as kernel
from ray_tpu.ops import pallas_sparse_paged_attention as sparse_kernel

BS = 16  # block size: whole sublane tiles of bf16 and float32
PAGES = 20  # pages a lane may hold: max_ctx 320, three compute blocks
LAYERS = 2

# name -> (n_head, d_head, dtype, tolerance against the reference)
HEADS = {
    "tiny": (4, 32, jnp.float32, 2e-5),
    "small": (12, 64, jnp.bfloat16, 2e-2),
}
LENGTHS = {
    "ragged": [5, 200, 17, 300],
    "a_lane_of_length_0": [0, 150, 0, 7],
    # a page is 16 positions, a compute block of the kernel 128
    "page_and_block_boundaries": [15, 16, 17, 127, 128, 129],
    "a_lane_at_max_ctx_less_1": [PAGES * BS - 1, 1, 64, PAGES * BS - 1],
}


def _paged(q, k, v, kp, vp, layer, tables, lengths, path):
    if path == "interpret":
        return kernel.paged_decode_attention_kernel(
            q, k, v, kp, vp, layer, tables, lengths, block_size=BS, interpret=True)
    assert jax.default_backend() != "tpu"  # the dispatch takes the plain path here
    return attention.paged_decode_attention(
        q, k, v, kp, vp, layer, tables, lengths, block_size=BS)


def _tables(lengths, order):
    """Block tables handing out the blocks of ``order`` lane by lane,
    padded with the scratch block 0."""
    tables = np.zeros((len(lengths), PAGES), np.int32)
    taken = 0
    for b, n in enumerate(lengths):
        need = -(-n // BS)
        tables[b, :need] = order[taken:taken + need]
        taken += need
    return tables


def _case(heads, lengths, seed=0):
    H, Dh, dtype, tol = HEADS[heads]
    rng = np.random.default_rng(seed)
    B = len(lengths)
    slots = (B * PAGES + 1) * BS
    kp, vp = (jnp.asarray(rng.standard_normal((LAYERS, slots, H * Dh)), dtype) for _ in range(2))
    q, k, v = (jnp.asarray(rng.standard_normal((B, H, Dh)), dtype) for _ in range(3))
    return q, k, v, kp, vp, tol


def _slots(tables, lengths, width):
    """Position by position through the block table, as the engine's
    ``phys_indices`` gather did: the slot of each cached position
    (scratch slot 0 beyond a lane's length) and the mask of the cached."""
    idx = np.zeros((len(lengths), width), np.int32)
    mask = np.zeros((len(lengths), width), bool)
    for b, n in enumerate(lengths):
        for p in range(n):
            idx[b, p] = tables[b, p // BS] * BS + p % BS
        mask[b, :n] = True
    return idx, mask


def _gathered_reference(q, k, v, kp, vp, layer, tables, lengths):
    """The lane's positions gathered to a contiguous context, then the
    contiguous-context attention."""
    B, H, Dh = q.shape
    C = PAGES * BS
    idx, mask = _slots(tables, lengths, C)
    k_ctx = kp[:, idx][layer].reshape(B, C, H, Dh)
    v_ctx = vp[:, idx][layer].reshape(B, C, H, Dh)
    return attention.reference_decode_attention(q, k, v, k_ctx, v_ctx, jnp.asarray(mask))


@pytest.mark.parametrize("path", ["plain", "interpret"])
@pytest.mark.parametrize("heads", list(HEADS))
@pytest.mark.parametrize("lengths", list(LENGTHS))
def test_paged_attention_matches_gathered_reference(lengths, heads, path):
    lens = LENGTHS[lengths]
    q, k, v, kp, vp, tol = _case(heads, lens)
    order = np.random.default_rng(1).permutation(np.arange(1, len(lens) * PAGES + 1))
    tables = _tables(lens, order)
    layer = 1
    out = _paged(q, k, v, kp, vp, layer, jnp.asarray(tables), jnp.asarray(lens, jnp.int32), path)
    ref = _gathered_reference(q, k, v, kp, vp, layer, tables, lens)
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    assert out.shape == ref.shape and np.isfinite(out).all()
    np.testing.assert_allclose(out, ref, atol=tol, rtol=tol)
    for b, n in enumerate(lens):
        if n == 0:  # nothing cached: the fed token attends to itself alone
            np.testing.assert_array_equal(out[b], np.asarray(v[b], np.float32))


@pytest.mark.parametrize("path", ["plain", "interpret"])
@pytest.mark.parametrize("heads", list(HEADS))
def test_same_sequences_on_other_physical_pages_bit_identical(heads, path):
    """The order of summation depends on positions only: the same
    logical sequences on permuted pages, interleaved between lanes,
    give the same bits."""
    lens = [70, 130, 0, 33, 250]
    q, k, v, kp, vp, _ = _case(heads, lens, seed=3)
    nblocks = len(lens) * PAGES
    in_order = _tables(lens, np.arange(1, nblocks + 1))
    # lane b takes every 5th block of a shuffled pool: pages of
    # different lanes alternate in memory
    shuffled = np.random.default_rng(4).permutation(np.arange(1, nblocks + 1))
    moved = np.zeros_like(in_order)
    for b, n in enumerate(lens):
        need = -(-n // BS)
        moved[b, :need] = shuffled[b::len(lens)][:need]
    # carry each page's rows to where the second table puts them
    kp2, vp2 = np.array(kp), np.array(vp)
    for b, n in enumerate(lens):
        for j in range(-(-n // BS)):
            src, dst = in_order[b, j] * BS, moved[b, j] * BS
            kp2[:, dst:dst + BS] = np.asarray(kp)[:, src:src + BS]
            vp2[:, dst:dst + BS] = np.asarray(vp)[:, src:src + BS]
    lengths = jnp.asarray(lens, jnp.int32)
    a = _paged(q, k, v, kp, vp, 0, jnp.asarray(in_order), lengths, path)
    b = _paged(q, k, v, jnp.asarray(kp2), jnp.asarray(vp2), 0, jnp.asarray(moved), lengths, path)
    np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


@pytest.mark.parametrize("path", ["plain", "interpret"])
def test_decode_forward_paged_matches_full_forward(path, monkeypatch):
    """The whole decode step of the tiny model over a pool that holds
    real sequences, pages of different lanes interleaved: the logits at
    the fed position against the Flax model's full forward over the
    sequence (no cache, no paging: an independent program), the new K/V
    against the prompt forward's at that position."""
    from ray_tpu.models import gpt2

    if path == "interpret":
        monkeypatch.setattr(
            attention, "paged_decode_attention",
            lambda *a, block_size: kernel.paged_decode_attention_kernel(
                *a, block_size=block_size, interpret=True),
        )
    cfg = gpt2.GPT2Config.tiny(dtype=jnp.float32)
    params = gpt2.init_params(cfg, rng=jax.random.PRNGKey(0))
    lens = [37, 0, 120, 16]
    B, pages = len(lens), cfg.max_seq_len // BS
    rng = np.random.default_rng(5)
    # each lane's sequence up to and with its fed token; attention is
    # causal, so what lies beyond it in the row moves nothing before it
    seqs = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, max(lens) + 1)), jnp.int32)
    _, k_all, v_all = gpt2.prefill_forward(params, cfg, seqs)  # [L, B, T, H, Dh]
    tables = np.zeros((B, pages), np.int32)
    order = rng.permutation(np.arange(1, B * pages + 1))
    for b in range(B):
        tables[b] = order[b * pages:(b + 1) * pages]
    idx, _ = _slots(tables, lens, cfg.max_seq_len)
    slots = (B * pages + 1) * BS
    kp, vp = (rng.standard_normal((cfg.n_layer, slots, cfg.d_model)).astype(np.float32)
              for _ in range(2))  # what a lane does not hold is noise, not zeros
    for b, n in enumerate(lens):
        kp[:, idx[b, :n]] = np.asarray(k_all[:, b, :n]).reshape(cfg.n_layer, n, cfg.d_model)
        vp[:, idx[b, :n]] = np.asarray(v_all[:, b, :n]).reshape(cfg.n_layer, n, cfg.d_model)
    rows, pos = np.arange(B), jnp.asarray(lens, jnp.int32)
    logits, k_new, v_new = gpt2.decode_forward_paged(
        params, cfg, seqs[rows, pos], jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), pos, BS)
    want = gpt2.GPT2(cfg).apply({"params": params}, seqs)[rows, pos]
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want), atol=2e-4, rtol=2e-4)
    assert np.array_equal(np.argmax(logits, -1), np.argmax(want, -1))
    np.testing.assert_allclose(np.asarray(k_new), np.asarray(k_all[:, rows, pos]), atol=2e-5)
    np.testing.assert_allclose(np.asarray(v_new), np.asarray(v_all[:, rows, pos]), atol=2e-5)


@pytest.mark.parametrize("n_head, d_head, block_size, dtype, takes", [
    (20, 64, 16, jnp.bfloat16, True),    # GPT-2-large in the serve cells
    (12, 64, 16, jnp.bfloat16, True),
    (4, 32, 16, jnp.float32, True),
    (4, 32, 8, jnp.float32, True),       # a page is one float32 sublane tile
    (4, 32, 8, jnp.bfloat16, False),     # half a bf16 tile: the plain path
    (4, 32, 4, jnp.float32, False),
    (4, 32, 48, jnp.bfloat16, False),    # a compute block would split a page
    (3, 32, 16, jnp.bfloat16, False),    # a row of all heads is not whole lanes
])
def test_kernel_takes_only_shapes_its_tiling_can(n_head, d_head, block_size, dtype, takes):
    assert kernel.kernel_takes(n_head, d_head, block_size, dtype) is takes


# ----------------------------------------------------------------------
# the walk the four kernels share
# ----------------------------------------------------------------------
def test_work_list_is_the_owners_blocks_in_order_and_skips_the_empty():
    counts = jnp.asarray([0, 3, 0, 1], jnp.int32)

    def body(cnt_ref, owner_ref, blk_ref, total_ref):
        total_ref[0] = paged_walk.list_work(cnt_ref.shape[0], lambda owner: cnt_ref[owner], owner_ref, blk_ref)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    owner, blk, total = pl.pallas_call(
        body, in_specs=[smem], out_specs=[smem] * 3, interpret=True,
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.int32) for n in (4, 4, 1)])(counts)
    assert int(total[0]) == 4
    assert owner.tolist() == [1, 1, 1, 3] and blk.tolist() == [0, 1, 2, 0]


def _dense_walk(rand, pools, tables, lens):
    q, k, v = (rand(len(lens), 4, 32) for _ in range(3))
    return (kernel.paged_decode_attention_kernel, attention.paged_decode_attention,
            (q, k, v, *pools, 1, tables, lens), dict(block_size=BS))


def _gqa_walk(rand, pools, tables, lens):
    B = len(lens)
    return (gqa_kernel.gqa_paged_decode_attention_kernel, attention.gqa_paged_decode_attention,
            (rand(B, 2, 8, 128), rand(B, 2, 128), rand(B, 2, 128), *pools, 1, tables, lens), dict(block_size=BS))


def _mla_walk(rand, pools, tables, lens):
    B = len(lens)
    return (mla_kernel.mla_paged_decode_attention_kernel, attention.mla_paged_decode_attention,
            (0.2 * rand(B, 8, 64), rand(B, 64), *pools, 1, tables, lens), dict(block_size=BS, v_width=32))


def _sparse_walk(rand, pools, tables, lens):
    """Every (lane, K/V head) pair chooses all the blocks its lane holds, in
    order, in a list with room for more: the pair's compute blocks are
    then the lane's."""
    B, G, sb = len(lens), 2, 64
    counts = -(-np.asarray(lens) // sb)
    S = tables.shape[1] * BS // sb
    blocks = np.where(np.arange(S) < counts[:, None], np.arange(S), 0)[:, None, :].repeat(G, 1).astype(np.int32)
    ppb = sb // BS
    pages = np.take_along_axis(np.asarray(tables)[:, None, :].repeat(G, 1),
                               (blocks[..., None] * ppb + np.arange(ppb)).reshape(B, G, -1), axis=2)
    return (sparse_kernel.sparse_paged_decode_attention_kernel, attention.sparse_paged_decode_attention,
            (rand(B, G, 8, 32), rand(B, G, 32), rand(B, G, 32), *pools, 1, jnp.asarray(pages), jnp.asarray(blocks),
             jnp.asarray(counts[:, None].repeat(G, 1), jnp.int32), lens), dict(block_size=BS, sparse_block=sb))


# kernel -> (its module, the columns of a pool row, pools, its entry and gather with their arguments)
WALKS = {
    "dense": (kernel, 128, 2, _dense_walk),
    "gqa": (gqa_kernel, 256, 2, _gqa_walk),  # 1 KB a row in float32: blocks of _BLOCK_POSITIONS
    "mla": (mla_kernel, 64, 1, _mla_walk),
    "sparse": (sparse_kernel, 64, 2, _sparse_walk),
}


def _walk_edges(what, bk):
    """Cached positions a lane, by what of the walk they hit; bk the
    positions of the kernel's compute block."""
    if what == "one_item":   # the first start and no other
        return [0, 7, 0]
    if what == "no_item":    # nothing starts, nothing is walked
        return [0, 0]
    if what == "long_lane":  # a lane longer than two compute blocks, before an empty one and a short one
        return [2 * bk + 3, 0, 7]
    # a lane that ends on a compute block's edge, a lane of length 0
    # between two that run, a lane one position past the edge
    return [bk, 0, bk + 1, 5]


def _assigned(lens, width, n_pools, per_lane, seed):
    """Pools of noise and block tables that hand the lanes' pages out
    in an order of ``seed``'s; the rows the lanes hold are the same for
    every seed, everything else in the pools differs."""
    held = np.random.default_rng(0).standard_normal((n_pools, LAYERS, len(lens) * per_lane * BS, width))
    rng = np.random.default_rng(seed)
    tables = np.zeros((len(lens), per_lane), np.int32)
    pools = rng.standard_normal((n_pools, LAYERS, (len(lens) * per_lane + 1) * BS, width))
    order = rng.permutation(np.arange(1, len(lens) * per_lane + 1))
    for b, n in enumerate(lens):
        for j in range(-(-n // BS)):
            page = tables[b, j] = order[b * per_lane + j]
            logical = (b * per_lane + j) * BS
            pools[:, :, page * BS:(page + 1) * BS] = held[:, :, logical:logical + BS]
    return [jnp.asarray(p, jnp.float32) for p in pools], jnp.asarray(tables)


@pytest.mark.parametrize("what", ["every_edge", "one_item", "no_item", "long_lane", "other_pages"])
@pytest.mark.parametrize("name", list(WALKS))
def test_every_kernel_walks_its_pages_as_its_gather_reads_them(name, what):
    """Each kernel on the shared walk (interpret mode, float32) at the
    walk's edges against its own gather reference; and the same lanes
    on other physical pages, in pools that differ everywhere else, to
    the bit: the order of summation depends on positions only."""
    module, width, n_pools, flavour = WALKS[name]
    lens = _walk_edges(what, module._BLOCK_POSITIONS)
    per_lane = (-(-max(lens) // 64) + 1) * 64 // BS  # whole chosen blocks of the sparse kernel, one to spare

    def run(seed, interpret):
        pools, tables = _assigned(lens, width, n_pools, per_lane, seed)
        rng = np.random.default_rng(7)
        entry, gather, args, sizes = flavour(
            lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32), pools, tables,
            jnp.asarray(lens, jnp.int32))
        return np.asarray(entry(*args, **sizes, interpret=True) if interpret else gather(*args, **sizes))

    got = run(1, True)
    assert np.isfinite(got).all()
    if what == "other_pages":
        np.testing.assert_array_equal(got, run(2, True))
    else:
        np.testing.assert_allclose(got, run(1, False), atol=2e-5, rtol=2e-5)


# ----------------------------------------------------------------------
# a whole block's pages as straight-line code, any other a page at a time
# ----------------------------------------------------------------------
def _block_lens(what, bk):
    """Cached positions a lane, by the pages its blocks hold; bk the
    positions of the kernel's compute block (n pages)."""
    return {
        "no_position": [0, 0],                      # no item: neither path
        "part_of_a_page": [BS - 3, 0, 1],           # one partial block a lane: the loop alone
        "n_pages": [bk, bk - BS + 1],               # a lane's only block is whole; so is one whose last page is part full
        "n_pages_and_a_part": [bk + BS + 3, 5],     # a whole block, then the loop, then the next lane's
        "whole_blocks": [2 * bk + BS, 0, bk],       # whole after whole, across an empty lane
    }[what]


@pytest.mark.parametrize("what", ["no_position", "part_of_a_page", "n_pages", "n_pages_and_a_part", "whole_blocks"])
@pytest.mark.parametrize("name", list(WALKS))
def test_a_whole_block_s_straight_line_copies_give_the_page_loop_s_bits(name, what, monkeypatch):
    """Each kernel (interpret mode, float32) with the walk as it is, a
    block that holds all its pages copied by ``whole_block``, against
    the same kernel with every block copied a page at a time
    (``page_loop``, the partial block's path, given the whole ones too):
    the same bits, and the same again on other physical pages.  What
    interpret mode cannot show is that a whole block's one wait a
    stream waits for all its copies: ``scripts/gqa_decode_check.py``
    holds that to the gather path on the chip."""
    module, width, n_pools, flavour = WALKS[name]
    lens = _block_lens(what, module._BLOCK_POSITIONS)
    per_lane = (-(-max(lens) // 64) + 1) * 64 // BS
    straight = []

    def run(seed):
        pools, tables = _assigned(lens, width, n_pools, per_lane, seed)
        rng = np.random.default_rng(7)
        entry, _, args, sizes = flavour(
            lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32), pools, tables,
            jnp.asarray(lens, jnp.int32))
        # a jit keeps what it traced: the function under the entry's own is traced anew
        return np.asarray(jax.jit(functools.partial(entry.__wrapped__, **sizes, interpret=True))(*args))

    def noted(*a, **kw):
        straight.append(kw["n"])
        return whole_block(*a, **kw)

    whole_block = paged_walk.whole_block
    monkeypatch.setattr(paged_walk, "whole_block", noted)
    got = run(1)
    assert straight and set(straight) == {module._BLOCK_POSITIONS // BS}  # traced, n pages a block
    if what in ("n_pages_and_a_part", "whole_blocks"):
        np.testing.assert_array_equal(got, run(2))
    monkeypatch.setattr(paged_walk, "whole_block", paged_walk.page_loop)
    del straight[:]
    looped = run(1)
    assert not straight and np.isfinite(got).all()
    np.testing.assert_array_equal(got, looped)


@pytest.mark.parametrize("block_size, block_positions", [(64, 512), (16, 128), (64, 4096), (4, 512)])
def test_blocks_counts_the_walk_s_blocks_and_the_whole_ones(block_size, block_positions):
    """``paged_walk.blocks`` against a count in Python over drawn
    lengths: a lane walks a block for every ``n`` pages it holds or part
    of them, and a block is whole when the lane holds all ``n``."""
    rng = np.random.default_rng(block_size + block_positions)
    lens = np.concatenate([rng.integers(0, 5 * block_positions, 61),
                           [0, 1, block_size, block_positions - block_size, block_positions - block_size + 1,
                            block_positions, block_positions + 1, 2 * block_positions]])
    n = block_positions // block_size
    held = [-(-int(length) // block_size) for length in lens]
    walked, whole = paged_walk.blocks(jnp.asarray(lens, jnp.int32), block_size, block_positions)
    assert int(walked) == sum(-(-pages // n) for pages in held)
    assert int(whole) == sum(pages // n for pages in held) > 0
    assert walked.dtype == whole.dtype == jnp.int32


# ----------------------------------------------------------------------
# the grouped-query kernel's block: a constant number of bytes
# ----------------------------------------------------------------------
@pytest.mark.parametrize("kv_heads, n_rep, positions", [(1, 20, 2048), (2, 4, 1024), (2, 16, 1024), (4, 8, 512),
                                                        (8, 4, 512)])
def test_gqa_kernel_block_is_read_off_the_pool_s_row_and_its_scratch_fits_the_vmem_a_kernel_gets(
        kv_heads, n_rep, positions):
    """The served shapes (Jamba's one K/V head of 128 in bf16, ZAYA's and
    Nemotron's two, Mellum's four, Granite's eight): a compute block is
    512 positions from 1 KB a row up and as many as keep a buffer at
    512 KB under it; the scratch as the chip lays it out (a group of
    fewer heads than a tile padded to one) and three ``[heads,
    positions]`` float32 tiles of scores beside it leave more than half
    of the 16 MiB a kernel gets unasked to its operands."""
    pool = jax.ShapeDtypeStruct((3, 64 * 100, kv_heads * 128), jnp.bfloat16)
    assert gqa_kernel.block_positions(pool) == positions
    assert max(positions * kv_heads * 128 * 2, 2**19) == (2**20 if kv_heads == 8 else 2**19)
    padded = -(-n_rep // 16) * 16
    scratch = gqa_kernel.vmem_scratch(kv_heads, padded, 128, positions, jnp.bfloat16)
    assert scratch[0] == scratch[1] == ((2, positions, kv_heads * 128), jnp.bfloat16)
    scores = 3 * kv_heads * padded * positions * 4
    assert paged_walk.tiled_bytes(scratch) + scores < 8 * 2**20
    # a float32 pool's row is twice the bytes: a block of half the positions, never under 512
    wide = jax.ShapeDtypeStruct(pool.shape, jnp.float32)
    assert gqa_kernel.block_positions(wide) == max(512, positions // 2)


@pytest.mark.parametrize("kv_heads, positions", [(1, 2048), (2, 1024)])
def test_gqa_kernel_at_a_narrow_row_s_block_reads_what_its_gather_reads(kv_heads, positions):
    """The kernel (interpret mode, bf16) where a row is under 1 KB and a
    compute block longer than 512 positions, at the block's edges (a
    lane ending on it, an empty lane, a lane one position past it, a
    short one, a lane of two blocks and a part) against the gather."""
    lens = [positions, 0, positions + 1, 5, 2 * positions + BS + 3]
    per_lane = -(-max(lens) // BS) + 1
    pools, tables = _assigned(lens, kv_heads * 128, 2, per_lane, 1)
    pools = [p.astype(jnp.bfloat16) for p in pools]
    assert gqa_kernel.block_positions(pools[0]) == positions
    rng = np.random.default_rng(7)

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    B = len(lens)
    args = (rand(B, kv_heads, 16, 128), rand(B, kv_heads, 128), rand(B, kv_heads, 128), *pools, 1, tables,
            jnp.asarray(lens, jnp.int32))
    got = np.asarray(gqa_kernel.gqa_paged_decode_attention_kernel(*args, block_size=BS, interpret=True), np.float32)
    want = np.asarray(attention.gqa_paged_decode_attention(*args, block_size=BS), np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("model, module, config, block_size", [
    ("zaya1_tiny", "ray_tpu.models.zaya", "ZayaConfig", 4),
    ("nemotron_3_nano_tiny", "ray_tpu.models.nemotron_h", "NemotronHConfig", 8),
])
def test_engine_counts_the_blocks_its_decode_steps_walk(model, module, config, block_size, monkeypatch):
    """``stats()`` of a tiny engine of two grouped-query families after
    decode steps over a lane that grows past a compute block: the
    blocks its kernel calls walk and those of them that hold all their
    pages, a Python count over the steps' lengths and the paged layers.
    The tiny presets stop at 512 positions, under one block of their
    narrow rows: the preset is given room for a block and a part."""
    import asyncio
    import dataclasses
    import importlib

    from ray_tpu.serve.llm import LLMConfig, LLMEngine
    from ray_tpu.serve.llm.engine import FINISHED

    cls = getattr(importlib.import_module(module), config)
    preset = getattr(cls, model)
    monkeypatch.setattr(cls, model, classmethod(
        lambda _, **kw: dataclasses.replace(preset(**kw), max_seq_len=2304, prefill_chunk=256)))
    n_prompt, n_out = 2044, 7

    async def main():
        eng = LLMEngine(LLMConfig(model=model, max_batch_size=2, num_blocks=2304 // block_size + 8,
                                  block_size=block_size, seed=5))
        req = await eng.add_request(np.random.default_rng(6).integers(0, 256, n_prompt).tolist(), max_tokens=n_out)
        while (await req.out.get()) is not FINISHED:
            pass
        stats, cfg, pool = eng.stats(), eng.model_cfg, eng.cache["k_pages"]
        await eng.stop()
        return stats, cfg, pool

    stats, cfg, pool = asyncio.run(main())
    bk = gqa_kernel.block_positions(pool)
    assert bk == 2048 and n_prompt < bk < n_prompt + n_out - 1
    n = bk // block_size
    held = [-(-length // block_size) for length in range(n_prompt, n_prompt + n_out - 1)]  # the decode steps' lanes
    assert stats["kv_blocks_walked"] == pool.shape[0] * sum(-(-pages // n) for pages in held)
    assert stats["kv_blocks_whole"] == pool.shape[0] * sum(pages // n for pages in held)
    assert stats["kv_blocks_walked"] > stats["kv_blocks_whole"] > 0


# ----------------------------------------------------------------------
# the latent kernel's block: large, folded in parts under one maximum
# ----------------------------------------------------------------------
def _mla_lens(what):
    bk, part = mla_kernel._BLOCK_POSITIONS, mla_kernel._PART_POSITIONS
    if what == "part_edges":  # a last block of one part, of two, of all but one, of every one; a second block of two
        return [part - 1, part, part + 1, bk - part, bk - part + 1, bk + part + 1]
    if what == "short_lanes":  # a lane shorter than one page, an empty lane between two that run
        return [BS - 3, 0, 2 * BS + 1]
    return _walk_edges(what, bk)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("what", ["every_edge", "part_edges", "short_lanes", "long_lane", "other_pages"])
def test_mla_kernel_folds_a_block_in_parts_as_its_gather_reads_them(what, dtype):
    """The latent kernel (interpret mode) at its own block's edges, in
    float32 and in bf16, against the gather: a lane ending on a compute
    block's edge and one position past it, on a part's edge and either
    side of it (each count of parts a last block can hold), shorter
    than a page, empty between two that run, longer than two blocks;
    and the same lanes on other physical pages to the bit."""
    lens = _mla_lens(what)
    per_lane = -(-max(lens) // BS) + 1

    def run(seed, interpret):
        pools, tables = _assigned(lens, 64, 1, per_lane, seed)
        rng = np.random.default_rng(7)
        entry, gather, args, sizes = _mla_walk(
            lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype), [p.astype(dtype) for p in pools], tables,
            jnp.asarray(lens, jnp.int32))
        out = entry(*args, **sizes, interpret=True) if interpret else gather(*args, **sizes)
        return np.asarray(out, np.float32)

    got = run(1, True)
    assert np.isfinite(got).all()
    if what == "other_pages":
        np.testing.assert_array_equal(got, run(2, True))
    else:
        tol = 2e-5 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(got, run(1, False), atol=tol, rtol=tol)


def test_mla_kernel_scratch_at_the_served_shape_fits_the_vmem_a_kernel_gets():
    """Mistral-Small-4's decode shape (32 heads over rows of 384 stored
    columns, 256 of them values, bf16): two buffers of a compute block,
    a lane's queries and the softmax state, laid out in whole tiles,
    leave more than half of the 16 MiB a kernel gets unasked to the
    operands the call holds there whole; a float32 pool's buffers would
    not, and the entry gathers instead."""
    H, W, V = 32, 384, 256
    shapes = mla_kernel.vmem_scratch(H, W, V, jnp.bfloat16)
    assert shapes[0] == ((2, mla_kernel._BLOCK_POSITIONS, W), jnp.bfloat16)
    by_hand = 2 * mla_kernel._BLOCK_POSITIONS * W * 2 + H * W * 2 + 2 * H * 128 * 4 + H * V * 4
    assert mla_kernel.vmem_scratch_bytes(H, W, V, jnp.bfloat16) == by_hand < 8 * 2**20
    assert mla_kernel.kernel_takes(H, W, V, 64, jnp.bfloat16)
    assert not mla_kernel.kernel_takes(H, W, V, 64, jnp.float32)

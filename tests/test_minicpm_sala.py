"""MiniCPM-SALA through the serving path, held to the plain float32
reference (``benchmark/reference_minicpm_sala.py``) at the tiny preset on
the CPU: chunks of 64 tokens, ``dense_len`` 64, blocks of 16 of which 4
are kept, pages of 8, so a prompt of 200 tokens takes four chunks, is
past ``dense_len`` and drops most of its blocks.

The tolerance, 3e-4 absolute on logits of size about 0.1: program and
reference are both float32 here and differ in the ORDER of their sums
(the program's online softmax over key blocks and its chunked scan of the
recurrence, carried from chunk to chunk through the lane's state, against
the reference's one softmax a query and its loop over positions): 4e-8
to 1.3e-6 seen, 7.5e-5 where a near-tie of two blocks' scores falls the
other way.  A state that is not carried from chunk to chunk, a missing
gate or a missing rotation move logits by 1e-2 and more:
``test_a_broken_model_fails_the_tolerance`` shows each.  A selection that
keeps the forced blocks alone moves them by 2.5e-4 only (attention under
random weights is near uniform, and what a dropped block would have added
is small): logits cannot hold the selection, so it is held block for
block (``test_decode_selection_...``, ``test_prefill_selection_...``).
"""

import asyncio
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import reference_minicpm_sala as reference  # noqa: E402
from ray_tpu.models import minicpm_sala as sala  # noqa: E402
from ray_tpu.ops import block_sparse, lightning  # noqa: E402
from ray_tpu.ops.attention import sparse_paged_decode_attention  # noqa: E402
from ray_tpu.serve.llm import LLMConfig, LLMEngine  # noqa: E402
from ray_tpu.serve.llm.engine import FINISHED  # noqa: E402

TOL = 3e-4
BS = 8  # positions a page
CFG = sala.MiniCPMSalaConfig.minicpm_sala_tiny(dtype=jnp.float32)
DENSE = CFG.dense_len  # 64


def _engine(**kw):
    kw = {"max_batch_size": 4, "num_blocks": 200, "block_size": BS, "seed": 5, **kw}
    return LLMEngine(LLMConfig(model="minicpm_sala_tiny", **kw))


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n).astype(np.int32)


def _distance(a, b):
    return float(np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32)).max())


async def _drain(req):
    toks = []
    while True:
        ev = await req.out.get()
        if ev is FINISHED:
            return toks
        toks.append(ev["token"])


def _forwards():
    """The family's two forwards, jitted (cfg and the page size static),
    as functions of their own: jit's cache goes by the function, and a
    test that breaks the model must trace it again."""
    return (jax.jit(lambda *a: sala.prefill_chunk(*a), static_argnums=(1, 8)),
            jax.jit(lambda *a: sala.decode_chosen(*a), static_argnums=(1, 6)))


FORWARDS = _forwards()


def _replay(eng, seq, n_prompt, lane=1, cfg=None, forwards=FORWARDS):
    """The sequence through the engine's own cache by the engine's own
    programs, and the logits of the family's forwards on the way: the
    prompt in chunks (the last chunk's logits are the prompt's), then
    one decode step a position in lane ``lane``.  -> (logits
    [len(seq) - n_prompt + 1, V] for positions n_prompt - 1 .., what
    each decode step's sparse layers chose)."""
    cfg = cfg or eng.model_cfg
    bm, bs, lanes = eng.bm, eng.bm.block_size, eng.config.max_batch_size
    pages = bm.blocks_needed(eng.max_ctx)
    rid = f"replay-{len(seq)}-{lane}"
    bm.allocate(rid, len(seq))
    most, logits, chose = eng._spec.prefill_chunk, [], []
    for start in range(0, n_prompt, most):
        m = min(most, n_prompt - start)
        bucket = eng._prefill_bucket(m, most)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :m] = seq[start:start + m]
        bm.advance(rid, m)
        last, table = np.array([m - 1], np.int32), bm.block_table(rid, pages)
        out = forwards[0](eng.params, cfg, eng.cache, toks, np.int32(start), last, table, np.int32(lane), bs)
        eng._run_on_cache(eng._prefill_jit, toks, bm.phys_indices(rid, start + m, bucket, start=start), last,
                          np.zeros(1, np.float32), eng._next_rng(), np.int32(start), table, np.int32(lane))
    logits.append(out[0][0])
    for pos in range(n_prompt, len(seq)):
        tok, lengths, write = (np.zeros(lanes, np.int32) for _ in range(3))
        tables = np.zeros((lanes, pages), np.int32)
        tok[lane], lengths[lane], tables[lane] = seq[pos], pos, bm.block_table(rid, pages)
        bm.advance(rid, 1)
        write[lane] = bm.phys_index(rid, pos)
        out = forwards[1](eng.params, cfg, eng.cache, tok, tables, lengths, bs)
        logits.append(out[0][lane])
        chose.append(jax.tree.map(lambda a: np.asarray(a[:, lane]), out[-1]))
        eng._run_on_cache(eng._decode_jit, tok, lengths, tables, write, np.zeros(lanes, np.float32),
                          eng._next_rng())
    bm.free(rid)
    return np.stack([np.asarray(x) for x in logits]), chose


@pytest.fixture(scope="module")
def engine():
    """One idle engine for the replays: its programs compile once, and
    every replay finds in lane 1 the state the one before it left."""
    return _engine()


# ----------------------------------------------------------------------
# the model against the reference: logits over the whole vocabulary
# ----------------------------------------------------------------------
@pytest.mark.parametrize("n_prompt, n_new", [
    (10, 6),            # one chunk, dense
    (DENSE - 1, 4),     # one token under dense_len: the prompt dense, the answer crosses over
    (DENSE + 1, 4),     # one over: two chunks, the last query sparse
    (200, 8),           # four chunks; most blocks dropped
    (3 * 64, 5),        # whole chunks only: no pad tail
])
def test_chunked_prefill_then_paged_decode_match_the_reference(engine, n_prompt, n_new):
    eng = engine
    seq = _tokens(n_prompt + n_new, seed=n_prompt)
    got, _ = _replay(eng, seq, n_prompt)
    want, keep = reference.full_logits(eng.params, jnp.asarray(seq), eng.model_cfg)
    assert _distance(got, np.asarray(want)[n_prompt - 1:]) < TOL
    assert eng.bm.leak_report()["blocks_in_use"] == 0
    if n_prompt >= 200:  # the case means something: the reference dropped blocks
        assert float(np.asarray(keep)[:, -1].mean()) < 0.5


@pytest.mark.parametrize("broken", ["no_gate", "no_rotation", "state_not_carried"])
def test_a_broken_model_fails_the_tolerance(monkeypatch, broken):
    """What the tolerance catches, each by 30 times and more."""
    eng = _engine()
    seq = _tokens(206, seed=1)
    want = np.asarray(reference.full_logits(eng.params, jnp.asarray(seq), eng.model_cfg)[0])[199:]
    cfg = eng.model_cfg
    if broken == "no_gate":
        monkeypatch.setattr(sala, "_gated", lambda o, z: o)
    elif broken == "no_rotation":
        monkeypatch.setattr(sala, "rope", lambda x, pos, theta: x)
    else:  # every chunk starts from zeros: nothing of the earlier chunks reaches the lightning layers
        chunk = sala.lightning_chunk
        monkeypatch.setattr(sala, "lightning_chunk",
                            lambda q, k, v, state, n, slopes: chunk(q, k, v, jnp.zeros_like(state), n, slopes))
    got, _ = _replay(eng, seq, 200, cfg=cfg, forwards=_forwards())  # traced with what is broken
    assert _distance(got, want) > 30 * TOL


# ----------------------------------------------------------------------
# selection, block for block
# ----------------------------------------------------------------------
def test_decode_selection_is_the_reference_s_block_for_block(engine):
    eng = engine
    n_prompt, n_new = 150, 40
    seq = _tokens(n_prompt + n_new, seed=9)
    _, chose = _replay(eng, seq, n_prompt)
    keep = np.asarray(reference.full_logits(eng.params, jnp.asarray(seq), eng.model_cfg)[1])  # [Lp, T, G, NB]
    dropped = 0
    for step, (blocks, counts) in enumerate(chose):
        t = n_prompt + step
        for layer in range(keep.shape[0]):
            for g in range(CFG.n_kv_head):
                got = sorted(blocks[layer, g, :counts[layer, g]].tolist())
                assert got == np.flatnonzero(keep[layer, t, g]).tolist(), (t, layer, g)
                dropped += t // CFG.block_size + 1 - len(got)
    assert dropped > 0


@pytest.mark.parametrize("t", [5, 63, 64, 100, 255])
def test_prefill_selection_is_the_reference_s_token_by_token(t):
    """``block_keep`` (the prefill's mask) against the reference's
    ranking, on one token's queries over random compressed keys."""
    rng = np.random.default_rng(t)
    G, R, d, C = 2, 2, 16, 256
    q = jnp.asarray(rng.normal(size=(G, R, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(C, G, d)), jnp.float32)
    ck = block_sparse.compress_keys(k, CFG)
    n_blocks = C // CFG.block_size
    s = jnp.einsum("grd,jgd->grj", q, ck) / np.sqrt(d)
    block_scores = block_sparse.block_scores(s, jnp.asarray(t), n_blocks, CFG)
    got = block_sparse.block_keep(block_scores, CFG)
    sp = {key: getattr(CFG, key) for key in reference._KEYS}
    windows = (C - CFG.kernel_size) // CFG.kernel_stride + 1
    want = reference.keep_blocks(q, ck[:windows], t, dict(sp, n_blocks=n_blocks))
    if t < DENSE:  # under dense_len every block there is, whatever it scores
        want = jnp.broadcast_to(jnp.arange(n_blocks) * CFG.block_size <= t, want.shape)
    assert np.array_equal(np.asarray(got), np.asarray(want)), np.asarray(block_scores)
    blocks, counts = block_sparse.block_choice(block_scores, jnp.asarray(t), block_sparse.max_choice(CFG), CFG)
    for g in range(G):
        assert sorted(np.asarray(blocks)[g, :int(counts[g])].tolist()) == np.flatnonzero(np.asarray(got)[g]).tolist()


@functools.partial(jax.jit, static_argnames="sp")
def _parent_chunk_attention(q, ctx_k, ctx_v, ck, start, n_valid, sp):
    """``sparse_chunk_attention`` as it stood before a tile's selection
    went by its position: every tile scores every window the context has
    room for, in one piece.  -> (o, blocks kept, blocks cached)."""
    T, G, R, d = q.shape
    tq, kb = min(T, block_sparse._Q_TILE), block_sparse.K_BLOCK
    per, n_blocks, scale = kb // sp.block_size, ctx_k.shape[0] // sp.block_size, 1.0 / (d ** 0.5)
    ck = ck.astype(q.dtype)

    def tile(xs):
        qt, off = xs
        t = start + off + jnp.arange(tq)
        s = jnp.einsum("tgrd,jgd->tgrj", qt, ck, preferred_element_type=jnp.float32) * scale
        keep = block_sparse.block_keep(block_sparse.block_scores(s, t, n_blocks, sp), sp)
        real = off + jnp.arange(tq) < n_valid
        kept = jnp.where(real[:, None], keep.sum(-1), 0).sum()
        cached = G * jnp.where(real, block_sparse.blocks_cached(t, sp), 0).sum()

        def block(i, carry):
            m, l, acc = carry
            kblk = jax.lax.dynamic_slice_in_dim(ctx_k, i * kb, kb)
            vblk = jax.lax.dynamic_slice_in_dim(ctx_v, i * kb, kb)
            sc = jnp.einsum("tgrd,kgd->tgrk", qt, kblk, preferred_element_type=jnp.float32) * scale
            mask = jnp.repeat(jax.lax.dynamic_slice_in_dim(keep, i * per, per, axis=2), sp.block_size, axis=2)
            mask = (mask & (i * kb + jnp.arange(kb) <= t[:, None, None]))[:, :, None, :]
            sc = jnp.where(mask, sc, block_sparse.NEG)
            m_new = jnp.maximum(m, sc.max(-1, keepdims=True))
            p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
            alpha = jnp.exp(m - m_new)
            acc = alpha * acc + jnp.einsum("tgrk,kgd->tgrd", p.astype(vblk.dtype), vblk,
                                           preferred_element_type=jnp.float32)
            return m_new, alpha * l + p.sum(-1, keepdims=True), acc

        init = (jnp.full((tq, G, R, 1), block_sparse.NEG, jnp.float32), jnp.zeros((tq, G, R, 1), jnp.float32),
                jnp.zeros((tq, G, R, d), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, (start + off + tq + kb - 1) // kb, block, init)
        return (acc / l).astype(q.dtype), kept, cached

    offs = jnp.arange(T // tq, dtype=jnp.int32) * tq
    o, kept, cached = jax.lax.map(tile, (q.reshape(T // tq, tq, G, R, d), offs))
    return o.reshape(T, G, R, d), kept.sum(), cached.sum()


@pytest.mark.parametrize("start, selected", [
    (0, 0),  # the tile wholly under dense_len: nothing is scored
    (32, 1),  # queries 32..95 straddle it
    (4032, 1),  # wholly past it, half the context's room not reached: two of its four steps of windows
])
def test_a_chunk_s_tile_selects_by_its_position_and_reads_what_it_always_read(start, selected):
    """The prefill's attention against its form before this selection,
    kept above.  The output depends on the selection through the mask
    alone, so the same output to the bit is the same blocks kept, block
    for block (the normaliser's sum, taken a step of windows at a time,
    may differ in its last bits: on these seeds no block changes sides)."""
    rng = np.random.default_rng(start)
    T, G, R, d, C = 64, 2, 2, 16, 8192
    n_valid = 50
    q = jnp.asarray(rng.normal(size=(T, G, R, d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(C, G, d)), jnp.float32) for _ in range(2))
    ck = block_sparse.compress_keys(k, CFG)
    assert ck.shape[0] == 4 * block_sparse._W_BLOCK
    o, counts = block_sparse.sparse_chunk_attention(q, k, v, ck, jnp.int32(start), jnp.int32(n_valid), CFG)
    want_o, kept, cached = _parent_chunk_attention(q, k, v, ck, jnp.int32(start), jnp.int32(n_valid), CFG)
    assert np.array_equal(np.asarray(o), np.asarray(want_o))
    assert counts.tolist() == [int(kept), int(cached), selected, 1]
    assert (kept < cached) == (start > 0)  # under dense_len every block before a query is read


@pytest.mark.parametrize("kernel, stride, block", [(32, 16, 64), (16, 16, 64), (48, 16, 32), (64, 16, 64)])
def test_blocks_score_the_same_a_step_of_windows_at_a_time(kernel, stride, block):
    """``_block_scores``, the one window-to-block maximum: a chunk's tile
    hands it a step of windows with the last of the step before, decode
    every window in one piece; a maximum is exact in any order, so the
    two agree to the bit whether 0, 1, 2 or 3 windows reach in."""
    import types

    sp = types.SimpleNamespace(kernel_size=kernel, kernel_stride=stride, block_size=block)
    r, lead, n_blocks, step = block // stride, kernel // stride - 1, 24, 8
    p = jnp.asarray(np.random.default_rng(kernel + block).random((3, 2, r * n_blocks)), jnp.float32)
    whole = block_sparse._block_scores(p, n_blocks, sp)
    before, parts = jnp.zeros((3, 2, lead), jnp.float32), []
    for i in range(0, n_blocks, step):
        part = p[..., r * i:r * (i + step)]
        parts.append(block_sparse._block_scores(part, step, sp, before))
        before = part[..., r * step - lead:]
    assert np.array_equal(np.asarray(whole), np.concatenate(parts, -1))
    for b in (0, 5, n_blocks - 1):  # and it is the maximum over the windows that overlap the block
        lo = max(r * b - lead, 0)
        assert np.array_equal(np.asarray(whole[..., b]), np.asarray(p[..., lo:r * (b + 1)].max(-1)))


@pytest.mark.parametrize("n_blocks", [2, 16, 48])
def test_block_keep_is_top_k_s_mask(n_blocks):
    """``block_keep`` finds the k-th highest score by a sort of a
    transposed copy; the mask is ``lax.top_k``'s to the bit, ties, the
    forced blocks' infinity and the unreached blocks' -1 included."""
    rng = np.random.default_rng(n_blocks)
    score = np.round(rng.random((7, 2, n_blocks)), 1).astype(np.float32)  # tenths: ties at the k-th
    score[:, :, :CFG.init_blocks] = np.inf
    score[:, :, n_blocks - n_blocks // 4:] = -1.0
    score[0] = -1.0
    score[0, :, 0] = np.inf
    kth = jax.lax.top_k(jnp.asarray(score), min(CFG.topk, n_blocks))[0][..., -1:]
    want = (score >= np.asarray(kth)) & (score >= 0)
    assert np.array_equal(np.asarray(block_sparse.block_keep(jnp.asarray(score), CFG)), want)
    assert want[0].sum() == 2  # where nothing is reached but a forced block, that block a K/V head
    assert n_blocks < 48 or want.sum(-1).max() > CFG.topk  # a tie at the k-th keeps both


# ----------------------------------------------------------------------
# the ops
# ----------------------------------------------------------------------
@pytest.mark.parametrize("T, n_valid", [(8, 8), (16, 11), (512, 300), (512, 512)])
def test_lightning_chunk_is_the_recurrence(T, n_valid):
    """The chunked scan against a loop of ``lightning_step``s, from a
    state that is not zero; pads leave the state alone."""
    rng = np.random.default_rng(T + n_valid)
    H, d = 4, 16
    q, k, v = (jnp.asarray(rng.normal(size=(T, H, d)), jnp.float32) for _ in range(3))
    s0 = jnp.asarray(rng.normal(size=(H, d, d)), jnp.float32)
    slopes = lightning.lightning_slopes(H)
    o, s1 = lightning.lightning_chunk(q, k, v, s0, jnp.asarray(n_valid), slopes)
    state, outs = s0[None], []
    for i in range(n_valid):
        out, state = lightning.lightning_step(q[i][None], k[i][None], v[i][None], state, slopes)
        outs.append(out[0])
    assert _distance(o[:n_valid], jnp.stack(outs)) < 1e-4
    assert _distance(s1, state[0]) < 1e-4


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sparse_paged_kernel_reads_the_chosen_pages(dtype):
    """The Pallas kernel (interpreted) against the gather of the same
    chosen pages: lanes of different lengths, one empty, lists of odd
    length, a newest block with no cached position."""
    from ray_tpu.ops.pallas_sparse_paged_attention import sparse_paged_decode_attention_kernel

    rng = np.random.default_rng(0)
    B, G, R, d, bs, sb, L, blocks_in_pool, S = 3, 2, 8, 128, 16, 64, 2, 40, 6
    kp, vp = (jnp.asarray(rng.normal(size=(L, blocks_in_pool * bs, G * d)), dtype) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(B, G, R, d)), dtype)
    ks, vs = (jnp.asarray(rng.normal(size=(B, G, d)), dtype) for _ in range(2))
    lengths = np.array([300, 0, 128], np.int32)  # lane 2: block 2 exists and holds nothing yet
    tables = np.stack([rng.permutation(np.arange(1, blocks_in_pool))[:24] for _ in range(B)]).astype(np.int32)
    blocks = np.stack([[rng.permutation(6)[:S] for _ in range(G)] for _ in range(B)]).astype(np.int32)
    blocks[2, :, :3] = [[2, 0, 1], [1, 2, 0]]
    counts = np.array([[5, 3], [0, 0], [3, 2]], np.int32)
    ppb = sb // bs
    pages = np.take_along_axis(tables[:, None, :].repeat(G, 1),
                               (blocks[..., None] * ppb + np.arange(ppb)).reshape(B, G, -1), axis=2)
    args = (q, ks, vs, kp, vp, 1, jnp.asarray(pages), jnp.asarray(blocks), jnp.asarray(counts), jnp.asarray(lengths))
    want = sparse_paged_decode_attention(*args, block_size=bs, sparse_block=sb)
    got = sparse_paged_decode_attention_kernel(*args, block_size=bs, sparse_block=sb, interpret=True)
    assert _distance(got, want) < (1e-5 if dtype == jnp.float32 else 3e-2)
    # and the gather is the attention it says: lane 0, K/V head 0, by hand
    if dtype == jnp.float32:
        pos = np.concatenate([blocks[0, 0, i] * sb + np.arange(sb) for i in range(counts[0, 0])])
        pos = pos[pos < lengths[0]]
        rows = tables[0][pos // bs] * bs + pos % bs
        K = np.concatenate([np.asarray(kp)[1][rows][:, :d], np.asarray(ks)[0, 0][None]])
        V = np.concatenate([np.asarray(vp)[1][rows][:, :d], np.asarray(vs)[0, 0][None]])
        s = np.asarray(q)[0, 0] @ K.T / np.sqrt(d)
        p = np.exp(s - s.max(-1, keepdims=True))
        assert _distance((p / p.sum(-1, keepdims=True)) @ V, np.asarray(want)[0, 0]) < 1e-5


# ----------------------------------------------------------------------
# the engine: lanes, preemption, leaks, the statement
# ----------------------------------------------------------------------
def test_a_lane_reused_after_a_short_request_gives_what_a_fresh_engine_gives():
    """One lane: the long request takes the lane the short one left its
    state and pages in."""
    long_prompt = _tokens(150, seed=4).tolist()

    async def main(warm):
        eng = _engine(max_batch_size=1)
        if warm:
            await _drain(await eng.add_request(_tokens(30, seed=3).tolist(), max_tokens=12))
        toks = await _drain(await eng.add_request(long_prompt, max_tokens=10))
        stats = eng.stats()
        await eng.stop()
        return toks, stats

    reused, stats = asyncio.run(main(True))
    fresh, _ = asyncio.run(main(False))
    assert reused == fresh and len(fresh) == 10
    assert stats["kv_blocks_in_use"] == 0 and stats["state_slots_in_use"] == 0


def test_engine_serves_the_reference_s_tokens_and_counts_what_it_read():
    prompt = _tokens(200, seed=6).tolist()

    async def main():
        eng = _engine()
        first, second = await asyncio.gather(*[_drain(await eng.add_request(prompt, max_tokens=8))
                                               for _ in range(2)])
        mid = eng.stats()
        await _drain(await eng.add_request(prompt[:40], max_tokens=2))  # under dense_len
        end = eng.stats()
        await eng.stop()
        return eng, first, second, mid, end

    eng, first, second, stats, end = asyncio.run(main())
    assert first == second and len(first) == 8
    seq = np.asarray(prompt + first, np.int32)
    want = np.asarray(reference.full_logits(eng.params, jnp.asarray(seq), eng.model_cfg)[0])
    assert [int(want[i].argmax()) for i in range(len(prompt) - 1, len(seq) - 1)] == first
    # two prompts of 200 tokens in chunks of 64: 3 whole and a tail of 8 each
    assert stats["prefill_chunks"] == 8 and stats["prompt_tokens"] == 400
    assert stats["prefill_bucket_tokens"] == 2 * (3 * 64 + 8)
    assert 0 < stats["sparse_blocks_kept"] < stats["sparse_blocks_cached"]
    # a chunk is one tile of 64 queries in each of the 2 sparse layers; the first lies under dense_len
    assert (stats["sparse_tiles_selected"], stats["sparse_tiles"]) == (2 * 2 * 3, 2 * 2 * 4)
    assert (end["sparse_tiles_selected"], end["sparse_tiles"]) == (2 * 2 * 3, 2 * 2 * 4 + 2)
    assert 0 < stats["kv_positions_attended"] <= stats["kv_positions_gathered"]
    # a decode program reads and writes every lane's state, a chunk its lane's
    state = 2 * eng.cache["lightning_state_0"].nbytes  # two lightning layers
    assert stats["state_bytes"] == 2 * state * stats["steps"] + 8 * 2 * state // 4
    assert stats["state_slots_total"] == 4 and stats["kv_leak_report"]["state_slots_in_use"] == 0
    assert stats["kv_blocks_in_use"] == 0


def test_preemption_by_recompute_gives_the_same_tokens_and_leaves_nothing():
    """The hog is evicted mid-answer (its pages AND its lane's state go),
    prefilled again over prompt + answer so far, and says what it would
    have said; twice."""
    prompt, n = _tokens(90, seed=8).tolist(), 24

    async def run(preempt):
        eng = _engine(max_batch_size=1, preempt_wait_s=0.0, tenant_weights={"a": 1.0, "b": 1.0})
        hog = await eng.add_request(prompt, max_tokens=n, tenant="a", slo="batch")
        sent = []
        if preempt:
            fetch = eng._fetch

            def fetch_then_send(prog):
                """Each preemptor is sent from the loop's own thread, at the
                fetch after which the hog holds the lane with 4 (then 8)
                tokens out and the preemptor before it has ended; it evicts
                at the next iteration (no wait).  A poll from outside can
                find the hog finished on a loaded host."""
                fetch(prog)
                wave = len(sent)
                if (wave < 2 and hog.slot >= 0 and hog.generated >= 4 * (wave + 1)
                        and all(t.done() and t.result().finish_reason for t in sent)):
                    sent.append(asyncio.ensure_future(eng.add_request(
                        _tokens(70, seed=wave).tolist(), max_tokens=3, tenant="b", slo="interactive")))

            eng._fetch = fetch_then_send
            while len(sent) < 2 and not hog.finish_reason:
                await asyncio.sleep(0.005)
        others = [await t for t in sent]
        await asyncio.gather(*[_drain(r) for r in [hog] + others])
        stats = eng.stats()
        await eng.stop()
        return hog, stats

    hog_p, stats = asyncio.run(run(True))
    hog_o, _ = asyncio.run(run(False))
    assert hog_p.preemptions >= 2, "nothing was preempted"
    assert hog_p.tokens == hog_o.tokens and len(hog_p.tokens) == n
    report = stats["kv_leak_report"]
    assert report["blocks_in_use"] == 0 and report["state_slots_in_use"] == 0
    assert report["total_allocs"] == report["total_frees"]


@pytest.mark.parametrize("model, names, chunk", [
    ("tiny", ("k_pages", "v_pages"), 0),
    ("olmoe_tiny", ("k_pages", "v_pages"), 0),
    ("minicpm_sala_tiny", ("k_pages", "v_pages", "ck_pages", "lightning_state_0", "lightning_state_1"), 64),
])
def test_every_family_states_its_cache_and_the_engine_builds_by_it(model, names, chunk):
    eng = LLMEngine(LLMConfig(model=model, max_batch_size=3, num_blocks=70, block_size=BS))
    cfg, spec = eng.model_cfg, eng._spec
    assert spec.names == names == tuple(eng.cache) and spec.prefill_chunk == chunk
    slots = 70 * BS
    if chunk:  # the sparse layers' K/V heads alone; a compressed key a stride; a state a lane
        assert eng.k_pages.shape == (2, slots, cfg.n_kv_head * cfg.head_dim)
        assert eng.cache["ck_pages"].shape == (2, 70 * BS // cfg.kernel_stride, cfg.n_kv_head * cfg.head_dim)
        assert eng.cache["lightning_state_1"].shape == (3, 4, 16, 16)  # a state a lightning layer
        assert eng.cache["lightning_state_1"].dtype == jnp.float32 and eng.bm.state_slots == 3
    else:  # what was hard-wired: every layer, all heads, and no more
        assert eng.k_pages.shape == eng.v_pages.shape == (cfg.n_layer, slots, cfg.d_model)
        assert eng.bm.state_slots == 0 and eng.stats()["state_slots_total"] == 0
    with pytest.raises(ValueError, match="minicpm_sala_16l"):
        LLMConfig(model="no_such_preset").model_config()


def test_pages_the_selection_cannot_be_cut_into_are_refused():
    with pytest.raises(ValueError, match="whole strides"):
        sala.cache_spec(CFG, 6)
    with pytest.raises(ValueError, match="whole pages"):
        sala.cache_spec(CFG, 32)

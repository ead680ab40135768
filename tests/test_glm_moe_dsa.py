"""GLM-5 through the serving path, held to the plain float32 reference
(``benchmark/reference_glm_5.py``) at the tiny preset on the CPU: chunks
of 64 tokens, pages of 8, ``index_topk`` 16 (so a prompt of a few dozen
tokens passes it and the index chooses), one dense layer before two
expert layers, 16 routed experts of which 4 a token.

The tolerance, 3e-4 absolute on logits of size about 0.5: program and
reference are both float32 here and differ in the ORDER of their sums
(the program's online softmax over key blocks under a mask, its absorbed
decode over gathered rows and its sorted grouped matmul, against the
reference's one softmax a query over its sorted choice and its loop over
experts): 1e-7 to 3e-7 seen.  A choice by recency, a bias that weighs, an
index that is not rotated or has no ReLU move logits by 1e-3 and more:
``test_a_broken_model_fails_the_tolerance`` shows each.
"""

import asyncio
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import reference_glm_5 as reference  # noqa: E402
from ray_tpu.models import glm_moe_dsa as glm  # noqa: E402
from ray_tpu.ops import dsa, mla  # noqa: E402
from ray_tpu.ops import pallas_dsa  # noqa: E402
from ray_tpu.ops import pallas_mla_paged_attention as mla_kernel  # noqa: E402
from ray_tpu.ops.attention import dsa_index_paged_scores, mla_sparse_paged_decode_attention  # noqa: E402
from ray_tpu.ops.pallas_mla_paged_attention import mla_sparse_paged_decode_attention_kernel  # noqa: E402
from ray_tpu.serve.llm import LLMConfig, LLMEngine  # noqa: E402
from ray_tpu.serve.llm.engine import FINISHED  # noqa: E402

TOL = 3e-4
BS = 8  # positions a page
CFG = glm.GlmMoeDsaConfig.glm5_tiny(dtype=jnp.float32)
PUBLISHED = glm.GlmMoeDsaConfig.glm5()
K = CFG.index_topk


def _engine(**kw):
    kw = {"max_batch_size": 4, "num_blocks": 200, "block_size": BS, "seed": 5, **kw}
    return LLMEngine(LLMConfig(model="glm5_tiny", **kw))


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
    return (jax.jit(lambda *a: glm.prefill_chosen(*a), static_argnums=(1, 8)),
            jax.jit(lambda *a: glm.decode_chosen(*a), static_argnums=(1, 6)))


FORWARDS = _forwards()


def _replay(eng, seq, n_prompt, lane=1, cfg=None, forwards=FORWARDS, most=None):
    """The sequence through the engine's own cache by the engine's own
    programs, and the results of the family's forwards on the way: the
    prompt in chunks of ``most`` (the last chunk's logits are the
    prompt's), then one decode step a position in lane ``lane``.
    -> (logits [len(seq) - n_prompt + 1, V] for positions n_prompt - 1 ..,
    the last chunk's whole result, the decode steps' whole results)."""
    cfg = cfg or eng.model_cfg
    bm, bs, lanes = eng.bm, eng.bm.block_size, eng.config.max_batch_size
    pages = bm.blocks_needed(eng.max_ctx)
    rid = f"replay-{len(seq)}-{lane}-{most}"
    bm.allocate(rid, len(seq))
    most, logits, steps = most or eng._spec.prefill_chunk, [], []
    for start in range(0, n_prompt, most):
        m = min(most, n_prompt - start)
        bucket = eng._prefill_bucket(m, most)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :m] = seq[start:start + m]
        bm.advance(rid, m)
        last, table = np.array([m - 1], np.int32), bm.block_table(rid, pages)
        chunk = forwards[0](eng.params, cfg, eng.cache, toks, np.int32(start), last, table, np.int32(lane), bs)
        eng._run_on_cache(eng._prefill_jit, toks, bm.phys_indices(rid, start + m, bucket, start=start), last,
                          np.zeros(1, np.float32), eng._next_rng(), np.int32(start), table, np.int32(lane))
    logits.append(chunk[0][0])
    for pos in range(n_prompt, len(seq)):
        tok, lengths, write = (np.zeros(lanes, np.int32) for _ in range(3))
        tables = np.zeros((lanes, pages), np.int32)
        tok[lane], lengths[lane], tables[lane] = seq[pos], pos, bm.block_table(rid, pages)
        bm.advance(rid, 1)
        write[lane] = bm.phys_index(rid, pos)
        out = forwards[1](eng.params, cfg, eng.cache, tok, tables, lengths, bs)
        steps.append(out)
        logits.append(out[0][lane])
        eng._run_on_cache(eng._decode_jit, tok, lengths, tables, write, np.zeros(lanes, np.float32),
                          eng._next_rng())
    bm.free(rid)
    return np.stack([np.asarray(x) for x in logits]), chunk, steps


@pytest.fixture(scope="module")
def engine():
    return _engine()


# ----------------------------------------------------------------------
# (a) chunks, then decode, against the reference: logits and the choice
# ----------------------------------------------------------------------
@pytest.mark.parametrize("n_prompt, n_new", [
    (5, 6),      # one short program; under index_topk all through: every position kept
    (14, 5),     # decode passes index_topk (16) on its third step
    (64, 4),     # exactly one chunk, most of whose queries choose
    (150, 8),    # three chunks
    (201, 3),    # four chunks, a tail of 9 in a bucket of 16
])
def test_chunked_prefill_then_paged_decode_match_the_reference(engine, n_prompt, n_new):
    seq = _tokens(n_prompt + n_new, seed=n_prompt)
    got, chunk, steps = _replay(engine, seq, n_prompt)
    want, _, kept = reference.full_logits(engine.params, jnp.asarray(seq), engine.model_cfg,
                                          list(range(n_prompt - 1, len(seq))))
    assert _distance(got, want) < TOL
    # the last chunk's choice, query by query, is the reference's
    start = (n_prompt - 1) // 64 * 64
    whole = np.asarray(reference.full_logits(engine.params, jnp.asarray(seq), engine.model_cfg)[2])  # [L, T, T]
    mask = np.asarray(chunk[-1])[:, :n_prompt - start, :len(seq)]
    assert (mask == whole[:, start:n_prompt]).all()
    # and every decode step's
    kept = np.asarray(kept)  # [L, the positions asked for, T]
    assert (kept == whole[:, n_prompt - 1:]).all()
    for pos, out in zip(range(n_prompt, len(seq)), steps):
        mask = np.asarray(out[-1])[:, 1]  # [L, positions]: lane 1's choice, its own position among them
        assert not mask[:, pos + 1:].any() and (mask.sum(-1) == min(pos + 1, K)).all()
        assert (mask[:, :len(seq)] == kept[:, pos - n_prompt + 1]).all()
    assert engine.bm.blocks_in_use == 0


def test_chunked_prefill_is_one_program_prefill(engine):
    """The prompt as chunks of 64 through the two pools, and as ONE
    program that reads no cache (a chunk as long as the prompt's bucket)."""
    seq = _tokens(150, seed=21)
    chunks, *_ = _replay(engine, seq, 150)
    whole, *_ = _replay(engine, seq, 150, most=256)
    assert _distance(chunks, whole) < 1e-5


@pytest.mark.parametrize("broken", ["latest_positions", "bias_weighs", "index_not_rotated", "no_relu"])
def test_a_broken_model_fails_the_tolerance(monkeypatch, broken):
    engine = _engine()  # its own: a program the engine traces under the patch stays in its cache
    if broken == "latest_positions":
        def latest(scores, valid, k, blocks=None):
            pos = jnp.arange(scores.shape[-1], dtype=jnp.float32)
            return keep_mask(jnp.broadcast_to(pos, scores.shape), valid, k, blocks)

        keep_mask = dsa.keep_mask
        monkeypatch.setattr(dsa, "keep_mask", latest)
    elif broken == "bias_weighs":
        def weighs(h, lp, cfg):
            scores = jax.nn.sigmoid(jnp.dot(h, lp["router"], preferred_element_type=jnp.float32))
            top_p, top_e = jax.lax.top_k(scores + lp["router_bias"], cfg.num_experts_per_tok)
            return top_p / top_p.sum(-1, keepdims=True) * cfg.routed_scaling_factor, top_e

        monkeypatch.setattr(glm, "route", weighs)
        # a bias of the seeded size (0.02 beside scores near 0.5) weighs by rounding alone
        engine.params["layers"][1]["router_bias"] = 10 * engine.params["layers"][1]["router_bias"]
        engine.params["layers"][2]["router_bias"] = 10 * engine.params["layers"][2]["router_bias"]
    elif broken == "index_not_rotated":
        monkeypatch.setattr(glm.GlmMoeDsaConfig, "index_rope_dim", property(lambda self: 0))
    else:
        def no_relu(q_i, w, k_i):
            s = jnp.einsum("nhd,cd->nhc", q_i, k_i, preferred_element_type=jnp.float32)
            return (s * w.astype(jnp.float32)[:, :, None]).sum(1)

        monkeypatch.setattr(dsa, "index_scores", no_relu)
    seq = _tokens(150 + 4, seed=31)
    got, *_ = _replay(engine, seq, 150, forwards=_forwards())
    want, *_ = reference.full_logits(engine.params, jnp.asarray(seq), engine.model_cfg,
                                     list(range(149, len(seq))))
    assert _distance(got, want) > 1.5 * TOL


# ----------------------------------------------------------------------
# (b) the exact choice; the absorbed path against the expanded one
# ----------------------------------------------------------------------
@pytest.mark.parametrize("k", [1, 16, 64, 299])
def test_the_exact_choice_is_a_full_sort_s_ties_included(k):
    """Scores of six values only (so the k-th is always a tie), a row of
    zeros of both signs, rows with fewer candidates than k."""
    rng = np.random.default_rng(k)
    C = 300
    scores = rng.integers(0, 6, size=(7, C)).astype(np.float32) - 2.0
    scores[0, :] = 0.0
    scores[0, ::2] = -0.0
    scores[1] *= 1e-30
    last = np.array([299, 250, 3, 0, 120, 17, 64])
    valid = np.arange(C)[None, :] <= last[:, None]
    order = np.argsort(-np.where(valid, scores, -np.inf), axis=-1, kind="stable")
    rank = np.empty_like(order)
    for row in range(7):
        rank[row, order[row]] = np.arange(C)
    want = valid & (rank < k)
    keep = np.asarray(dsa.keep_mask(jnp.asarray(scores), jnp.asarray(valid), k))
    assert (keep == want).all()
    assert (keep.sum(-1) == np.minimum(last + 1, k)).all()


def _planted(rng, n, blocks):
    """Scores [n, blocks * KEY_BLOCK] of a dozen values only (every k-th
    largest is a tie), each row's ties running across every block
    boundary, one row all zeros of both signs, and uneven candidates."""
    C = blocks * dsa.KEY_BLOCK
    scores = rng.integers(0, 12, size=(n, C)).astype(np.float32) - 4.0
    scores[0, :] = 0.0
    scores[0, ::2] = -0.0
    for b in range(1, blocks):  # one value from 40 columns before a boundary to 40 after it
        scores[1:, b * dsa.KEY_BLOCK - 40:b * dsa.KEY_BLOCK + 40] = rng.integers(0, 12, size=(n - 1, 1)) - 4.0
    # a row whose values' bit patterns end in fifteens: a pass's digit is 15 and the count above it the pass before's
    ends = np.array([0x3FFFFFFF, 0x3FFFFFEF, 0x3FFFFF0F, 0x3FFFF0FF, 0x3FF0FFFF], np.uint32).view(np.float32)
    scores[5] = ends[rng.integers(0, len(ends), size=C)]
    last = rng.integers(0, C, size=n)
    last[:4] = [C - 1, dsa.KEY_BLOCK, dsa.KEY_BLOCK - 1, 0]
    last[5] = C - 7
    return scores, np.arange(C)[None, :] <= last[:, None]


@pytest.mark.parametrize("traced", [False, True], ids=["blocks_static", "blocks_traced"])
@pytest.mark.parametrize("k", [1, 64, 2048, 2049])
@pytest.mark.parametrize("b", [0, 1, 2, 4])
def test_the_choice_within_a_reach_is_the_whole_width_s_choice(b, k, traced):
    """``keep_mask(..., blocks=b)`` against ``keep_mask`` of the same
    scores with everything past ``b`` blocks invalid: the same mask, by
    counting over ``b`` blocks or (``b * KEY_BLOCK <= k``: k 2,048 at one
    block, and just past the edge at 2,049) by counting nothing."""
    scores, valid = _planted(np.random.default_rng(100 * b + k), 8, 4)
    reach = np.arange(scores.shape[1])[None, :] < b * dsa.KEY_BLOCK
    want = np.asarray(dsa.keep_mask(jnp.asarray(scores), jnp.asarray(valid & reach), k))
    if traced:
        got = jax.jit(lambda s, v, n: dsa.keep_mask(s, v, k, n))(scores, valid, jnp.int32(b))
    else:
        got = dsa.keep_mask(jnp.asarray(scores), jnp.asarray(valid), k, b)
    got = np.asarray(got)
    assert (got == want).all()
    assert (got.sum(-1) == np.minimum((valid & reach).sum(-1), k)).all()
    # it counted wherever a row could hold more than k candidates, and there alone
    assert bool(dsa.counts_over(b, k)) == (b * dsa.KEY_BLOCK > k)
    # the ties' running count by jnp.cumsum (a candidate form of scripts/dsa_decode_check.py) is the product's
    if b and dsa.counts_over(b, k):
        plain = dsa._keep_within(jnp.asarray(scores), jnp.asarray(valid & reach), k, jnp.int32(b),
                                 running=lambda ties: jnp.cumsum(ties, axis=-1, dtype=jnp.int32))
        assert (np.asarray(plain) == want).all()


def _parent_chunk_attention(q_nope, q_rope, q_i, w, ctx, k_ctx, wukv, start, n_valid, cfg, k):
    """``dsa.sparse_chunk_attention`` as it stood before a tile's choice
    took its reach: every tile's scores chosen from over all C columns."""
    T, C = q_nope.shape[0], ctx.shape[0]
    tile = min(T, dsa.Q_TILE)
    pos = jnp.arange(C)
    masks = {}

    def keep_of(first, n):
        q_pos = start + first + jnp.arange(n)
        seen = jnp.minimum(start + first + n, start + n_valid)
        blocks = jnp.where(first < n_valid, -(-seen // dsa.KEY_BLOCK), 0)
        scores = dsa.chunk_index_scores(q_i[first:first + n], w[first:first + n], k_ctx, blocks)
        valid = (pos[None, :] <= q_pos[:, None]) & ((first + jnp.arange(n)) < n_valid)[:, None]
        masks[first] = dsa.keep_mask(scores, valid, k)
        return masks[first]

    out = mla.expanded_attention(q_nope, q_rope, ctx, wukv, start, n_valid, cfg, keep_of=keep_of, q_block=tile)
    return out, jnp.concatenate([masks[f] for f in sorted(masks)])


@pytest.mark.parametrize("start, n_valid, k", [
    (1500, 1000, 64),    # mid-prompt, across the first key block's end, pads after 1,000 queries: tiles reach 1, 2, 0 blocks
    (0, 1024, 2048),     # a prompt's first tiles: at most k candidates, nothing is counted
    (2048, 700, 2048),   # the first tile past the edge: 2 blocks, counted; a whole tile of pads
    (3000, 1536, 16),    # every tile real, reaches 2, 2 and 3 blocks
])
def test_a_chunk_s_choice_and_output_are_the_parent_form_s(start, n_valid, k):
    """A chunk of three tiles over 3 key blocks and room, under jit with
    ``start`` and ``n_valid`` traced as the engine's are."""
    rng = np.random.default_rng(start)
    T, C, H, Hi, Di = 3 * dsa.Q_TILE, 4 * dsa.KEY_BLOCK, CFG.n_head, CFG.index_n_heads, CFG.index_head_dim

    def normal(*shape, scale=1.0):
        return jnp.asarray(scale * rng.normal(size=shape), jnp.float32)

    # index keys of a few values only: scores tie, across key blocks too
    k_ctx = jnp.asarray(rng.integers(-1, 2, size=(C, Di)), jnp.float32)
    q_i = jnp.asarray(rng.integers(-1, 2, size=(T, Hi, Di)), jnp.float32)
    args = (normal(T, H, CFG.qk_nope_head_dim, scale=0.3), normal(T, H, CFG.qk_rope_head_dim, scale=0.3), q_i,
            jnp.ones((T, Hi), jnp.float32), normal(C, CFG.latent_row),
            k_ctx, normal(CFG.kv_lora_rank, H * (CFG.qk_nope_head_dim + CFG.v_head_dim), scale=0.2))
    got = jax.jit(lambda a, s, n: dsa.sparse_chunk_attention(*a, s, n, CFG, k))(args, jnp.int32(start), jnp.int32(n_valid))
    want = jax.jit(lambda a, s, n: _parent_chunk_attention(*a, s, n, CFG, k))(args, jnp.int32(start), jnp.int32(n_valid))
    out, kept, columns, mask = (np.asarray(x) for x in got)
    assert (mask == np.asarray(want[1])).all() and kept == mask.sum()
    assert (mask.sum(-1) == np.where(np.arange(T) < n_valid, np.minimum(start + np.arange(T) + 1, k), 0)).all()
    assert (out[:n_valid] == np.asarray(want[0])[:n_valid]).all()
    # the columns its passes read, by hand: a real tile's 512 queries times its reach, where that is over k
    reach = [-(-min(start + f + dsa.Q_TILE, start + n_valid) // dsa.KEY_BLOCK) * dsa.KEY_BLOCK if f < n_valid else 0
             for f in range(0, T, dsa.Q_TILE)]
    assert columns == sum(dsa.Q_TILE * r for r in reach if r > k)


def test_absorbed_sparse_decode_is_expanded_chunk_attention_on_the_same_choice(engine):
    """Position p's logits from a decode step (absorbed, over the chosen
    rows gathered from the pool) and from a prefill chunk that ends at p
    (expanded keys and values under the mask), and their choices."""
    seq = _tokens(100, seed=41)
    decode, _, steps = _replay(engine, seq, 96)      # fed seq[99] at position 99
    prefill, chunk, _ = _replay(engine, seq, 100)    # the prompt's last position, 99
    assert _distance(decode[-1], prefill[0]) < 1e-5
    mask = np.asarray(chunk[-1])[:, 99 - 64]
    assert (np.asarray(steps[-1][-1])[:, 1, :100] == mask[:, :100]).all() and mask.sum() == K * CFG.n_layer


def test_absorbed_queries_score_what_expanded_keys_score():
    rng = np.random.default_rng(3)
    H, nope, rope, kv, dv = CFG.n_head, CFG.qk_nope_head_dim, CFG.qk_rope_head_dim, CFG.kv_lora_rank, CFG.v_head_dim
    wukv = jnp.asarray(rng.normal(size=(kv, H * (nope + dv))), jnp.float32)
    q_nope, q_rope = (jnp.asarray(rng.normal(size=(3, H, n)), jnp.float32) for n in (nope, rope))
    row = jnp.asarray(rng.normal(size=(CFG.latent_row,)), jnp.float32)
    q = mla.absorbed_queries(q_nope, q_rope, wukv, CFG)
    assert q.shape == (3, H, CFG.latent_row) and not np.asarray(q[..., kv + rope:]).any()
    k_nope = (row[:kv] @ wukv).reshape(H, nope + dv)[:, :nope]
    want = jnp.einsum("bhd,hd->bh", q_nope, k_nope) + jnp.einsum("bhd,d->bh", q_rope, row[kv:kv + rope])
    assert _distance(jnp.einsum("bhw,w->bh", q, row), want) < 1e-4


# ----------------------------------------------------------------------
# (c) the two kernels in interpret mode against their jax.numpy paths
# ----------------------------------------------------------------------
def _lanes(rng, bs, per, lengths):
    n_blocks = 1 + len(lengths) * per
    tables = rng.permutation(np.arange(1, n_blocks)).reshape(len(lengths), per).astype(np.int32)
    for b, n in enumerate(lengths):
        tables[b, -(-n // bs):] = 0
    return n_blocks, tables


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_index_kernel_scores_the_keys_in_the_lanes_pages(dtype):
    """Lanes with nothing cached, one position, a page less one, and
    three compute blocks with a partial last one; physical pages
    shuffled.  By hand for the long lane."""
    rng = np.random.default_rng(0)
    B, Hi, Di, bs, per = 4, 8, 128, 16, 300
    lengths = np.array([0, 1, bs - 1, 4500], np.int32)
    n_blocks, tables = _lanes(rng, bs, per, lengths)
    pool = jnp.asarray(rng.normal(size=(2, n_blocks * bs, Di)), dtype)
    q = jnp.asarray(rng.normal(size=(B, Hi, Di)), dtype)
    w = jnp.asarray(rng.normal(size=(B, Hi)), jnp.float32)
    k_self = jnp.asarray(rng.normal(size=(B, Di)), dtype)
    args = (pool, 1, jnp.asarray(tables), jnp.asarray(lengths))
    want = np.asarray(dsa_index_paged_scores(q, w, k_self, *args, block_size=bs))
    got = np.asarray(pallas_dsa.dsa_index_paged_scores_kernel(q, w, *args, block_size=bs, interpret=True))
    cached = np.arange(per * bs)[None, :] < lengths[:, None]
    assert got.shape == want.shape == (B, per * bs)
    assert _distance(got[cached], want[cached]) < 2e-5
    assert (got[~cached] < -1e29).all()
    # the dispatch puts the fed token's own score at its own position, and nothing after it
    own = np.maximum(np.asarray(q, np.float32) @ np.asarray(k_self, np.float32)[..., None], 0)[..., 0]
    assert _distance(want[np.arange(B), lengths], (own * np.asarray(w)).sum(-1)) < 1e-3
    assert (want[np.arange(per * bs)[None, :] > lengths[:, None]] < -1e29).all()
    pos = np.arange(4500)
    keys = np.asarray(pool, np.float32)[1][tables[3][pos // bs] * bs + pos % bs]
    by_hand = (np.maximum(np.asarray(q, np.float32)[3] @ keys.T, 0) * np.asarray(w)[3][:, None]).sum(0)
    assert _distance(got[3, :4500], by_hand) < 1e-3


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sparse_attention_kernel_attends_the_chosen_rows(dtype):
    """Lanes of uneven length: nothing cached (its own row alone), under
    the choice's size (every position), over it with a first block of
    which NOTHING is chosen and the own position chosen, and well over
    it with the own position left out."""
    rng = np.random.default_rng(1)
    B, H, W, V, bs, per, k = 4, 8, 256, 128, 16, 640, 512
    lengths = np.array([0, 100, 6000, 9000], np.int32)
    n_blocks, tables = _lanes(rng, bs, per, lengths)
    pool = jnp.asarray(rng.normal(size=(2, n_blocks * bs, W)), dtype)
    q = jnp.asarray(0.2 * rng.normal(size=(B, H, W)), dtype)
    own = jnp.asarray(rng.normal(size=(B, W)), dtype)
    scores = rng.normal(size=(B, per * bs)).astype(np.float32)
    scores[2, :4096] = -50.0  # lane 2 keeps nothing of its first compute block
    scores[2, 6000], scores[3, 9000] = 100.0, -100.0  # the own position: chosen, left out
    valid = np.arange(per * bs)[None, :] <= lengths[:, None]
    keep = dsa.keep_mask(jnp.asarray(scores), jnp.asarray(valid), k)
    assert np.asarray(keep).sum(-1).tolist() == [1, 101, k, k] and not np.asarray(keep)[2, :4096].any()
    args = (q, own, pool, 1, jnp.asarray(tables))
    want = mla_sparse_paged_decode_attention(*args, keep, jnp.asarray(lengths), block_size=bs, v_width=V)
    cached = np.asarray(keep) & (np.arange(per * bs)[None, :] < lengths[:, None])
    is_own = np.asarray(keep)[np.arange(B), lengths]
    assert is_own.tolist() == [True, True, True, False]
    got = mla_sparse_paged_decode_attention_kernel(
        q, own, jnp.asarray(cached), jnp.asarray(is_own), pool, 1, jnp.asarray(tables), jnp.asarray(lengths),
        block_size=bs, v_width=V, interpret=True)
    assert got.shape == (B, H, V)
    assert _distance(got, want) < (2e-5 if dtype == jnp.float32 else 3e-2)
    assert _distance(got[0], jnp.broadcast_to(own[0, :V], (H, V))) < 1e-6
    if dtype == jnp.float32:  # lane 3 by hand: its chosen rows, its own row left out
        pos = np.flatnonzero(cached[3])
        rows = np.asarray(pool)[1][tables[3][pos // bs] * bs + pos % bs]
        s = np.asarray(q)[3] @ rows.T
        p = np.exp(s - s.max(-1, keepdims=True))
        assert _distance((p / p.sum(-1, keepdims=True)) @ rows[:, :V], want[3]) < 1e-5


def test_the_kernels_take_the_cell_s_shapes_and_say_what_they_hold():
    bf16 = jnp.bfloat16
    assert pallas_dsa.index_kernel_takes(20, 32, 128, 64, 576, bf16)
    assert mla_kernel.sparse_kernel_takes(20, 64, 640, 512, 64, 576, bf16)
    assert pallas_dsa.index_vmem_scratch_bytes(32, 128, bf16) == 2 * 2048 * 128 * 2 + 32 * 128 * 2
    # a row of 640 columns halves the latent kernel's compute block; Mistral's 384 keep it whole
    assert mla_kernel.block_positions(640, bf16) == 2048 and mla_kernel.block_positions(384, bf16) == 4096
    # pages of 8 are not whole sublane tiles of bf16; 300 positions are not whole parts of the mask
    assert not pallas_dsa.index_kernel_takes(4, 8, 128, 8, 64, bf16)
    assert not mla_kernel.sparse_kernel_takes(4, 8, 256, 128, 16, 300 // 16, bf16)
    # every lane's scores are whole in VMEM: 128 lanes of 36,864 positions are not
    assert not pallas_dsa.index_kernel_takes(128, 32, 128, 64, 576, bf16)


# ----------------------------------------------------------------------
# (d) the shares add up
# ----------------------------------------------------------------------
def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Sixteen chips of ONE of the 16 experts each: their parts, the
    shared expert and the attention counted once, are the uncut
    reference's layer."""
    params = glm.init_params(CFG, jax.random.PRNGKey(7))
    lp = params["layers"][1]
    T = 50
    x = jnp.asarray(np.random.default_rng(8).normal(size=(T, CFG.d_model)), jnp.float32)
    sizes = tuple((k, getattr(CFG, k)) for k in reference._KEYS)
    want, want_e, _ = reference.layer(x, lp, cfg=sizes)
    c = dict(sizes)
    # x after attention: the reference's layer with every expert's down projection zero
    silent = dict(lp, wd_shared=jnp.zeros_like(lp["wd_shared"]), wd=jnp.zeros_like(lp["wd"]))
    mid, *_ = reference.layer(x, silent, cfg=sizes)
    uncut, _ = reference.expert_half(mid, lp, c)
    assert _distance(mid + uncut, want) < 1e-5
    h2 = np.asarray(reference.rmsnorm(mid, lp["w_post"], CFG.rms_norm_eps))
    gate, up = np.split(h2 @ np.asarray(lp["wgu_shared"]), 2, axis=-1)
    shared = (gate / (1 + np.exp(-gate)) * up) @ np.asarray(lp["wd_shared"])
    total, held_pairs = shared.copy(), 0
    for first in range(16):
        cfg = dataclasses.replace(CFG, experts_first=first, experts_held=1)
        share = dict(lp, wgu=lp["wgu"][first:first + 1], wd=lp["wd"][first:first + 1])
        y, counts, top_e = glm._feed_forward(mid, share, cfg, False)
        total += np.asarray(y) - shared
        routed, held, computed = np.asarray(counts)[:3].tolist()
        assert routed == T * 4 and held == computed
        held_pairs += held
        assert (np.sort(np.asarray(top_e), -1) == np.sort(np.asarray(want_e), -1)).all()
        ref_share, _ = reference.expert_half(mid, share, dict(c, experts_first=first))
        assert _distance(y, ref_share) < 1e-5
    assert held_pairs == T * 4  # every pair is some chip's
    assert _distance(total, uncut) < 1e-5


def test_the_router_s_bias_chooses_and_does_not_weigh():
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.normal(size=(6, CFG.d_model)), jnp.float32)
    lp = {"router": jnp.asarray(0.05 * rng.normal(size=(CFG.d_model, 16)), jnp.float32),
          "router_bias": jnp.asarray(np.where(np.arange(16) == 11, 5.0, 0.0), jnp.float32)}
    top_p, top_e = glm.route(h, lp, CFG)
    s = 1 / (1 + np.exp(-np.asarray(h) @ np.asarray(lp["router"])))
    assert (np.asarray(top_e)[:, 0] == 11).all()  # a bias of 5 puts expert 11 first for every token
    chosen = np.take_along_axis(s, np.asarray(top_e), -1)
    assert _distance(top_p, 2.5 * chosen / chosen.sum(-1, keepdims=True)) < 1e-6
    assert np.allclose(np.asarray(top_p).sum(-1), 2.5, atol=1e-5)


# ----------------------------------------------------------------------
# (e) the statement; the published sizes
# ----------------------------------------------------------------------
def test_the_engine_holds_what_the_family_states_and_no_more():
    eng = LLMEngine(LLMConfig(model="glm5_tiny", max_batch_size=3, num_blocks=70, block_size=BS))
    cfg = eng.model_cfg
    assert tuple(eng.cache) == ("k_pages", "index_k") == eng._spec.names
    assert not eng._spec.v_pool and eng._spec.reads_cache and eng._spec.prefill_chunk == 64
    assert cfg.latent_row == 128 and eng.k_pages.shape == (cfg.n_layer, 70 * BS, 128)
    assert eng.cache["index_k"].shape == (cfg.n_layer, 70 * BS, cfg.index_head_dim)
    assert eng.bm.state_slots == 0
    with pytest.raises(KeyError):
        eng.v_pages


def test_the_published_row_is_576_values_in_five_lane_tiles_beside_an_index_key():
    spec = glm.cache_spec(PUBLISHED, 64)
    assert (spec.paged_layers, spec.row_width, spec.v_pool, spec.prefill_chunk) == (78, 640, False, 4096)
    assert spec.page_extras == (("index_k", 64, 128, jnp.bfloat16),)
    held = glm.GlmMoeDsaConfig.glm5_6l_ep16()
    assert (held.n_layer, held.first_k_dense_replace, held.experts_first, held.experts_held,
            held.n_routed_experts) == (6, 1, 0, 16, 256)
    assert (held.vocab_size, held.published_vocab_size) == (19360, 154880)
    assert glm.cache_spec(held, 64).paged_layers == 6
    assert 6 * (640 + 128) * 2 == 9216  # B a cached position
    assert glm.softmax_scale(PUBLISHED) == 0.0625 and glm.inv_freq(PUBLISHED)[1] == 1e6 ** (-2 / 64)
    # the sizes of the issue's arithmetic: a layer outside its routed experts, an expert, a dense layer
    shapes = jax.eval_shape(lambda: glm.init_params(dataclasses.replace(held, n_layer=2)))
    dense, expert = ({k: int(np.prod(v.shape)) for k, v in layer.items()} for layer in shapes["layers"])
    assert expert["wgu"] + expert["wd"] == 16 * 37_748_736
    outside = sum(expert.values()) - expert["wgu"] - expert["wd"]
    assert abs(outside - 213.71e6) < 0.05e6 and abs(sum(dense.values()) - 400.88e6) < 0.05e6
    assert "glm5_6l_ep16" in LLMConfig.__doc__


# ----------------------------------------------------------------------
# (f) through the engine: tokens, counters, preemption, an early join
# ----------------------------------------------------------------------
def test_engine_serves_the_reference_s_tokens_and_counts_by_hand():
    prompt = _tokens(150, seed=6).tolist()

    async def main():
        eng = _engine()
        first, second = await asyncio.gather(*[_drain(await eng.add_request(prompt, max_tokens=8))
                                               for _ in range(2)])
        stats = eng.stats()
        await eng.stop()
        return eng, first, second, stats

    eng, first, second, stats = asyncio.run(main())
    assert first == second and len(first) == 8
    seq = np.asarray(prompt + first, np.int32)
    want = np.asarray(reference.full_logits(eng.params, jnp.asarray(seq), eng.model_cfg)[0])
    assert [int(want[i].argmax()) for i in range(len(prompt) - 1, len(seq) - 1)] == first
    # two prompts of 150 tokens in chunks of 64: 2 whole and a tail of 22 in a bucket of 32
    assert stats["prefill_chunks"] == 6 and stats["prefill_bucket_tokens"] == 2 * (2 * 64 + 32)
    rows = stats["prefill_bucket_tokens"] + 4 * stats["steps"]
    L, experts = eng.model_cfg.n_layer, eng.model_cfg.n_layer - 1  # the dense layer counts nowhere
    assert stats["moe_pairs_routed"] == 4 * experts * rows
    assert stats["moe_pairs_held"] == stats["moe_pairs"] == stats["moe_pairs_routed"]  # all 16 held here
    assert stats["moe_expert_slots"] == 16 * experts * (6 + stats["steps"])
    assert stats["moe_layer_programs"] == experts * (6 + stats["steps"])
    # a query at position t has t + 1 candidates and attends min(t + 1, 16), in every layer
    assert stats["dsa_positions_cached_prefill"] == 2 * L * sum(t + 1 for t in range(150))
    assert stats["dsa_positions_kept_prefill"] == 2 * L * sum(min(t + 1, K) for t in range(150))
    # decode: the first of the 8 tokens is the prefill's; 7 steps a request at positions 150..156
    assert stats["dsa_positions_cached"] == 2 * L * sum(t + 1 for t in range(150, 157))
    assert stats["dsa_positions_kept"] == 2 * L * 7 * K
    assert stats["dsa_index_positions_scored"] == 2 * L * sum(range(150, 157))
    # the choice's passes: a chunk's one tile (64, 64, 32 queries at 0, 64, 128) reaches one key block of
    # 2,048 columns, over index_topk (16), so it counts; a decode step counts over the table's 25 pages of 8
    assert stats["dsa_select_columns_prefill"] == 2 * L * (64 + 64 + 32) * dsa.KEY_BLOCK
    assert stats["dsa_select_columns"] == 2 * L * 7 * eng.bm.blocks_needed(eng.max_ctx) * BS
    # the benchmark's entry over them: columns read a candidate
    from benchmark import readers, spec

    how = spec.load_layer_metric("dsa_select_columns_per_candidate.glm5")
    zero = dict.fromkeys(stats, 0)
    read = readers.stats_delta(how["args"], {"values": {}, "stats": {"before": zero, "after": stats, "window_s": 1.0}})
    assert read == pytest.approx(
        (stats["dsa_select_columns_prefill"] + stats["dsa_select_columns"])
        / (2 * L * sum(t + 1 for t in range(157))))
    # of the 16 a query keeps, the cached ones were attended (all, or all but its own), out of
    # the whole pages of 8 the walk copies: 152 positions at lengths 150-152, 160 from 153
    assert 2 * L * 7 * (K - 1) <= stats["kv_positions_attended"] <= 2 * L * 7 * K
    assert stats["kv_positions_gathered"] == 2 * L * (3 * 152 + 4 * 160)
    assert stats["kv_blocks_in_use"] == 0 and stats["state_slots_total"] == 0


def test_a_share_serves_through_the_engine_and_counts_what_it_held(monkeypatch):
    """The tiny preset holding experts 4-7 of 16: the engine's tokens are
    the reference's given the same share, and about a quarter of the
    pairs are held, every one of them computed."""
    monkeypatch.setattr(glm.GlmMoeDsaConfig, "glm5_tiny", staticmethod(lambda **kw: GlmTinyShare(**kw)))
    prompt = _tokens(100, seed=9).tolist()

    async def main():
        eng = _engine()
        toks = await _drain(await eng.add_request(prompt, max_tokens=6))
        stats = eng.stats()
        await eng.stop()
        return eng, toks, stats

    eng, toks, stats = asyncio.run(main())
    assert eng.params["layers"][1]["wgu"].shape[0] == 4 and "wgu" not in eng.params["layers"][0]
    seq = np.asarray(prompt + toks, np.int32)
    want = np.asarray(reference.full_logits(eng.params, jnp.asarray(seq), eng.model_cfg)[0])
    assert [int(want[i].argmax()) for i in range(len(prompt) - 1, len(seq) - 1)] == toks
    assert 0 < stats["moe_pairs_held"] == stats["moe_pairs"] < stats["moe_pairs_routed"] // 2
    assert stats["moe_expert_slots"] == 4 * stats["moe_layer_programs"]


def GlmTinyShare(**kw):
    base = glm.GlmMoeDsaConfig(**{f.name: getattr(CFG, f.name) for f in dataclasses.fields(CFG)})
    return dataclasses.replace(base, experts_first=4, experts_held=4, **kw)


def test_preemption_by_recompute_and_an_early_join_give_the_same_tokens():
    """The hog is evicted mid-answer, prefilled again over prompt +
    answer so far (two chunks, both pools written again), and says what
    it would have said; a request that joins while another decodes says
    what it says alone."""
    prompt, n = _tokens(90, seed=8).tolist(), 20
    other = _tokens(70, seed=2).tolist()

    async def run(preempt):
        eng = _engine(max_batch_size=1, preempt_wait_s=0.005, tenant_weights={"a": 1.0, "b": 1.0})
        hog = await eng.add_request(prompt, max_tokens=n, tenant="a", slo="batch")
        others = []
        if preempt:
            while hog.generated < 4 or hog.slot < 0:
                await asyncio.sleep(0.005)
            others.append(await eng.add_request(other, max_tokens=3, tenant="b", slo="interactive"))
            while not others[-1].finish_reason:
                await asyncio.sleep(0.005)
        await asyncio.gather(*[_drain(r) for r in [hog] + others])
        stats = eng.stats()
        await eng.stop()
        return hog, stats

    async def join():
        eng = _engine(max_batch_size=2)
        a = await eng.add_request(prompt, max_tokens=n)
        while a.generated < 3:
            await asyncio.sleep(0.005)
        b = await eng.add_request(other, max_tokens=5)
        out = await asyncio.gather(_drain(a), _drain(b))
        alone = await _drain(await eng.add_request(other, max_tokens=5))
        stats = eng.stats()
        await eng.stop()
        return out, alone, stats

    hog_p, stats = asyncio.run(run(True))
    hog_o, _ = asyncio.run(run(False))
    assert hog_p.preemptions >= 1, "nothing was preempted"
    assert hog_p.tokens == hog_o.tokens and len(hog_p.tokens) == n
    report = stats["kv_leak_report"]
    assert report["blocks_in_use"] == 0 and report["total_allocs"] == report["total_frees"]
    (a_toks, b_toks), alone, stats = asyncio.run(join())
    assert a_toks == hog_o.tokens and b_toks == alone
    assert stats["kv_blocks_in_use"] == 0


# ----------------------------------------------------------------------
# (g) the benchmark's entry over the choice's two counters
# ----------------------------------------------------------------------
def test_the_columns_a_candidate_entry_reads_the_two_counters():
    import json

    from benchmark import readers, spec

    name, cell = "dsa_select_columns_per_candidate.glm5", "glm-5.serve.longrepo-backlog"
    how = spec.load_layer_metric(name)
    assert how["reader"] == "stats_delta"
    # a 16k prompt's fifth chunk (positions 16,384 .. 20,479 of 20,480: 8 tiles reaching 9, 9, 9, 9, 10, 10,
    # 10, 10 key blocks) in six layers, then one decode step of 20 lanes at 20,480 over a table of 36,864
    chunk = 6 * 512 * 2048 * (4 * 9 + 4 * 10)
    cached = 6 * sum(range(16385, 20481))
    step, candidates = 6 * 20 * 36864, 6 * 20 * 20481
    before = {"dsa_select_columns_prefill": 5, "dsa_select_columns": 7, "dsa_positions_cached_prefill": 11,
              "dsa_positions_cached": 13}
    after = {"dsa_select_columns_prefill": 5 + chunk, "dsa_select_columns": 7 + step,
             "dsa_positions_cached_prefill": 11 + cached, "dsa_positions_cached": 13 + candidates}
    ctx = {"values": {}, "stats": {"before": before, "after": after, "window_s": 30.0}}
    by_hand = (chunk + step) / (cached + candidates)
    assert 1.0 < by_hand < 1.2
    assert readers.stats_delta(how["args"], ctx) == pytest.approx(by_hand)
    # the parent's arithmetic on the same work: 40,960 columns a query of the chunk
    assert (6 * 4096 * 40960 + step) / (cached + candidates) > 2
    # on a program without the two counters the entry is left out, not raised
    old = {"before": {"dsa_positions_cached": 13}, "after": {"dsa_positions_cached": 99}, "window_s": 30.0}
    assert readers.stats_delta(how["args"], {"values": {}, "stats": old}) is None
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": "cols/candidate", "better": "lower", "source": "program_counter",
                     "layer": "models", "moves": "serve_out_tokens_per_s", "workloads": [cell]}
    assert {"dsa_select_columns_prefill", "dsa_select_columns"} <= set(glm.COUNTERS)

"""Granite 4.0-H through the serving path, held to the plain float32
reference (``benchmark/reference_granite_4_0_h_small.py``) at the tiny
preset on the CPU: mixers ``mamba, attention, mamba, attention`` (every
kind of layer twice, each with its experts part), chunks of 32 tokens in
scan blocks of 8, pages of 8, 16 routed experts of which 4 a token, four
queries a K/V head as published.

The tolerance, 3e-6 absolute on logits of size about 0.08 (they are
divided by 16): program and reference are both float32 here and differ
in the ORDER of their sums (the program's chunked scan from a carried
state and its online softmax over key blocks, its sorted grouped matmul,
against the reference's recurrence a position at a time, one softmax a
query and a loop over experts): 7e-9 seen.  A multiplier dropped, a
softmax over all the router's logits, a head that is not the embedding,
one norm a layer move logits by 2e-5 and more:
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

from benchmark import reference_granite_4_0_h_small as reference  # noqa: E402
from ray_tpu.models import granite_hybrid as gh  # noqa: E402
from ray_tpu.serve.llm import LLMConfig, LLMEngine  # noqa: E402
from ray_tpu.serve.llm.engine import FINISHED  # noqa: E402

TOL = 3e-6
BS = 8  # positions a page
CFG = gh.GraniteHybridConfig.granite_4_0_h_small_tiny(dtype=jnp.float32)
HELD = gh.GraniteHybridConfig.granite_4_0_h_small_10l_ep2()
MULTIPLIERS = ("embedding_multiplier", "residual_multiplier", "attention_multiplier", "logits_scaling")


def _engine(**kw):
    kw = {"max_batch_size": 4, "num_blocks": 200, "block_size": BS, "seed": 5, **kw}
    return LLMEngine(LLMConfig(model="granite_4_0_h_small_tiny", **kw))


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


FORWARDS = (jax.jit(lambda *a: gh.prefill_chunk(*a), static_argnums=(1, 8)),
            jax.jit(lambda *a: gh.decode_forward_cached(*a), static_argnums=(1, 6)))


def _replay(eng, seq, n_prompt, lane=1):
    """The sequence through the engine's own cache by the engine's own
    programs, and the logits of the family's forwards on the way: the
    prompt in chunks (the last chunk's logits are the prompt's), then
    one decode step a position in lane ``lane``.
    -> logits [len(seq) - n_prompt + 1, V] for positions n_prompt - 1 .."""
    cfg = eng.model_cfg
    bm, bs, lanes = eng.bm, eng.bm.block_size, eng.config.max_batch_size
    pages = bm.blocks_needed(eng.max_ctx)
    rid = f"replay-{len(seq)}-{lane}"
    bm.allocate(rid, len(seq))
    most, logits = eng._spec.prefill_chunk, []
    for start in range(0, n_prompt, most):
        m = min(most, n_prompt - start)
        bucket = eng._prefill_bucket(m, most)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :m] = seq[start:start + m]
        bm.advance(rid, m)
        last, table = np.array([m - 1], np.int32), bm.block_table(rid, pages)
        out = FORWARDS[0](eng.params, cfg, eng.cache, toks, np.int32(start), last, table, np.int32(lane), bs)
        eng._run_on_cache(eng._prefill_jit, toks, bm.phys_indices(rid, start + m, bucket, start=start), last,
                          np.zeros(1, np.float32), eng._next_rng(), np.int32(start), table, np.int32(lane))
    logits.append(out[0][0])
    for pos in range(n_prompt, len(seq)):
        tok, lengths, write = (np.zeros(lanes, np.int32) for _ in range(3))
        tables = np.zeros((lanes, pages), np.int32)
        tok[lane], lengths[lane], tables[lane] = seq[pos], pos, bm.block_table(rid, pages)
        bm.advance(rid, 1)
        write[lane] = bm.phys_index(rid, pos)
        out = FORWARDS[1](eng.params, cfg, eng.cache, tok, tables, lengths, bs)
        logits.append(out[0][lane])
        eng._run_on_cache(eng._decode_jit, tok, lengths, tables, write, np.zeros(lanes, np.float32),
                          eng._next_rng())
    bm.free(rid)
    return np.stack([np.asarray(x) for x in logits])


def _reference(eng, seq, n_prompt):
    return reference.full_logits(eng.params, jnp.asarray(seq), eng.model_cfg, list(range(n_prompt - 1, len(seq))))[0]


@pytest.fixture(scope="module")
def engine():
    return _engine()


# ----------------------------------------------------------------------
# (a) chunks, then decode, against the reference: logits
# ----------------------------------------------------------------------
@pytest.mark.parametrize("n_prompt, n_new", [
    (5, 6),      # one short program (a bucket of 8: one scan block, 3 pads), then decode
    (32, 4),     # exactly one chunk of four scan blocks
    (75, 8),     # three chunks: a state and a tail cross two chunk boundaries, the last 11 in a bucket of 16
    (97, 3),     # four chunks, the last a single token in a bucket of 8
])
def test_chunked_prefill_then_paged_decode_match_the_reference(engine, n_prompt, n_new):
    seq = _tokens(n_prompt + n_new, seed=n_prompt)
    assert _distance(_replay(engine, seq, n_prompt), _reference(engine, seq, n_prompt)) < TOL


def test_a_lane_reused_by_a_second_sequence_reads_zeros(engine):
    first, second = _tokens(60, seed=21), _tokens(41, seed=22)
    _replay(engine, first, 50, lane=2)
    left = [np.asarray(engine.cache[name][2]) for name in engine._spec.names[2:]]
    assert all(np.abs(a).max() > 0 for a in left)
    assert _distance(_replay(engine, second, 37, lane=2), _reference(engine, second, 37)) < TOL


def _two_chunks(params, seq, cfg=CFG):
    """Logits after two chunks of 32 tokens by the family's chunk forward
    alone, the first chunk's K, V, tails and states written by hand: no
    engine, one program to trace."""
    T, pages = 32, 8
    spec = gh.cache_spec(cfg, BS)
    cache = {"k_pages": jnp.zeros((spec.paged_layers, (pages + 1) * BS, spec.row_width)),
             "v_pages": jnp.zeros((spec.paged_layers, (pages + 1) * BS, spec.row_width)),
             **{name: jnp.zeros((2, *shape), dtype) for name, shape, dtype in spec.lane_state}}
    table = jnp.arange(1, pages + 1, dtype=jnp.int32)
    chunk = jax.jit(lambda cache, toks, start: gh.prefill_chunk(
        params, cfg, cache, toks, start, jnp.array([T - 1]), table, jnp.int32(1), BS))
    _, k, v, _, state, _ = chunk(cache, jnp.asarray(seq[None, :T]), jnp.int32(0))
    cache["k_pages"] = cache["k_pages"].at[:, BS:BS + T].set(k[:, 0].reshape(-1, T, spec.row_width))
    cache["v_pages"] = cache["v_pages"].at[:, BS:BS + T].set(v[:, 0].reshape(-1, T, spec.row_width))
    for name, value in state.items():
        cache[name] = cache[name].at[1].set(value)
    return chunk(cache, jnp.asarray(seq[None, T:2 * T]), jnp.int32(T))[0][0]


def _softmax_over_all(y, lp, cfg):
    """The expert part weighing the chosen by a softmax over ALL the
    router's logits (renormalised or not, another model's reading)."""
    from ray_tpu.ops.moe import moe_experts

    out, counts, top_e = REAL_EXPERTS(y, lp, cfg)
    logits = jnp.dot(y, lp["router"], preferred_element_type=jnp.float32)
    right = jax.nn.softmax(jnp.take_along_axis(logits, top_e, axis=-1), axis=-1)
    wrong = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), top_e, axis=-1)
    a, _ = moe_experts(y, right, top_e, lp["w_in"], lp["w_down"])
    b, _ = moe_experts(y, wrong, top_e, lp["w_in"], lp["w_down"])
    return out - a + b, counts, top_e


REAL_EXPERTS = gh._experts


@pytest.mark.parametrize("broken", [None, *MULTIPLIERS, "softmax_over_all_logits", "head_is_not_the_embedding",
                                    "one_norm_a_layer"])
def test_a_broken_model_fails_the_tolerance(monkeypatch, broken):
    """What the tolerance is for.  Each of the four multipliers set to 1
    (dropped), the router's weights taken from a softmax over all 16
    logits, a head of its own, and the experts reading the mixer's norm
    move the logits of the same two chunks by more than twice 3e-6 (2e-5
    to 1.0 seen); intact (None) they are within a tenth of it."""
    cfg = CFG
    params = gh.init_params(CFG, jax.random.PRNGKey(5))
    # queries and keys ten times the seeded ones, for program and reference alike: under weights
    # of std 0.02 at width 64 the scores are near uniform whatever multiplies them
    params["layers"] = [dict(lp, wqkv=10 * lp["wqkv"]) if "wqkv" in lp else lp for lp in params["layers"]]
    if broken in MULTIPLIERS:
        cfg = dataclasses.replace(CFG, **{broken: 1.0})
    elif broken == "softmax_over_all_logits":
        monkeypatch.setattr(gh, "_experts", _softmax_over_all)
    elif broken == "head_is_not_the_embedding":
        other = gh.init_params(CFG, jax.random.PRNGKey(6))["embed"]
        monkeypatch.setattr(gh, "_logits", lambda x, p, c: REAL_LOGITS(x, dict(p, embed=other), c))
    elif broken == "one_norm_a_layer":
        params["layers"] = [dict(lp, norm2=2 * lp["norm2"]) for lp in params["layers"]]
        wrong = dict(params, layers=[dict(lp, norm2=lp["norm1"]) for lp in params["layers"]])
    seq = _tokens(64, seed=3)
    want = reference.full_logits(params, jnp.asarray(seq), CFG, [63])[0][0]
    got = _two_chunks(wrong if broken == "one_norm_a_layer" else params, seq, cfg)
    distance = _distance(got, want)
    assert distance < TOL / 10 if broken is None else distance > 2 * TOL


REAL_LOGITS = gh._logits


# ----------------------------------------------------------------------
# (b) the experts: a softmax over the chosen, a share, the shared expert
# ----------------------------------------------------------------------
def test_the_two_shares_add_up_to_the_uncut_layer():
    """Two chips of 8 of the 16 experts each: their parts, the shared
    expert counted once, are the uncut reference's expert part."""
    params = gh.init_params(CFG, jax.random.PRNGKey(7))
    lp = params["layers"][1]
    y = jnp.asarray(np.random.default_rng(8).normal(size=(50, CFG.d_model)), jnp.float32)
    c = {k: getattr(CFG, k) for k in reference._KEYS}
    want, want_e = reference.expert_part(y, lp, c)
    shared = np.asarray(reference.swiglu(y @ lp["w_in_shared"]) @ lp["w_down_shared"])
    total, held_pairs = shared.copy(), 0
    for first in (0, 8):
        cfg = dataclasses.replace(CFG, experts_first=first, experts_held=8)
        share = dict(lp, w_in=lp["w_in"][first:first + 8], w_down=lp["w_down"][first:first + 8])
        out, counts, top_e = gh._experts(y, share, cfg)
        assert np.array_equal(np.sort(np.asarray(top_e)), np.sort(np.asarray(want_e)))  # both chips route alike
        total += np.asarray(out) - shared
        routed, held, computed = np.asarray(counts)[:3].tolist()
        assert routed == 50 * 4 and held == computed
        held_pairs += held
        # the reference given the same share says what this chip says
        assert _distance(out, reference.expert_part(y, share, dict(c, experts_first=first))[0]) < 1e-5
    assert held_pairs == 50 * 4  # every pair is one chip's or the other's
    assert _distance(total, want) < 1e-5


def test_the_router_is_a_softmax_over_the_chosen_logits_alone():
    params = gh.init_params(CFG, jax.random.PRNGKey(3))
    lp = params["layers"][0]
    y = jnp.asarray(np.random.default_rng(1).normal(size=(20, CFG.d_model)), jnp.float32)
    w, top_e = reference.expert_weights(y, lp, {k: getattr(CFG, k) for k in reference._KEYS})
    g = np.asarray(y) @ np.asarray(lp["router"])
    assert np.array_equal(np.asarray(top_e), np.argsort(-g, axis=-1, kind="stable")[:, :4])
    chosen = np.exp(np.take_along_axis(g, np.asarray(top_e), axis=1))
    assert _distance(np.take_along_axis(np.asarray(w), np.asarray(top_e), axis=1),
                     chosen / chosen.sum(-1, keepdims=True)) < 1e-6
    assert _distance(np.asarray(w).sum(-1), 1.0) < 1e-5 and (np.asarray(w) > 0).sum(-1).tolist() == [4] * 20
    _, _, mine = gh._experts(y, lp, CFG)
    assert np.array_equal(np.asarray(mine), np.asarray(top_e))


def test_the_tied_head_reads_the_embedding_s_held_rows():
    """No head leaf; the logits of a stream are its normed rows against
    the embedding's rows over ``logits_scaling``, and a changed row of
    the embedding changes that row's logit."""
    params = gh.init_params(CFG, jax.random.PRNGKey(2))
    assert set(params) == {"embed", "norm", "layers"} and params["embed"].shape == (CFG.vocab_size, CFG.d_model)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(3, CFG.d_model)), jnp.float32)
    y = np.asarray(x) / np.sqrt((np.asarray(x) ** 2).mean(-1, keepdims=True) + 1e-5)
    assert _distance(gh._logits(x, params, CFG), y @ np.asarray(params["embed"]).T / 16) < 1e-6
    moved = dict(params, embed=params["embed"].at[7].multiply(3.0))
    change = np.asarray(gh._logits(x, moved, CFG) - gh._logits(x, params, CFG))
    assert np.abs(change[:, 7]).min() > 0 and not np.delete(change, 7, axis=1).any()


# ----------------------------------------------------------------------
# (c) the statement, the sizes
# ----------------------------------------------------------------------
def test_the_engine_holds_what_the_family_states_and_no_more():
    eng = LLMEngine(LLMConfig(model="granite_4_0_h_small_tiny", max_batch_size=3, num_blocks=70, block_size=BS))
    names = ("k_pages", "v_pages", "conv_tail_0", "ssm_state_0", "conv_tail_1", "ssm_state_1")
    assert tuple(eng.cache) == names == eng._spec.names
    cfg = eng.model_cfg
    assert eng.k_pages.shape == eng.v_pages.shape == (2, 70 * BS, cfg.n_kv_head * cfg.head_dim)  # 2 of 4 layers page
    assert eng.cache["conv_tail_1"].shape == (3, 3 * cfg.conv_dim)
    assert eng.cache["ssm_state_1"].shape == (3, 8, 16, 16) and eng.cache["ssm_state_1"].dtype == jnp.float32
    assert eng._spec.reads_cache and eng._spec.prefill_chunk == 32 and eng.bm.state_slots == 3


def test_the_published_sizes_and_the_cut():
    full = gh.GraniteHybridConfig.granite_4_0_h_small()
    assert (full.n_layer, full.layer_types.count("mamba"), full.layer_types.count("attention")) == (40, 36, 4)
    assert [i for i, kind in enumerate(full.layer_types) if kind == "attention"] == [5, 15, 25, 35]
    assert HELD.layer_types == full.layer_types[:10] and HELD.layer_types.count("attention") == 1
    assert (HELD.experts_first, HELD.experts_held, HELD.num_local_experts) == (0, 36, 72)
    assert (HELD.vocab_size, HELD.published_vocab_size) == (50176, 100352)
    assert (HELD.head_dim, HELD.n_head // HELD.n_kv_head, HELD.d_inner, HELD.conv_dim) == (128, 4, 8192, 8448)
    assert HELD.d_inner == 2 * HELD.d_model  # mamba_expand x hidden_size
    assert [getattr(HELD, k) for k in MULTIPLIERS] == [12.0, 0.22, 0.0078125, 16.0]
    spec = gh.cache_spec(HELD, 64)
    assert (spec.paged_layers, spec.row_width, spec.prefill_chunk, len(spec.lane_state)) == (1, 1024, 2048, 18)
    # a lane's state: 9 layers x (128 x 64 x 128 float32 + 3 x 8448 bf16)
    lane = sum(int(np.prod(shape)) * jnp.dtype(dtype).itemsize for _, shape, dtype in spec.lane_state)
    assert lane == 9 * (4_194_304 + 50_688)
    # the sizes of the issue's arithmetic: a layer of each kind, the ends, the whole cut
    shapes = jax.eval_shape(lambda: gh.init_params(HELD))
    count = lambda tree: sum(int(np.prod(v.shape)) for v in jax.tree_util.tree_leaves(tree))  # noqa: E731
    experts = 358_907_904 + 8_192  # router, shared, 36 held; the two norms
    assert count(shapes["layers"][0]) == 102_286_976 + experts
    assert count(shapes["layers"][5]) == 41_943_040 + experts
    assert count({k: shapes[k] for k in ("embed", "norm")}) == 205_524_992
    assert count(shapes) == 4_757_211_776  # 9.51 GB in bf16
    assert "granite_4_0_h_small_10l_ep2" in LLMConfig.__doc__


# ----------------------------------------------------------------------
# (d) through the engine
# ----------------------------------------------------------------------
def test_engine_serves_the_reference_s_tokens_and_counts_what_it_did():
    prompt = _tokens(75, seed=6).tolist()

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
    # two prompts of 75 tokens in chunks of 32: 2 whole and a tail of 11 in a bucket of 16
    assert stats["prefill_chunks"] == 6 and stats["prefill_bucket_tokens"] == 2 * (2 * 32 + 16)
    rows = stats["prefill_bucket_tokens"] + 4 * stats["steps"]
    n_l, n_m, n_a = CFG.n_layer, CFG.layer_types.count("mamba"), CFG.layer_types.count("attention")
    assert stats["moe_pairs_routed"] == 4 * n_l * rows  # every layer has its experts
    assert stats["moe_pairs_held"] == stats["moe_pairs"] == stats["moe_pairs_routed"]  # all 16 held here
    assert stats["moe_layer_programs"] == n_l * (6 + stats["steps"])
    assert stats["moe_expert_slots"] == 16 * stats["moe_layer_programs"]
    assert stats["ssm_chunk_tokens"] == 2 * 75 * n_m
    # a decode step updates the running lanes' states alone: two lanes, 7 steps each
    assert stats["ssm_lane_steps"] == 2 * 7 * n_m
    assert 0 < stats["kv_positions_attended"] <= stats["kv_positions_gathered"]
    assert stats["kv_positions_gathered"] % (BS * n_a) == 0
    assert stats["kv_blocks_in_use"] == 0 and stats["state_slots_in_use"] == 0 and stats["state_slots_total"] == 4
    assert stats["state_bytes"] > 0


def test_a_share_serves_through_the_engine_and_counts_what_it_held(monkeypatch):
    """The tiny preset holding experts 8-15 of 16: the engine's tokens
    are the reference's given the same share, and about half of the
    pairs are held, every one of them computed."""
    tiny = gh.GraniteHybridConfig.granite_4_0_h_small_tiny
    monkeypatch.setattr(gh.GraniteHybridConfig, "granite_4_0_h_small_tiny", staticmethod(
        lambda **kw: tiny(experts_first=8, experts_held=8, **kw)))
    prompt = _tokens(50, seed=9).tolist()

    async def main():
        eng = _engine()
        toks = await _drain(await eng.add_request(prompt, max_tokens=6))
        stats = eng.stats()
        await eng.stop()
        return eng, toks, stats

    eng, toks, stats = asyncio.run(main())
    assert eng.params["layers"][1]["w_in"].shape[0] == 8
    seq = np.asarray(prompt + toks, np.int32)
    want = np.asarray(reference.full_logits(eng.params, jnp.asarray(seq), eng.model_cfg)[0])
    assert [int(want[i].argmax()) for i in range(len(prompt) - 1, len(seq) - 1)] == toks
    routed = stats["moe_pairs_routed"]
    assert routed // 4 < stats["moe_pairs_held"] == stats["moe_pairs"] < 3 * routed // 4
    assert stats["moe_expert_slots"] == 8 * stats["moe_layer_programs"]


def test_preemption_by_recompute_and_an_early_join_give_the_same_tokens():
    """The hog is evicted mid-answer, prefilled again over prompt +
    answer so far (the lane's states and tails rebuilt from zeros by the
    chunks), and says what it would have said; a request that joins
    while another decodes says what it says alone."""
    prompt, n = _tokens(45, seed=8).tolist(), 40
    other = _tokens(35, seed=2).tolist()

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
    assert stats["kv_blocks_in_use"] == 0 and stats["state_slots_in_use"] == 0

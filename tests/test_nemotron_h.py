"""Nemotron-H through the serving path, held to the plain float32
reference (``benchmark/reference_nemotron_3_nano.py``) at the tiny preset
on the CPU: the pattern ``MEM*EM*E`` (every kind of layer, twice and
more), chunks of 32 tokens in scan blocks of 8, pages of 8, 32 routed
experts of which 6 a token.

The tolerance, 3e-4 absolute on logits of size about 0.5: program and
reference are both float32 here and differ in the ORDER of their sums
(the program's chunked scan from a carried state and its online softmax
over key blocks, its sorted grouped matmul, against the reference's
recurrence a position at a time, one softmax a query and a loop over
experts): 2e-7 to 4e-7 seen.  A gate applied after the norm, a router
that weighs by the biased score, a tail or a state that is not carried
move logits by 7e-4 and more: ``test_a_broken_model_fails_the_tolerance``
shows each.
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

from benchmark import reference_nemotron_3_nano as reference  # noqa: E402
from ray_tpu.models import layers, nemotron_h as nh  # noqa: E402
from ray_tpu.ops import mamba2, moe  # noqa: E402
from ray_tpu.serve.llm import LLMConfig, LLMEngine  # noqa: E402
from ray_tpu.serve.llm.engine import FINISHED  # noqa: E402

TOL = 3e-4
BS = 8  # positions a page
CFG = nh.NemotronHConfig.nemotron_3_nano_tiny(dtype=jnp.float32)
HELD = nh.NemotronHConfig.nemotron_3_nano_26l_ep4()


def _engine(**kw):
    kw = {"max_batch_size": 4, "num_blocks": 200, "block_size": BS, "seed": 5, **kw}
    return LLMEngine(LLMConfig(model="nemotron_3_nano_tiny", **kw))


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
    return (jax.jit(lambda *a: nh.prefill_chunk(*a), static_argnums=(1, 8)),
            jax.jit(lambda *a: nh.decode_forward_cached(*a), static_argnums=(1, 6)))


FORWARDS = _forwards()


def _replay(eng, seq, n_prompt, lane=1, forwards=FORWARDS):
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
    """Lane 2 serves one sequence and then, its states and tails still
    what that one left, another: the second's logits are the reference's
    (the chunk at position 0 reads zeros whatever lies in the lane), and
    the arrays did hold the first's (they are not zeros in between)."""
    first, second = _tokens(60, seed=21), _tokens(41, seed=22)
    _replay(engine, first, 50, lane=2)
    left = [np.asarray(engine.cache[name][2]) for name in engine._spec.names[2:]]
    assert all(np.abs(a).max() > 0 for a in left)
    assert _distance(_replay(engine, second, 37, lane=2), _reference(engine, second, 37)) < TOL


def _two_chunks(params, seq):
    """Logits after two chunks of 32 tokens by the family's chunk forward
    alone, the first chunk's K, V, tails and states written by hand: no
    engine, one program to trace."""
    cfg, T, pages = CFG, 32, 8
    spec = nh.cache_spec(cfg, BS)
    cache = {"k_pages": jnp.zeros((spec.paged_layers, (pages + 1) * BS, spec.row_width)),
             "v_pages": jnp.zeros((spec.paged_layers, (pages + 1) * BS, spec.row_width)),
             **{name: jnp.zeros((2, *shape), dtype) for name, shape, dtype in spec.lane_state}}
    table = jnp.arange(1, pages + 1, dtype=jnp.int32)
    chunk = jax.jit(lambda cache, toks, start: nh.prefill_chunk(
        params, cfg, cache, toks, start, jnp.array([T - 1]), table, jnp.int32(1), BS))
    _, k, v, _, state, _ = chunk(cache, jnp.asarray(seq[None, :T]), jnp.int32(0))
    cache["k_pages"] = cache["k_pages"].at[:, BS:BS + T].set(k[:, 0].reshape(-1, T, spec.row_width))
    cache["v_pages"] = cache["v_pages"].at[:, BS:BS + T].set(v[:, 0].reshape(-1, T, spec.row_width))
    for name, value in state.items():
        cache[name] = cache[name].at[1].set(value)
    return chunk(cache, jnp.asarray(seq[None, T:2 * T]), jnp.int32(T))[0][0]


@pytest.mark.parametrize("broken", [None, "gate_after_norm", "weights_by_biased_score", "tail_not_carried",
                                    "state_not_carried"])
def test_a_broken_model_fails_the_tolerance(monkeypatch, broken):
    """What the tolerance is for: each of these is a reading of the
    config another implementation could make, and each moves logits by
    more than twice 3e-4 (7e-4 to 2e-2 seen); intact (None), the same two
    chunks are within a tenth of it.
    (A rotation in attention would not show at this size: under weights
    of std 0.02 at width 64 the scores are near uniform.)"""
    if broken == "gate_after_norm":
        def out(o, z, lp, cfg):
            N = o.shape[0]
            u = o.reshape(N, cfg.n_groups, -1)
            u = (u * jax.lax.rsqrt((u * u).mean(-1, keepdims=True) + cfg.layer_norm_epsilon)).reshape(N, -1)
            return (u * lp["w_gn"] * jax.nn.silu(z)) @ lp["out_proj"]
        monkeypatch.setattr(layers, "_mamba_out", out)
    elif broken == "weights_by_biased_score":
        real = nh._experts
        # b_sel enters the weights: as if the scores themselves were biased
        monkeypatch.setattr(nh, "_experts", lambda y, lp, cfg: _biased(real, y, lp, cfg))
    elif broken == "tail_not_carried":
        real_conv = mamba2.conv_tail
        monkeypatch.setattr(mamba2, "conv_tail", lambda x, tail, *a: real_conv(x, jnp.zeros_like(tail), *a))
    elif broken == "state_not_carried":
        real_scan = mamba2.ssd_chunk
        monkeypatch.setattr(mamba2, "ssd_chunk", lambda x, dt, A, B, C, D, state, *a: real_scan(
            x, dt, A, B, C, D, jnp.zeros_like(state), *a))
    params = nh.init_params(CFG, jax.random.PRNGKey(5))
    # a selection bias five times the seeded one (std 0.1), for program and reference alike
    params["layers"] = [dict(lp, b_sel=5 * lp["b_sel"]) if "b_sel" in lp else lp for lp in params["layers"]]
    seq = _tokens(64, seed=3)
    want = reference.full_logits(params, jnp.asarray(seq), CFG, [63])[0][0]
    distance = _distance(_two_chunks(params, seq), want)
    assert distance < TOL / 10 if broken is None else distance > 2 * TOL


def _biased(real, y, lp, cfg):
    """The expert part with ``sigmoid(logits) + b_sel`` as the weights of
    the chosen, not ``sigmoid(logits)``."""
    from ray_tpu.ops.moe import moe_experts

    out, counts, top_e = real(y, lp, cfg)
    s = jax.nn.sigmoid(jnp.dot(y, lp["router"], preferred_element_type=jnp.float32))

    def weights(scores):
        w = jnp.take_along_axis(scores, top_e, axis=-1)
        return cfg.routed_scaling_factor * w / w.sum(-1, keepdims=True)

    right, _ = moe_experts(y, weights(s), top_e, lp["w_up"], lp["w_down"], gated=False)
    wrong, _ = moe_experts(y, weights(s + lp["b_sel"]), top_e, lp["w_up"], lp["w_down"], gated=False)
    return out - right + wrong, counts, top_e


# ----------------------------------------------------------------------
# (b) the experts: not gated, a sigmoid router, a share
# ----------------------------------------------------------------------
def _moe_experts_pr36(h, top_p, top_e, wgu, wd, held=None):
    """``ops.moe.moe_experts`` as it stood before this family (PR 36),
    to the letter: what OLMoE (no share) and Mistral-Small-4 (a share)
    call must still compute."""
    T, d = h.shape
    k = top_e.shape[1]
    E = wgu.shape[0]
    with jax.named_scope("moe.route"):
        expert = top_e.reshape(T * k)
        if held is not None:
            first, count = held
            assert count == E, f"{E} experts' weights for a share of {count}"
            expert = jnp.where((expert >= first) & (expert < first + count), expert - first, E)
        order = jnp.argsort(expert, stable=True)
        group_sizes = jnp.bincount(expert, length=E if held is None else E + 1).astype(jnp.int32)
        if held is not None:
            group_sizes = group_sizes[:E]
        rows = h[order // k]
    with jax.named_scope("moe.experts"):
        gate, up = jnp.split(moe.grouped_matmul(rows, wgu, group_sizes), 2, axis=-1)
        out = moe.grouped_matmul(jax.nn.silu(gate) * up, wd, group_sizes)
        if held is not None:
            out = jnp.where((jnp.arange(T * k) < group_sizes.sum())[:, None], out, 0)
    with jax.named_scope("moe.combine"):
        computed = (out != 0).any(axis=-1).sum(dtype=jnp.int32)
        out = out.astype(jnp.float32) * top_p.reshape(T * k)[order][:, None]
        back = jnp.zeros(T * k, order.dtype).at[order].set(jnp.arange(T * k, dtype=order.dtype))
        y = out[back].reshape(T, k, d).sum(axis=1).astype(h.dtype)
    return y, jnp.stack([computed, (group_sizes > 0).sum(dtype=jnp.int32), group_sizes.max()])


def _routing(T, E, k, seed):
    rng = np.random.default_rng(seed)
    top_e = np.stack([rng.choice(E, k, replace=False) for _ in range(T)]).astype(np.int32)
    top_p = rng.random((T, k)).astype(np.float32)
    return jnp.asarray(top_p / top_p.sum(-1, keepdims=True)), jnp.asarray(top_e)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("held", [None, (8, 8)], ids=["as_olmoe_calls_it", "as_mistral_small_4_calls_it"])
def test_gated_experts_are_the_function_they_were_bit_for_bit(held, dtype):
    rng = np.random.default_rng(1)
    T, d, f, E, k = 37, 64, 32, 8, 2
    h = jnp.asarray(rng.normal(size=(T, d)), dtype)
    wgu = jnp.asarray(0.1 * rng.normal(size=(E, d, 2 * f)), dtype)
    wd = jnp.asarray(0.1 * rng.normal(size=(E, f, d)), dtype)
    top_p, top_e = _routing(T, 32 if held else E, k, seed=2)
    want_y, want_c = _moe_experts_pr36(h, top_p, top_e, wgu, wd, held)
    y, c = moe.moe_experts(h, top_p, top_e, wgu, wd, held=held)
    got, want = np.asarray(y, np.float32), np.asarray(want_y, np.float32)
    # bit for bit where the result is rounded to bfloat16; in float32 to a step of the largest addend: the
    # combine is a jit of its own since PR 43, and a compiled multiply-add rounds once where two operations round twice
    assert np.array_equal(got, want) if dtype == jnp.bfloat16 else np.abs(got - want).max() <= 2.0**-23 * np.abs(want).max()
    assert np.asarray(c).tolist() == np.asarray(want_c).tolist()
    # the values are PR 36's (k = 2: two additions, in either order); the program is not since PR 43, which
    # gathers the pairs' rows once and never lays them out as [T, k, d]
    before = jax.make_jaxpr(lambda *a: _moe_experts_pr36(*a, held))(h, top_p, top_e, wgu, wd)
    after = jax.make_jaxpr(lambda *a: moe.moe_experts(*a, held=held))(h, top_p, top_e, wgu, wd)
    assert f"[{T},{k},{d}]" in str(before) and f"[{T},{k},{d}]" not in str(after)


@pytest.mark.parametrize("first, count", [(0, 32), (0, 8), (24, 8), (5, 3)])
def test_experts_without_a_gate_compute_their_own_pairs_and_no_others(first, count):
    rng = np.random.default_rng(4)
    T, d, f, E, k = 29, 64, 24, 32, 6
    h = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    w_up = jnp.asarray(0.1 * rng.normal(size=(E, f, d)), jnp.float32)  # transposed, as the function takes it
    w_down = jnp.asarray(0.1 * rng.normal(size=(E, f, d)), jnp.float32)
    top_p, top_e = _routing(T, E, k, seed=5)
    y, c = moe.moe_experts(h, top_p, top_e, w_up[first:first + count], w_down[first:first + count],
                           held=None if count == E else (first, count), gated=False)
    here = (np.asarray(top_e) >= first) & (np.asarray(top_e) < first + count)
    want = np.zeros((T, d), np.float32)  # the dense way, the held experts alone
    for t in range(T):
        for p, e in zip(np.asarray(top_p)[t], np.asarray(top_e)[t]):
            if first <= e < first + count:
                want[t] += p * (np.maximum(np.asarray(w_up)[e] @ np.asarray(h)[t], 0) ** 2 @ np.asarray(w_down)[e])
    assert _distance(y, want) < 1e-5
    assert not np.asarray(y)[~here.any(-1)].any()  # a token with no held expert gets nothing
    assert np.asarray(c).tolist()[:2] == [int(here.sum()), len(set(np.asarray(top_e)[here].tolist()))]


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Four chips of 8 of the 32 experts each: their parts, the shared
    expert counted once, are the uncut reference's expert part."""
    params = nh.init_params(CFG, jax.random.PRNGKey(7))
    lp = params["layers"][1]
    assert CFG.pattern[1] == nh.EXPERTS
    y = jnp.asarray(np.random.default_rng(8).normal(size=(50, CFG.d_model)), jnp.float32)
    c = {k: getattr(CFG, k) for k in reference._KEYS}
    want, want_e = reference.expert_part(y, lp, c)
    shared = np.maximum(np.asarray(y) @ np.asarray(lp["w_up_shared"]), 0) ** 2 @ np.asarray(lp["w_down_shared"])
    total, held_pairs = shared.copy(), 0
    for first in range(0, 32, 8):
        cfg = dataclasses.replace(CFG, experts_first=first, experts_held=8)
        share = dict(lp, w_up=lp["w_up"][first:first + 8], w_down=lp["w_down"][first:first + 8])
        out, counts, top_e = nh._experts(y, share, cfg)
        assert np.array_equal(np.sort(np.asarray(top_e)), np.sort(np.asarray(want_e)))  # every chip routes alike
        total += np.asarray(out) - shared
        routed, held, computed = np.asarray(counts)[:3].tolist()
        assert routed == 50 * 6 and held == computed
        held_pairs += held
        # the reference given the same share says what this chip says
        assert _distance(out, reference.expert_part(y, share, dict(c, experts_first=first))[0]) < 1e-5
    assert held_pairs == 50 * 6  # every pair is some chip's
    assert _distance(total, want) < 1e-5


def test_the_router_is_a_sigmoid_whose_bias_selects_and_does_not_weigh():
    params = nh.init_params(CFG, jax.random.PRNGKey(3))
    lp = dict(params["layers"][1])
    lp["b_sel"] = lp["b_sel"].at[5].set(10.0)  # expert 5 is chosen by every token, by its bias alone
    y = jnp.asarray(np.random.default_rng(1).normal(size=(20, CFG.d_model)), jnp.float32)
    w, top_e = reference.expert_weights(y, lp, {k: getattr(CFG, k) for k in reference._KEYS})
    s = 1 / (1 + np.exp(-np.asarray(y) @ np.asarray(lp["router"])))
    assert (np.asarray(top_e)[:, 0] == 5).all()
    chosen = np.take_along_axis(s, np.asarray(top_e), axis=1)
    assert _distance(np.take_along_axis(np.asarray(w), np.asarray(top_e), axis=1),
                     2.5 * chosen / chosen.sum(-1, keepdims=True)) < 1e-6
    assert _distance(np.asarray(w).sum(-1), 2.5) < 1e-5  # norm_topk_prob, routed_scaling_factor
    _, _, mine = nh._experts(y, lp, CFG)
    assert np.array_equal(np.asarray(mine), np.asarray(top_e))


# ----------------------------------------------------------------------
# (c) the statement, the sizes
# ----------------------------------------------------------------------
def test_the_engine_holds_what_the_family_states_and_no_more():
    eng = LLMEngine(LLMConfig(model="nemotron_3_nano_tiny", max_batch_size=3, num_blocks=70, block_size=BS))
    names = ("k_pages", "v_pages", "conv_tail_0", "ssm_state_0", "conv_tail_1", "ssm_state_1",
             "conv_tail_2", "ssm_state_2")
    assert tuple(eng.cache) == names == eng._spec.names
    cfg = eng.model_cfg
    assert eng.k_pages.shape == eng.v_pages.shape == (2, 70 * BS, cfg.n_kv_head * cfg.head_dim)  # 2 of 8 layers page
    assert eng.cache["conv_tail_1"].shape == (3, 3 * cfg.conv_dim)
    assert eng.cache["ssm_state_1"].shape == (3, 8, 8, 16) and eng.cache["ssm_state_1"].dtype == jnp.float32
    assert eng._spec.reads_cache and eng._spec.prefill_chunk == 32 and eng.bm.state_slots == 3


def test_the_published_sizes_and_the_cut():
    full = nh.NemotronHConfig.nemotron_3_nano()
    assert (full.n_layer, full.pattern.count("M"), full.pattern.count("E"), full.pattern.count("*")) == (52, 23, 23, 6)
    assert (HELD.n_layer, HELD.pattern.count("M"), HELD.pattern.count("E"), HELD.pattern.count("*")) == (26, 12, 11, 3)
    assert HELD.pattern == full.pattern[:26] == "MEMEM*EMEMEM*EMEMEM*EMEMEM"
    assert (HELD.experts_first, HELD.experts_held, HELD.n_routed_experts) == (0, 32, 128)
    assert (HELD.vocab_size, HELD.published_vocab_size) == (32768, 131072)
    spec = nh.cache_spec(HELD, 64)
    assert (spec.paged_layers, spec.row_width, spec.prefill_chunk, len(spec.lane_state)) == (3, 256, 2048, 24)
    # a lane's state: 12 layers x (64 x 64 x 128 float32 + 3 x 6144 bf16) = 25.6 MB
    lane = sum(int(np.prod(shape)) * jnp.dtype(dtype).itemsize for _, shape, dtype in spec.lane_state)
    assert lane == 12 * (2_097_152 + 36_864)
    # the sizes of the issue's arithmetic: a layer of each kind, the ends, the whole cut
    shapes = jax.eval_shape(lambda: nh.init_params(HELD))
    count = lambda tree: sum(int(np.prod(v.shape)) for v in jax.tree_util.tree_leaves(tree))  # noqa: E731
    by_kind = {kind: count(shapes["layers"][HELD.pattern.index(kind)]) for kind in "ME*"}
    assert by_kind == {"M": 38_744_896, "E": 339_593_984, "*": 23_399_040}
    assert count({k: shapes[k] for k in ("embed", "norm", "lm_head")}) == 176_163_456
    assert count(shapes) == 4_446_833_152  # 8.89 GB in bf16
    assert "nemotron_3_nano_26l_ep4" in LLMConfig.__doc__


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
    n_m, n_e, n_a = (CFG.pattern.count(kind) for kind in "ME*")
    assert stats["moe_pairs_routed"] == 6 * n_e * rows
    assert stats["moe_pairs_held"] == stats["moe_pairs"] == stats["moe_pairs_routed"]  # all 32 held here
    assert stats["moe_layer_programs"] == n_e * (6 + stats["steps"])
    assert stats["moe_expert_slots"] == 32 * stats["moe_layer_programs"]
    assert stats["ssm_chunk_tokens"] == 2 * 75 * n_m
    # a decode step updates the running lanes' states alone: two lanes, 7 steps each
    assert stats["ssm_lane_steps"] == 2 * 7 * n_m
    assert 0 < stats["kv_positions_attended"] <= stats["kv_positions_gathered"]
    assert stats["kv_positions_gathered"] % (BS * n_a) == 0
    assert stats["kv_blocks_in_use"] == 0 and stats["state_slots_in_use"] == 0 and stats["state_slots_total"] == 4
    assert stats["state_bytes"] > 0


def NemotronTinyShare(**kw):
    base = nh.NemotronHConfig(**{f.name: getattr(CFG, f.name) for f in dataclasses.fields(CFG)})
    return dataclasses.replace(base, experts_first=8, experts_held=8, **kw)


def test_a_share_serves_through_the_engine_and_counts_what_it_held(monkeypatch):
    """The tiny preset holding experts 8-15 of 32: the engine's tokens
    are the reference's given the same share, and about a quarter of the
    pairs are held, every one of them computed."""
    monkeypatch.setattr(nh.NemotronHConfig, "nemotron_3_nano_tiny", staticmethod(
        lambda **kw: NemotronTinyShare(**kw)))
    prompt = _tokens(50, seed=9).tolist()

    async def main():
        eng = _engine()
        toks = await _drain(await eng.add_request(prompt, max_tokens=6))
        stats = eng.stats()
        await eng.stop()
        return eng, toks, stats

    eng, toks, stats = asyncio.run(main())
    assert eng.params["layers"][1]["w_up"].shape[0] == 8
    seq = np.asarray(prompt + toks, np.int32)
    want = np.asarray(reference.full_logits(eng.params, jnp.asarray(seq), eng.model_cfg)[0])
    assert [int(want[i].argmax()) for i in range(len(prompt) - 1, len(seq) - 1)] == toks
    assert 0 < stats["moe_pairs_held"] == stats["moe_pairs"] < stats["moe_pairs_routed"] // 2
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

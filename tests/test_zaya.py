"""ZAYA1 through the serving path, held to the plain float32 reference
(``benchmark/reference_zaya1.py``) at the tiny preset on the CPU: 4
layers of compressed convolutional attention (8 query heads on 2 K/V
heads of 16) and a top-1 router of width 16 over 4 experts and the skip
output, pages of 4, prompt chunks of 8.

The tolerance, 5e-6 absolute on logits of size about 1: program and
reference are both float32 here and differ in the ORDER of their sums
(the program's convolutions from a lane's tail, chunk by chunk, its
online softmax over key blocks, its sorted rows through a grouped
matmul; against the reference's shifted sequence, one softmax a query
and a dense sum over the experts): 5e-7 seen.  The value shift off, the
q-k mean off, the second convolution depthwise, the rotation over a
whole head, ``gamma`` 0, the skip output computed as an expert, ``p[e]``
taken as 1 and the residual scales taken as 1 move logits by 1e-2 and
more: ``test_a_wrong_model_fails_the_tolerance`` shows each.  Top-1
routing makes a flip swap a token's WHOLE expert; at float32 against
float32 none flips on these seeds, and every test that compares logits
also compares the outputs chosen.
"""

import asyncio
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import reference_zaya1 as reference  # noqa: E402
from ray_tpu.models import zaya  # noqa: E402
from ray_tpu.ops import cca  # noqa: E402
from ray_tpu.serve.llm import LLMConfig, LLMEngine  # noqa: E402
from ray_tpu.serve.llm.engine import FINISHED, decode_step, prefill_step  # noqa: E402

TOL = 5e-6
BS = 4  # positions a page
CFG = zaya.ZayaConfig.zaya1_tiny(dtype=jnp.float32)
CELL = zaya.ZayaConfig.zaya1_8b_20l()
L, SKIP = CFG.n_layer, CFG.num_experts


def _engine(**kw):
    kw = {"max_batch_size": 4, "num_blocks": 300, "block_size": BS, "seed": 5, **kw}
    return LLMEngine(LLMConfig(model="zaya1_tiny", **kw))


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


FORWARDS = (jax.jit(lambda *a: zaya.prefill_chosen(*a), static_argnums=(1, 8)),
            jax.jit(lambda *a: zaya.decode_chosen(*a), static_argnums=(1, 6)))


def _chunk(eng, rid, seq, start, m, lane, bucket=None):
    """Positions ``start .. start + m`` of seq through the family's
    chunk forward (-> its results) and then through the engine's own
    prefill program, which writes them into the engine's cache."""
    bm = eng.bm
    bucket = bucket or eng._prefill_bucket(m, eng._spec.prefill_chunk)
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :m] = seq[start:start + m]
    bm.advance(rid, m)
    last, table = np.array([m - 1], np.int32), bm.block_table(rid, bm.blocks_needed(eng.max_ctx))
    out = FORWARDS[0](eng.params, eng.model_cfg, eng.cache, toks, np.int32(start), last, table, np.int32(lane),
                      bm.block_size)
    eng._run_on_cache(eng._prefill_jit, toks, bm.phys_indices(rid, start + m, bucket, start=start), last,
                      np.zeros(1, np.float32), eng._next_rng(), np.int32(start), table, np.int32(lane))
    return out


def _replay(eng, seqs, n_prompts, lanes_used=(1,)):
    """Sequences through the engine's own cache and lane state by the
    engine's own programs, and the logits and chosen outputs of the
    family's forwards on the way: each prompt in chunks into its lane
    (the last chunk's logits are the prompt's), then one decode step a
    position for all of them at once, the other lanes idle beside them.
    -> for each sequence, (logits [len(seq) - n_prompt + 1, V], chosen
    [L, the same positions]) for positions n_prompt - 1 .. (the
    sequences end together)."""
    cfg = eng.model_cfg
    bm, bs, lanes = eng.bm, eng.bm.block_size, eng.config.max_batch_size
    pages = bm.blocks_needed(eng.max_ctx)
    most = eng._spec.prefill_chunk
    rids = [f"replay-{len(seq)}-{lane}" for seq, lane in zip(seqs, lanes_used)]
    logits, chose = [[] for _ in seqs], [[] for _ in seqs]
    for i, (rid, seq, n_prompt, lane) in enumerate(zip(rids, seqs, n_prompts, lanes_used)):
        bm.allocate(rid, len(seq))
        for start in range(0, n_prompt, most):
            m = min(most, n_prompt - start)
            out = _chunk(eng, rid, seq, start, m, lane)
        logits[i].append(out[0][0])
        chose[i].append(out[-1][:, m - 1, 0])
    steps = {len(seq) - n for seq, n in zip(seqs, n_prompts)}
    assert len(steps) == 1
    for step in range(steps.pop()):
        tok, lengths, write = (np.zeros(lanes, np.int32) for _ in range(3))
        tables = np.zeros((lanes, pages), np.int32)
        for rid, seq, n_prompt, lane in zip(rids, seqs, n_prompts, lanes_used):
            pos = n_prompt + step
            tok[lane], lengths[lane], tables[lane] = seq[pos], pos, bm.block_table(rid, pages)
            bm.advance(rid, 1)
            write[lane] = bm.phys_index(rid, pos)
        out = FORWARDS[1](eng.params, cfg, eng.cache, tok, tables, lengths, bs)
        for i, lane in enumerate(lanes_used):
            logits[i].append(out[0][lane])
            chose[i].append(out[-1][:, lane, 0])
        eng._run_on_cache(eng._decode_jit, tok, lengths, tables, write, np.zeros(lanes, np.float32),
                          eng._next_rng())
    for rid, seq in zip(rids, seqs):
        assert bm.blocks_held(rid) == -(-len(seq) // bs)  # the sequence's pages, counted once whatever the layers
        bm.free(rid)
    return [(np.stack([np.asarray(x) for x in rows]), np.stack([np.asarray(e) for e in es], axis=1))
            for rows, es in zip(logits, chose)]


def _replay_one(eng, seq, n_prompt, lane=1):
    return _replay(eng, [seq], [n_prompt], (lane,))[0]


def _reference(eng, seq, n_prompt, wrong=None, params=None):
    """-> (logits at positions n_prompt - 1 .., the outputs chosen there [L, positions])."""
    logits, chose, _ = reference.full_logits(params or eng.params, jnp.asarray(seq), eng.model_cfg,
                                             list(range(n_prompt - 1, len(seq))), wrong=wrong)
    return np.asarray(logits), np.asarray(chose)[:, n_prompt - 1:, 0]


def _agrees(mine, theirs):
    """The program's (logits, chosen) are the reference's: the same
    outputs chosen in every layer, logits within the tolerance."""
    return np.array_equal(mine[1], theirs[1]) and _distance(mine[0], theirs[0]) < TOL


@pytest.fixture(scope="module")
def engine():
    return _engine()


# ----------------------------------------------------------------------
# (a) chunks, then decode, against the reference: logits and routing
# ----------------------------------------------------------------------
@pytest.mark.parametrize("n_prompt, n_new", [
    (1, 6),      # a prompt of one token: both convolutions and the value shift see zeros
    (2, 9),      # the second convolution's first real predecessor
    (8, 5),      # exactly one chunk
    (21, 10),    # three chunks, the last ragged: the tails cross two chunk boundaries, pads leave them alone
    (33, 3),     # the last chunk a single token
    (10, 70),    # decode for many steps past the prompt
])
def test_chunked_prefill_then_decode_match_the_reference(engine, n_prompt, n_new):
    seq = _tokens(n_prompt + n_new, seed=n_prompt)
    assert _agrees(_replay_one(engine, seq, n_prompt), _reference(engine, seq, n_prompt))
    assert engine.bm.blocks_in_use == 0


@pytest.mark.parametrize("cut", range(1, 13))
def test_a_prompt_cut_anywhere_gives_the_uncut_prompt_s_logits_and_tails(engine, cut):
    """13 tokens as one program in lane 0, and as two (``cut`` tokens,
    then the rest from the lane's tails and pages) in lane 2, every
    program padded to 16: the last position's logits, the outputs chosen
    there and every layer's tail after it are the same."""
    seq, T = _tokens(13, seed=77), 13
    eng, tails = engine, {}
    for lane, cuts in ((0, (0, T)), (2, (0, cut, T))):
        rid = f"cut-{cut}-{lane}"
        eng.bm.allocate(rid, T)
        for start, end in zip(cuts, cuts[1:]):
            out = _chunk(eng, rid, seq, start, end - start, lane, bucket=16)
        tails[lane] = (out[0][0], out[-1][:, end - start - 1, 0],
                       [np.asarray(eng.cache[zaya.tail_name(i)][lane]) for i in range(L)])
        eng.bm.free(rid)
    assert np.array_equal(tails[0][1], tails[2][1])
    assert _distance(tails[0][0], tails[2][0]) < TOL
    assert all(_distance(a, b) < TOL and np.abs(a).max() > 0 for a, b in zip(tails[0][2], tails[2][2]))
    want, chose = _reference(eng, np.append(seq, 0), T)
    assert _distance(tails[2][0], want[0]) < TOL and np.array_equal(tails[2][1], chose[:, 0])


def test_two_lanes_run_beside_two_idle_ones_and_an_idle_lane_s_tails_are_left_alone(engine):
    """Lanes 0 and 3 decode, 1 and 2 do not (``active``): the running
    lanes' logits are the reference's, and what lane 2 held before (a
    sequence that left) is what it holds after, bit for bit."""
    _replay_one(engine, _tokens(30, seed=40), 20, lane=2)
    names = [n for n, *_ in engine._spec.lane_state]
    before = {n: np.asarray(engine.cache[n][2]) for n in names}
    assert all(np.abs(v).max() > 0 for v in before.values())
    seqs = [_tokens(26, seed=41), _tokens(37, seed=42)]
    got = _replay(engine, seqs, [11, 22], (0, 3))
    for seq, n, mine in zip(seqs, (11, 22), got):
        assert _agrees(mine, _reference(engine, seq, n))
    assert all(np.array_equal(np.asarray(engine.cache[n][2]), before[n]) for n in names)


def test_a_lane_taken_over_by_a_successor_starts_from_zeros(engine):
    """The predecessor leaves a tail in every layer of the lane; the
    successor's first chunk (start 0) reads zeros."""
    _replay_one(engine, _tokens(60, seed=21), 50, lane=2)
    assert all(np.abs(np.asarray(engine.cache[n][2])).max() > 0 for n, *_ in engine._spec.lane_state)
    second = _tokens(14, seed=22)
    assert _agrees(_replay_one(engine, second, 2, lane=2), _reference(engine, second, 2))


@pytest.mark.parametrize("wrong", [None, *reference.WRONG])
def test_a_wrong_model_fails_the_tolerance(engine, wrong):
    """What the tolerance is for.  The reference told a model with one
    mechanism off (``reference_zaya1.WRONG``) is more than a thousand
    times 5e-6 from the program (1e-2 to 0.7 seen); told the truth
    (None) it is within it.  The sequence routes tokens to the skip
    output, or ``skip_is_an_expert`` would have nothing to show."""
    seq = _tokens(60, seed=3)
    mine = _replay_one(engine, seq, 45)
    truth = _reference(engine, seq, 45)
    assert (truth[1] == SKIP).any() and (truth[1] != SKIP).any()
    if wrong is None:
        assert _agrees(mine, truth)
    else:
        assert _distance(mine[0], _reference(engine, seq, 45, wrong)[0]) > 1000 * TOL


# ----------------------------------------------------------------------
# (b) the parts
# ----------------------------------------------------------------------
def _mix_inputs(T, seed=0):
    rng = np.random.default_rng(seed)
    S, hd, heads = CFG.latent_dim, CFG.head_dim, CFG.n_head + CFG.n_kv_head
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    w = {"conv0_w": f(S, 2), "conv0_b": f(S), "conv1_w": f(heads, 2, hd, hd) / 4, "conv1_b": f(S)}
    return f(T, S), f(T, CFG.shifted_dim), f(CFG.tail_dim), w


def test_the_mixing_is_its_equations_written_out():
    T, (S, hd) = 9, (CFG.latent_dim, CFG.head_dim)
    s, v2, tail, w = _mix_inputs(T)
    c1, v2_prev, after = cca.cca_mix_chunk(s, v2, tail, w, jnp.int32(T))
    s_all = np.concatenate([np.asarray(tail[:S])[None], np.asarray(s)])  # row t + 1 is position t
    c0 = np.asarray(w["conv0_b"]) + np.asarray(w["conv0_w"])[:, 0] * s_all[:-1] + np.asarray(w["conv0_w"])[:, 1] * s_all[1:]
    c0_all = np.concatenate([np.asarray(tail[S:2 * S])[None], c0])
    W1 = np.asarray(w["conv1_w"])
    want = np.stack([
        np.concatenate([c0_all[t].reshape(-1, hd)[h] @ W1[h, 0] + c0_all[t + 1].reshape(-1, hd)[h] @ W1[h, 1]
                        for h in range(W1.shape[0])]) for t in range(T)]) + np.asarray(w["conv1_b"])
    assert _distance(c1, want) < 1e-5
    assert _distance(v2_prev, np.concatenate([np.asarray(tail[2 * S:])[None], np.asarray(v2)[:-1]])) == 0
    assert _distance(after, np.concatenate([np.asarray(s)[-1], c0[-1], np.asarray(v2)[-1]])) < 1e-6


@pytest.mark.parametrize("n_valid", [1, 4, 9])
def test_the_mixing_s_pads_leave_the_tail_alone_and_a_step_is_a_chunk_of_one(n_valid):
    s, v2, tail, w = _mix_inputs(9, seed=n_valid)
    whole = cca.cca_mix_chunk(s, v2, tail, w, jnp.int32(n_valid))
    short = cca.cca_mix_chunk(s[:n_valid], v2[:n_valid], tail, w, jnp.int32(n_valid))
    assert _distance(whole[2], short[2]) == 0 and _distance(whole[0][:n_valid], short[0]) < 1e-6
    # one position a lane, lane 0 running and lane 1 not: the chunk's first position, and the tail as it was
    tails = jnp.stack([tail, tail])
    c1, v2_prev, after = cca.cca_mix_step(s[:2], v2[:2], tails, w, jnp.asarray([True, False]))
    first = cca.cca_mix_chunk(s[:1], v2[:1], tail, w, jnp.int32(1))
    assert _distance(c1[0], first[0][0]) < 1e-6 and _distance(v2_prev[0], first[1][0]) == 0
    assert _distance(after[0], first[2]) < 1e-6 and np.array_equal(np.asarray(after[1]), np.asarray(tail))


def test_every_head_leaves_the_mixing_with_norm_sqrt_hd_and_a_key_head_times_tau():
    s, _, _, _ = _mix_inputs(5)
    c1 = jnp.asarray(np.random.default_rng(1).normal(size=s.shape), jnp.float32)
    tau = jnp.asarray([0.8, 1.2], jnp.float32)
    q, k = cca.cca_heads(s, c1, tau, CFG.n_head, CFG.n_kv_head)
    assert q.shape == (5, 2, 4, CFG.head_dim) and k.shape == (5, 2, CFG.head_dim)
    assert np.allclose(np.linalg.norm(np.asarray(q), axis=-1), CFG.head_dim ** 0.5, rtol=1e-5)
    assert np.allclose(np.linalg.norm(np.asarray(k), axis=-1), CFG.head_dim ** 0.5 * np.asarray(tau), rtol=1e-5)


def _layer_inputs(T=24, seed=0):
    lp = zaya.init_params(CFG, jax.random.PRNGKey(seed))["layers"][1]
    rng = np.random.default_rng(seed)
    y = jnp.asarray(rng.normal(size=(T, CFG.d_model)), jnp.float32)
    r = jnp.asarray(rng.normal(size=(T, CFG.router_hidden_size)), jnp.float32)
    return CFG, lp, y, r


def test_a_token_routed_to_output_16_leaves_the_expert_part_as_a2_x_exactly_and_is_counted():
    cfg, lp, y, r = _layer_inputs()
    x = jnp.asarray(np.random.default_rng(9).normal(size=y.shape), jnp.float32)
    out, _, c, top_e = zaya._experts(y, r, lp, cfg)
    skipped = np.asarray(top_e[:, 0]) == SKIP
    assert 0 < skipped.sum() < len(skipped), "the draw sends some tokens to the skip output and some to experts"
    merged = np.asarray(zaya._merge(x, out, lp["a2"], lp["b2"]))
    assert np.array_equal(merged[skipped], np.asarray(lp["a2"] * x)[skipped])
    assert not np.array_equal(merged[~skipped], np.asarray(lp["a2"] * x)[~skipped])
    routed, held, computed, hit, peak, n_skipped = (int(v) for v in c)
    assert (routed, n_skipped, held) == (len(skipped), skipped.sum(), (~skipped).sum())
    assert held + n_skipped == routed and computed == held and 0 < hit <= cfg.num_experts and peak <= held
    # a bias that sends EVERY token there: the whole part is zero, and no expert is visited
    beta = jnp.zeros(SKIP + 1).at[SKIP].set(10.0)
    out, _, c, top_e = zaya._experts(y, r, {**lp, "router_beta": beta}, cfg)
    assert (np.asarray(top_e) == SKIP).all() and not np.asarray(out).any()
    assert [int(v) for v in c] == [len(skipped), 0, 0, 0, 0, len(skipped)]


def test_the_biases_choose_and_do_not_weigh():
    cfg, lp, y, r = _layer_inputs()
    _, p, e = zaya._router(y, r, lp, cfg)
    beta = jnp.zeros(SKIP + 1).at[2].set(10.0)
    _, p2, e2 = zaya._router(y, r, {**lp, "router_beta": beta}, cfg)
    full = reference.router(y, r, lp, {"layer_norm_epsilon": cfg.layer_norm_epsilon}, None)[1]
    assert (np.asarray(e2) == 2).all() and not (np.asarray(e) == 2).all()
    assert _distance(p2[:, 0], np.asarray(full)[:, 2]) < 1e-6  # the probability of output 2, not that plus 10


def test_two_shares_of_the_experts_add_up_to_the_whole_layer():
    """Experts 0-1 on one chip and 2-3 on another: each routes over all
    five outputs and computes its own experts' part; the parts add up
    to what the whole layer gives (the skip output belongs to nobody)."""
    cfg, lp, y, r = _layer_inputs(T=40)
    whole, r_whole, c, top_e = zaya._experts(y, r, lp, cfg)
    parts, held = [], 0
    for first in (0, 2):
        share = zaya.ZayaConfig.zaya1_tiny(dtype=jnp.float32, experts_first=first, experts_held=2)
        mine = {**lp, "w_in": lp["w_in"][first:first + 2], "w_down": lp["w_down"][first:first + 2]}
        out, r_share, c_share, e_share = zaya._experts(y, r, mine, share)
        assert np.array_equal(np.asarray(e_share), np.asarray(top_e)) and _distance(r_share, r_whole) == 0
        parts.append(out)
        held += int(c_share[1])
    assert _distance(parts[0] + parts[1], whole) < 1e-6 and np.abs(np.asarray(whole)).max() > 1e-3
    assert held == int(c[1]) and held + int(c[5]) == int(c[0])


# ----------------------------------------------------------------------
# (c) the statement, the sizes, the names
# ----------------------------------------------------------------------
def test_the_engine_holds_pages_and_a_tail_for_every_layer():
    eng = LLMEngine(LLMConfig(model="zaya1_tiny", max_batch_size=3, num_blocks=70, block_size=BS, max_model_len=256))
    names = ("k_pages", "v_pages", *(f"cca_tail_{i}" for i in range(L)))
    assert tuple(eng.cache) == names == eng._spec.names
    cfg = eng.model_cfg
    assert eng.k_pages.shape == eng.v_pages.shape == (L, 70 * BS, cfg.n_kv_head * cfg.head_dim)
    assert eng.cache["cca_tail_3"].shape == (3, 2 * 160 + 16) and cfg.tail_dim == 336
    assert eng._spec.reads_cache and eng._spec.prefill_chunk == 8 and eng.bm.state_slots == 3


def test_the_cell_s_preset_states_20480_bytes_a_position_and_107520_a_lane():
    spec = zaya.cache_spec(CELL, 64)
    assert (spec.paged_layers, spec.row_width, spec.prefill_chunk, spec.v_pool) == (20, 256, 2048, True)
    assert 2 * spec.paged_layers * spec.row_width * jnp.dtype(CELL.dtype).itemsize == 20_480
    assert spec.lane_state[0] == ("cca_tail_0", (2 * 1280 + 128,), jnp.bfloat16) and len(spec.lane_state) == 20
    lane = sum(int(np.prod(shape)) * jnp.dtype(dtype).itemsize for _, shape, dtype in spec.lane_state)
    assert lane == 20 * 5_376 == 107_520
    with pytest.raises(ValueError, match="kernels of 2 and 2"):
        zaya.cache_spec(zaya.ZayaConfig.zaya1_tiny(cca_time1=3), 4)


def test_the_published_sizes_reckoned_again():
    full = zaya.ZayaConfig.zaya1_8b()
    assert (full.n_layer, CELL.n_layer, full.d_model, full.n_head, full.n_kv_head, full.head_dim) == (40, 20, 2048, 8, 2, 128)
    assert (full.vocab_size, full.num_experts, full.num_experts_per_tok, full.moe_intermediate_size) == (262272, 16, 1, 2048)
    assert (full.router_hidden_size, full.partial_rotary_factor, full.rope_theta) == (256, 0.5, 5e6)
    assert (full.max_seq_len, full.layer_norm_epsilon, full.cca_time0, full.cca_time1) == (131072, 1e-5, 2, 2)
    count = lambda tree: sum(int(np.prod(v.shape)) for v in jax.tree_util.tree_leaves(tree))  # noqa: E731
    shapes = jax.eval_shape(lambda: zaya.init_params(CELL))
    lp = shapes["layers"][0]
    attention = count({k: lp[k] for k in ("wqkv", "wo", "conv0_w", "conv0_b", "conv1_w", "conv1_b", "tau")})
    router = count({k: v for k, v in lp.items() if k.startswith("router_")})
    assert (attention, router, count((lp["w_in"], lp["w_down"]))) == (5_575_682, 660_498, 201_326_592)
    assert count(lp) == 207_575_060 and "lm_head" not in shapes and count(shapes["embed"]) == 537_133_056
    assert count(shapes) == 20 * 207_575_060 + 537_135_104 == 4_688_636_304  # 9.38 GB in bf16
    assert all(v.dtype == jnp.bfloat16 for v in jax.tree_util.tree_leaves(shapes))
    assert "zaya1_8b_20l" in LLMConfig.__doc__


def test_the_seeded_weights_leave_no_mechanism_invisible():
    """A scale of 1, a gamma of 0, a temperature of 1 or a router whose
    17 logits read alike would pass with the mechanism deleted; and what
    reads a gelu's output has columns that sum to zero, so that no
    output has a bias of the seed's own."""
    lp = zaya.init_params(CFG, jax.random.PRNGKey(2))["layers"][0]
    for name in ("a1", "b1", "a2", "b2"):
        v = np.asarray(lp[name])
        assert 0.75 <= v.min() < 0.9 < 1.1 < v.max() <= 1.25, name
    tau = np.asarray(lp["tau"])
    assert ((1.5 <= tau) & (tau <= 2.5)).all() and tau[0] != tau[1]  # a peaked softmax: init_params says why
    assert float(lp["router_gamma"]) == 0.5 and not np.asarray(lp["router_beta"]).any()
    assert np.abs(np.asarray(lp["conv0_w"])).max() <= 0.707 and np.asarray(lp["conv1_w"]).std() > 0.1
    for name in ("router_w2", "router_w3"):
        assert np.abs(np.asarray(lp[name]).sum(0)).max() < 1e-5, name
    cfg, lp, y, r = _layer_inputs(T=2000, seed=4)
    e = np.asarray(zaya._router(y, r, lp, cfg)[2])[:, 0]
    share = np.bincount(e, minlength=SKIP + 1) / len(e)
    assert share.min() > 0.05, f"every one of the five outputs is chosen: {share}"


@pytest.mark.parametrize("program", ["serve_prefill", "serve_decode"])
def test_the_programs_carry_the_scopes_the_traces_are_read_by(program):
    eng = _engine()
    cfg, spec, bs = eng.model_cfg, eng._spec, BS
    pages = eng.bm.blocks_needed(eng.max_ctx)
    cache = [eng.cache[n] for n in spec.names]
    key = jax.random.PRNGKey(0)
    if program == "serve_prefill":
        lowered = jax.jit(lambda *a: prefill_step(cfg, 0, bs, spec, *a)).lower(
            eng.params, *cache, jnp.zeros((1, 8), jnp.int32), jnp.zeros(8, jnp.int32), jnp.zeros(1, jnp.int32),
            jnp.zeros(1, jnp.float32), key, jnp.int32(0), jnp.zeros(pages, jnp.int32), jnp.int32(0))
    else:
        lowered = jax.jit(lambda *a: decode_step(cfg, 0, bs, spec, *a)).lower(
            eng.params, *cache, jnp.zeros(4, jnp.int32), jnp.zeros(4, jnp.int32), jnp.zeros((4, pages), jnp.int32),
            jnp.zeros(4, jnp.int32), jnp.zeros(4, jnp.float32), key)
    text = lowered.as_text(debug_info=True)
    for scope in ("attn.cca.mix", "attn.cca/", "moe.router", "moe.route", "moe.experts", "moe.combine"):
        assert scope in text, scope


# ----------------------------------------------------------------------
# (d) through the engine
# ----------------------------------------------------------------------
def test_engine_serves_the_reference_s_tokens_and_counts_what_it_did():
    prompt = _tokens(21, seed=6).tolist()

    async def main():
        eng = _engine()
        reqs = [await eng.add_request(prompt, max_tokens=8) for _ in range(2)]
        while any(r.slot < 0 for r in reqs):
            await asyncio.sleep(0.005)
        reserved = eng.stats()["kv_blocks_in_use"]
        first, second = await asyncio.gather(*[_drain(r) for r in reqs])
        stats = eng.stats()
        await eng.stop()
        return eng, first, second, stats, reserved

    eng, first, second, stats, reserved = asyncio.run(main())
    assert first == second and len(first) == 8
    seq = np.asarray(prompt + first, np.int32)
    want = np.asarray(reference.full_logits(eng.params, jnp.asarray(seq), eng.model_cfg)[0])
    assert [int(want[i].argmax()) for i in range(len(prompt) - 1, len(seq) - 1)] == first
    # a sequence reserves ceil((prompt + max_tokens) / block) pages ONCE, not once a layer
    assert reserved == 2 * -(-(21 + 8) // BS)
    # two prompts of 21 tokens in chunks of 8: two whole and a tail of 5 in a bucket of 8
    assert stats["prefill_chunks"] == 6 and stats["prefill_bucket_tokens"] == 2 * 24
    # every row a program was given made ONE pair in every layer: the chunks' 48 rows, and 7 steps of 4 lanes
    rows = 2 * 24 + 7 * 4
    assert stats["moe_pairs_routed"] == rows * L and stats["moe_layer_programs"] == (6 + 7) * L
    assert stats["moe_pairs_held"] + stats["moe_pairs_skipped"] == stats["moe_pairs_routed"]
    assert stats["moe_pairs"] == stats["moe_pairs_held"] and 0 < stats["moe_pairs_skipped"] < rows * L
    assert stats["moe_expert_slots"] == (6 + 7) * L * CFG.num_experts
    # two lanes decode positions 21 .. 27 in every layer
    assert stats["kv_positions_attended"] == L * 2 * sum(range(21, 28))
    assert stats["kv_positions_gathered"] == L * 2 * sum(-(-n // BS) * BS for n in range(21, 28))
    assert stats["kv_blocks_in_use"] == 0 and stats["state_slots_in_use"] == 0 and stats["state_slots_total"] == 4
    assert stats["state_bytes_held"] == 4 * L * CFG.tail_dim * 4  # the tails float32 here
    # a chunk reads and writes its lane's tails, a decode step every lane's
    assert stats["state_bytes"] == 2 * L * CFG.tail_dim * 4 * (6 + 7 * 4)
    report = stats["kv_leak_report"]
    assert report["blocks_in_use"] == 0 and report["total_allocs"] == report["total_frees"]


def test_preemption_by_recompute_and_an_early_join_give_the_same_tokens():
    """The hog is evicted mid-answer, prefilled again over prompt +
    answer so far (the lane's tails rebuilt by the chunks), and says
    what it would have said; a request that joins while another decodes
    says what it says alone."""
    prompt, n = _tokens(19, seed=8).tolist(), 30
    other = _tokens(13, seed=2).tolist()

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

"""Mellum 2 through the serving path, held to the plain float32
reference (``benchmark/reference_mellum2.py``) at the tiny preset on the
CPU: two periods of ``sliding, sliding, sliding, full``, a window of 16
(a ring of 15 rows a lane a window layer), pages of 8, prompt chunks of
8 and of 24 (neither divides the ring), 8 experts of which 2 a token,
four queries a K/V head, YaRN's ramp inside a head's 8 pairs.

The tolerance, 3e-6 absolute on logits of size about 0.5: program and
reference are both float32 here and differ in the ORDER of their sums
(the program's online softmax over key blocks and over a ring's rows in
whatever order they lie, its sorted grouped matmul, against the
reference's one softmax a query over a full mask and a loop over
experts): 4e-7 seen.  A window one key longer or shorter, no window, a
kind's rotation on the other kind, cos and sin without YaRN's factor,
router weights not renormalised move logits by 0.01 and more:
``test_a_wrong_reading_fails_the_tolerance`` shows each.
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

from benchmark import reference_mellum2 as reference  # noqa: E402
from ray_tpu.models import common, mellum as ml, mistral4  # noqa: E402
from ray_tpu.serve.llm import LLMConfig, LLMEngine  # noqa: E402
from ray_tpu.serve.llm.engine import FINISHED  # noqa: E402

TOL = 3e-6
BS = 8  # positions a page
CFG = ml.MellumConfig.mellum2_tiny(dtype=jnp.float32)
HELD = ml.MellumConfig.mellum2_12b_a2_5b_12l()
TINY = ml.MellumConfig.mellum2_tiny


def _engine(**kw):
    kw = {"max_batch_size": 4, "num_blocks": 200, "block_size": BS, "seed": 5, **kw}
    return LLMEngine(LLMConfig(model="mellum2_tiny", **kw))


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


FORWARDS = (jax.jit(lambda *a: ml.prefill_chunk(*a), static_argnums=(1, 8)),
            jax.jit(lambda *a: ml.decode_forward_cached(*a), static_argnums=(1, 6)))


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
    assert bm.blocks_held(rid) == -(-len(seq) // bs)  # the sequence's pages, counted once whatever the layers
    bm.free(rid)
    return np.stack([np.asarray(x) for x in logits])


def _reference(eng, seq, n_prompt, numbers=None):
    return reference.full_logits(eng.params, jnp.asarray(seq), numbers or eng.model_cfg,
                                 list(range(n_prompt - 1, len(seq))))[0]


@pytest.fixture(scope="module", params=[8, 24])
def engine(request):
    """An engine at each of the two chunk sizes (buckets of 8; of 8, 16
    and 24): 24 tokens are three query blocks and overwrite a ring of 15
    more than once in one program."""
    chunk = request.param
    mp = pytest.MonkeyPatch()
    mp.setattr(ml.MellumConfig, "mellum2_tiny", staticmethod(lambda **kw: TINY(prefill_chunk=chunk, **kw)))
    eng = _engine()
    mp.undo()
    assert eng._spec.prefill_chunk == chunk
    return eng


# ----------------------------------------------------------------------
# (a) chunks, then decode, against the reference: logits
# ----------------------------------------------------------------------
@pytest.mark.parametrize("n_prompt, n_new", [
    (5, 6),      # a prompt shorter than the window, decode inside it
    (12, 10),    # decode across the wrap: positions 12 .. 21 pass the ring's 15 rows and the window's 16 keys
    (10, 40),    # decode a whole ring and more beyond the wrap
    (75, 4),     # a prompt five windows long: the ring wrapped more than once inside prefill
    (97, 3),     # the last chunk a single token; the ring's rows in every rotation
    (31, 20),    # a prompt that ends one short of two windows, then decode over two more wraps
])
def test_chunked_prefill_then_decode_match_the_reference(engine, n_prompt, n_new):
    seq = _tokens(n_prompt + n_new, seed=n_prompt)
    assert _distance(_replay(engine, seq, n_prompt), _reference(engine, seq, n_prompt)) < TOL
    assert engine.bm.blocks_in_use == 0


def test_a_lane_taken_over_by_a_shorter_successor_reads_none_of_its_rows(engine):
    """The predecessor fills every row of the lane's rings; the
    successor's prompt is shorter than the ring, so most rows it could
    read are the predecessor's, behind the length mask."""
    first, second = _tokens(60, seed=21), _tokens(14, seed=22)
    _replay(engine, first, 50, lane=2)
    left = np.asarray(engine.cache[ml.RING_K][2])
    assert (np.abs(left[:, :CFG.ring_rows]).max(-1) > 0).all()  # every readable row of every window layer
    assert _distance(_replay(engine, second, 9, lane=2), _reference(engine, second, 9)) < TOL


def test_a_decode_step_writes_one_row_a_running_lane_a_window_layer(engine):
    """In place: of a lane that runs, row ``position mod 15`` of each of
    its 6 rings changes and no other; of a lane that does not, only the
    last row, which nothing reads."""
    seq = _tokens(40, seed=5)
    _replay(engine, seq, 33, lane=1)
    before = {name: np.asarray(engine.cache[name]) for name in (ml.RING_K, ml.RING_V)}
    lanes, pages = engine.config.max_batch_size, engine.bm.blocks_needed(engine.max_ctx)
    tok, lengths, write = (np.zeros(lanes, np.int32) for _ in range(3))
    tok[1], lengths[1] = 7, 40
    engine._run_on_cache(engine._decode_jit, tok, lengths, np.zeros((lanes, pages), np.int32), write,
                         np.zeros(lanes, np.float32), engine._next_rng())
    for name, was in before.items():
        changed = (np.asarray(engine.cache[name]) != was).any(-1)  # [lanes, layers, rows]
        assert changed[1, :, 40 % 15].all() and changed[1].sum() == 6
        assert not changed[[0, 2, 3], :, :CFG.ring_rows].any()


def test_the_rings_hold_the_reference_s_keys_of_the_window_s_positions(engine):
    """After 40 positions a lane's ring of each window layer holds the
    keys (normed, rotated) of positions 25 .. 39, position p at row ``p
    mod 15``; the reference computes them from the tokens alone."""
    seq = _tokens(40, seed=9)
    _replay(engine, seq, 33, lane=3)
    keys = np.asarray(reference.full_logits(engine.params, jnp.asarray(seq), engine.model_cfg, [39], keys=True)[2])
    window_layers = [i for i, kind in enumerate(CFG.layer_types) if kind == ml.SLIDING]
    held = np.arange(25, 40)
    ring = np.asarray(engine.cache[ml.RING_K][3])
    assert _distance(ring[:, held % 15], keys[window_layers][:, held]) < 1e-5
    # one position earlier is another token's key
    assert _distance(ring[:, held % 15], keys[window_layers][:, held - 1]) > 1


@pytest.mark.parametrize("wrong", [None, "window_15", "window_17", "no_window", "full_rotation_on_window_layers",
                                   "window_rotation_on_full_layers", "no_attention_factor",
                                   "router_not_renormalised"])
def test_a_wrong_reading_fails_the_tolerance(engine, wrong):
    """What the tolerance is for.  The reference told a window one key
    shorter or longer, none, either kind's rotation for both kinds, cos
    and sin without the 1.2773, or router weights as the softmax left
    them is more than twice 3e-6 from the program (0.01 to 0.6 seen);
    told the truth (None) it is within it."""
    c = reference.numbers(engine.model_cfg)
    rope = c["rope_parameters"]
    edits = {
        None: {}, "window_15": {"sliding_window": 15}, "window_17": {"sliding_window": 17},
        "no_window": {"sliding_window": 1 << 30},
        "full_rotation_on_window_layers": {"rope_parameters": dict(rope, sliding_attention=rope["full_attention"])},
        "window_rotation_on_full_layers": {"rope_parameters": dict(rope, full_attention=rope["sliding_attention"])},
        "no_attention_factor": {"rope_parameters": dict(
            rope, full_attention=dict(rope["full_attention"], attention_factor=1.0))},
        "router_not_renormalised": {"norm_topk_prob": False},
    }
    seq = _tokens(60, seed=3)
    distance = _distance(_replay(engine, seq, 50), _reference(engine, seq, 50, dict(c, **edits[wrong])))
    assert distance < TOL if wrong is None else distance > 2 * TOL


# ----------------------------------------------------------------------
# (b) the parts
# ----------------------------------------------------------------------
@pytest.mark.parametrize("start, T, n_valid", [
    (0, 8, 8), (0, 8, 3), (8, 8, 8), (16, 24, 24), (9, 24, 17), (40, 16, 1), (45, 24, 24),
])
def test_window_chunk_attention_is_the_dense_mask(start, T, n_valid):
    """The chunk's window attention (key blocks of 8, blocks outside a
    query block's window not visited) against one softmax a query over
    the dense window mask, for chunks that start before, at and after
    the ring is full."""
    rng = np.random.default_rng(start * 100 + T)
    G, R, hd, ring = CFG.n_kv_head, CFG.n_head // CFG.n_kv_head, CFG.head_dim, CFG.ring_rows
    q = jnp.asarray(rng.normal(size=(T, G, R, hd)), jnp.float32)
    k_all = rng.normal(size=(start + T, G, hd)).astype(np.float32)
    v_all = rng.normal(size=(start + T, G, hd)).astype(np.float32)

    def context(rows):
        # row c: position start - ring + c; what lies before position 0 is another sequence's
        ctx = rng.normal(size=(ring + T, G, hd)).astype(np.float32) * 50
        for c in range(ring + T):
            if 0 <= start - ring + c:
                ctx[c] = rows[start - ring + c]
        pad = -(ring + T) % CFG.chunk_block
        return jnp.asarray(np.concatenate([ctx, np.zeros((pad, G, hd), np.float32)]))

    got = np.asarray(ml.window_chunk_attention(q, context(k_all), context(v_all), jnp.int32(start),
                                               jnp.int32(n_valid), CFG))
    for t in range(n_valid):
        lo = max(0, start + t - CFG.sliding_window + 1)
        keys, vals = k_all[lo:start + t + 1], v_all[lo:start + t + 1]
        s = np.einsum("grd,kgd->grk", np.asarray(q[t]), keys) / np.sqrt(hd)
        p = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("grk,kgd->grd", p / p.sum(-1, keepdims=True), vals).reshape(-1)
        assert _distance(got[t], want) < 1e-5, t


def test_the_yarn_table_is_mistral_small_4_s_as_it_was_and_mellum_2_s_as_written_out():
    """``common.yarn_inv_freq`` with Mistral-Small-4's numbers gives what
    ``mistral4.yarn_inv_freq`` returned before the table moved to
    ``common`` (PR 45; the 32 values of the parent commit, bit for bit),
    and with Mellum 2's numbers what the reference writes out."""
    before = [
        "0x1.0000000000000p+0", "0x1.7ff2224115d9ap-1", "0x1.1feb33c1c381ep-1", "0x1.afd1354c40d50p-2",
        "0x1.43d136248490fp-2", "0x1.e5a84719edcd2p-3", "0x1.6c310e3769f3fp-3", "0x1.111aedafb9a9dp-3",
        "0x1.999999999999ap-4", "0x1.33281b6744ae1p-4", "0x1.ccab8602d2697p-5", "0x1.59742aa36710dp-5",
        "0x1.030dc4ea03a72p-5", "0x1.66df6ca9aedc3p-6", "0x1.edc2820b20e0ep-7", "0x1.50ead205d48d5p-7",
        "0x1.c7494160e2db0p-8", "0x1.2fe86bda1d4ebp-8", "0x1.8f8aeadffc664p-9", "0x1.016df3774e08ep-9",
        "0x1.42d224a596466p-10", "0x1.8545e57eba466p-11", "0x1.b98326809e611p-12", "0x1.c0bb9b045f888p-13",
        "0x1.60e2dafa7c746p-14", "0x1.892918d61a787p-18", "0x1.26d42cce9b24cp-18", "0x1.ba2e4b0e98677p-19",
        "0x1.4b96be9c2da2cp-19", "0x1.f150280a2ace0p-20", "0x1.74eea61c12623p-20", "0x1.17a8e301c4436p-20",
    ]
    m = mistral4.Mistral4Config.mistral_small_4()
    table = common.yarn_inv_freq(m.rope_theta, m.qk_rope_head_dim, m.rope_factor,
                                 m.original_max_position_embeddings, m.beta_fast, m.beta_slow)
    assert table == mistral4.yarn_inv_freq(m) and len(table) == 32
    assert [x.hex() for x in table] == before
    mine = common.yarn_inv_freq(HELD.rope_theta, HELD.head_dim, HELD.yarn_factor,
                                HELD.original_max_position_embeddings, HELD.beta_fast, HELD.beta_slow)
    theirs, factor = reference.frequencies(reference.numbers(HELD)["rope_parameters"]["full_attention"], 128)
    assert mine == theirs and len(mine) == 64 and factor == 1.2772588722239782
    assert factor == pytest.approx(0.1 * np.log(16) + 1)
    # fast pairs are the plain theta^(-2i/128), slow pairs that over 16, a ramp between
    plain = [500000.0 ** (-2.0 * i / 128) for i in range(64)]
    assert mine[0] == plain[0] and mine[-1] == pytest.approx(plain[-1] / 16)
    assert sum(a == b for a, b in zip(mine, plain)) < 64 > sum(a != b for a, b in zip(mine, plain))
    assert reference.frequencies(reference.numbers(HELD)["rope_parameters"]["sliding_attention"], 128) == (plain, 1.0)


def test_common_rope_takes_a_table_and_a_factor_and_is_what_it_was_without():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(5, 3, 16)), jnp.float32)
    pos = jnp.arange(5) * 7
    plain = [10000.0 ** (-i / 8) for i in range(8)]
    assert _distance(common.rope(x, pos, 10000.0), common.rope(x, pos, None, inv_freq=plain)) < 1e-6
    assert _distance(common.rope(x, pos, 10000.0, factor=1.5), 1.5 * common.rope(x, pos, 10000.0)) < 1e-6
    # the reference's rotation, written out on its own, is the program's for both kinds
    c = reference.numbers(CFG)["rope_parameters"]
    seq = jnp.asarray(np.random.default_rng(1).normal(size=(40, 2, 16)), jnp.float32)
    for kind in (ml.SLIDING, ml.FULL):
        assert _distance(ml._rotate(seq, jnp.arange(40), CFG, kind), reference.rotate(seq, c[kind])) < 1e-5
    assert _distance(ml._rotate(seq, jnp.arange(40), CFG, ml.SLIDING),
                     ml._rotate(seq, jnp.arange(40), CFG, ml.FULL)) > 0.1


def test_the_router_renormalises_the_chosen_of_a_softmax_over_all():
    params = ml.init_params(CFG, jax.random.PRNGKey(3))
    lp = params["layers"][0]
    y = jnp.asarray(np.random.default_rng(1).normal(size=(20, CFG.d_model)), jnp.float32)
    w, top_e = reference.expert_weights(y, lp, reference.numbers(CFG))
    g = np.asarray(jax.nn.softmax(y @ lp["router"], axis=-1))
    assert np.array_equal(np.asarray(top_e), np.argsort(-g, axis=-1, kind="stable")[:, :2])
    chosen = np.take_along_axis(g, np.asarray(top_e), axis=1)
    assert _distance(np.take_along_axis(np.asarray(w), np.asarray(top_e), axis=1),
                     chosen / chosen.sum(-1, keepdims=True)) < 1e-6
    assert _distance(np.asarray(w).sum(-1), 1.0) < 1e-5 and (np.asarray(w) > 0).sum(-1).tolist() == [2] * 20
    out, counts, mine = ml._experts(y, lp, CFG)
    assert np.array_equal(np.asarray(mine), np.asarray(top_e)) and np.asarray(counts)[0] == 20 * 2
    assert _distance(out, reference.expert_part(y, lp, reference.numbers(CFG))[0]) < 1e-5


# ----------------------------------------------------------------------
# (c) the statement, the sizes
# ----------------------------------------------------------------------
def test_the_engine_holds_pages_for_the_full_layers_and_rings_for_the_window_layers():
    eng = LLMEngine(LLMConfig(model="mellum2_tiny", max_batch_size=3, num_blocks=70, block_size=BS))
    assert tuple(eng.cache) == ("k_pages", "v_pages", "win_k", "win_v") == eng._spec.names
    cfg = eng.model_cfg
    row = cfg.n_kv_head * cfg.head_dim
    assert eng.k_pages.shape == eng.v_pages.shape == (2, 70 * BS, row)  # 2 of 8 layers page
    assert eng.cache["win_k"].shape == eng.cache["win_v"].shape == (3, 6, 16, row)  # whatever num_blocks is
    assert eng._spec.reads_cache and eng._spec.prefill_chunk == 8 and eng.bm.state_slots == 3
    with pytest.raises(ValueError, match="not whole pages"):
        ml.cache_spec(cfg, 12)


def test_the_published_sizes_and_the_cut():
    full = ml.MellumConfig.mellum2_12b_a2_5b()
    assert (full.n_layer, full.layer_types.count(ml.SLIDING), full.layer_types.count(ml.FULL)) == (28, 21, 7)
    assert [i for i, kind in enumerate(full.layer_types) if kind == ml.FULL] == [3, 7, 11, 15, 19, 23, 27]
    assert HELD.layer_types == full.layer_types[:12] and HELD.layer_types.count(ml.FULL) == 3
    assert (HELD.d_model, HELD.n_head, HELD.n_kv_head, HELD.head_dim, HELD.vocab_size) == (2304, 32, 4, 128, 98304)
    assert (HELD.num_experts, HELD.num_experts_per_tok, HELD.moe_intermediate_size) == (64, 8, 896)
    assert (HELD.sliding_window, HELD.ring_rows, HELD.max_seq_len) == (1024, 1023, 131072)
    spec = ml.cache_spec(HELD, 64)
    assert (spec.paged_layers, spec.row_width, spec.prefill_chunk) == (3, 512, 2048)
    assert spec.lane_state == (("win_k", (9, 1024, 512), jnp.bfloat16), ("win_v", (9, 1024, 512), jnp.bfloat16))
    # a lane's rings: 9 layers x 1,024 positions x 2,048 B, whatever its sequence's length
    lane = sum(int(np.prod(shape)) * jnp.dtype(dtype).itemsize for _, shape, dtype in spec.lane_state)
    assert lane == 9 * 1024 * 2048 == 18_874_368
    # the sizes of the issue's arithmetic: a layer, the ends, the cut, the whole
    count = lambda tree: sum(int(np.prod(v.shape)) for v in jax.tree_util.tree_leaves(tree))  # noqa: E731
    shapes = jax.eval_shape(lambda: ml.init_params(HELD))
    assert count(shapes["layers"][0]) == count(shapes["layers"][3]) == 417_747_712
    assert count({k: shapes[k] for k in ("embed", "lm_head")}) == 452_984_832
    assert count(shapes) == 12 * 417_747_712 + 452_984_832 + 2304 == 5_465_959_680  # 10.93 GB in bf16
    assert count(jax.eval_shape(lambda: ml.init_params(full))) == 12_149_923_072
    assert "mellum2_12b_a2_5b_12l" in LLMConfig.__doc__


# ----------------------------------------------------------------------
# (d) through the engine
# ----------------------------------------------------------------------
def test_engine_serves_the_reference_s_tokens_and_counts_what_it_did():
    prompt = _tokens(75, seed=6).tolist()

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
    # a sequence reserves ceil((prompt + max_tokens) / block) pages ONCE, not once a window layer
    assert reserved == 2 * -(-(75 + 8) // BS)
    # two prompts of 75 tokens in chunks of 8: 9 whole and a tail of 3 in a bucket of 8
    assert stats["prefill_chunks"] == 20 and stats["prefill_bucket_tokens"] == 2 * 80
    rows = stats["prefill_bucket_tokens"] + 4 * stats["steps"]
    n_l, n_w, n_f = CFG.n_layer, CFG.layer_types.count(ml.SLIDING), CFG.layer_types.count(ml.FULL)
    assert stats["moe_pairs"] == 2 * n_l * rows  # every row a program was given, in every layer
    assert stats["moe_layer_programs"] == n_l * (20 + stats["steps"])
    assert stats["moe_expert_slots"] == 8 * stats["moe_layer_programs"]
    # two lanes decode positions 75 .. 81: a window layer reads the ring's 15 rows, a full layer all
    cached = 2 * sum(range(75, 82))
    assert stats["attn_positions_full"] == n_f * cached
    assert stats["attn_positions_window"] == n_w * 2 * 7 * 15
    assert stats["attn_positions_unwindowed"] == n_l * cached
    assert stats["kv_positions_attended"] == stats["attn_positions_full"] + stats["attn_positions_window"]
    assert stats["kv_positions_attended"] <= stats["kv_positions_gathered"]
    assert stats["kv_positions_gathered"] == n_w * 2 * 7 * 16 + n_f * 2 * sum(-(-n // BS) * BS for n in range(75, 82))
    assert stats["kv_blocks_in_use"] == 0 and stats["state_slots_in_use"] == 0 and stats["state_slots_total"] == 4
    assert stats["state_bytes_held"] == 2 * 4 * n_w * 16 * 32 * 4  # K and V rings of 4 lanes, float32 here


def test_preemption_by_recompute_and_an_early_join_give_the_same_tokens():
    """The hog is evicted mid-answer, prefilled again over prompt +
    answer so far (the lane's rings rebuilt with the pages by the
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

"""Jamba through the serving path, held to the plain float32 reference
(``benchmark/reference_jamba2.py``) at the tiny preset on the CPU: two
periods of four layers with the attention layer at offset 2 (6 Mamba-1
layers of 80 channels x 4 state values, ``dt`` through a rank-4
bottleneck; 2 attention layers of 5 queries on ONE K/V head of 8), pages
of 4, prompt chunks of 8.

The tolerance, 3e-6 absolute on logits of size about 0.3: program and
reference are both float32 here and differ in the ORDER of their sums and
in their layout (the program's state ``[N, d_inner]`` from a lane's
array and its tail, chunk by chunk; its online softmax over key blocks;
against the reference's ``[d_inner, N]`` recurrence over the whole
sequence and one softmax a query): 3e-7 seen.  An inner norm left out,
``b_dt`` left out, ``A = A_log``, no convolution bias, no ``D x``, the
gate before the scan, a rotation, the attention layers one place early,
a state rounded to bfloat16 move logits by 3e-4 and more:
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

from benchmark import reference_jamba2 as reference  # noqa: E402
from benchmark.runners import serve_jamba2 as runner  # noqa: E402
from ray_tpu.models import jamba  # noqa: E402
from ray_tpu.ops import attention, mamba1, pallas_mamba1  # noqa: E402
from ray_tpu.ops import pallas_gqa_paged_attention as gqa_kernel  # noqa: E402
from ray_tpu.serve.llm import LLMConfig, LLMEngine  # noqa: E402
from ray_tpu.serve.llm.engine import FINISHED  # noqa: E402

TOL = 3e-6
BS = 4  # positions a page
CFG = jamba.JambaConfig.jamba2_tiny(dtype=jnp.float32)
FULL = jamba.JambaConfig.jamba2_3b()
N_M, N_A = CFG.layer_types.count(jamba.MAMBA), CFG.layer_types.count(jamba.ATTENTION)


def _engine(**kw):
    kw = {"max_batch_size": 4, "num_blocks": 300, "block_size": BS, "seed": 5, **kw}
    return LLMEngine(LLMConfig(model="jamba2_tiny", **kw))


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


FORWARDS = (jax.jit(lambda *a: jamba.prefill_chunk(*a), static_argnums=(1, 8)),
            jax.jit(lambda *a: jamba.decode_forward_cached(*a), static_argnums=(1, 6)))


def _replay(eng, seqs, n_prompts, lanes_used=(1,)):
    """Sequences through the engine's own cache and lane state by the
    engine's own programs, and the logits of the family's forwards on the
    way: each prompt in chunks into its lane (the last chunk's logits are
    the prompt's), then one decode step a position for all of them at
    once, the other lanes idle beside them.
    -> for each sequence, logits [len(seq) - n_prompt + 1, V] for
    positions n_prompt - 1 .. (the sequences end together)."""
    cfg = eng.model_cfg
    bm, bs, lanes = eng.bm, eng.bm.block_size, eng.config.max_batch_size
    pages = bm.blocks_needed(eng.max_ctx)
    most = eng._spec.prefill_chunk
    rids = [f"replay-{len(seq)}-{lane}" for seq, lane in zip(seqs, lanes_used)]
    logits = [[] for _ in seqs]
    for i, (rid, seq, n_prompt, lane) in enumerate(zip(rids, seqs, n_prompts, lanes_used)):
        bm.allocate(rid, len(seq))
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
        logits[i].append(out[0][0])
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
        eng._run_on_cache(eng._decode_jit, tok, lengths, tables, write, np.zeros(lanes, np.float32),
                          eng._next_rng())
    for rid, seq in zip(rids, seqs):
        assert bm.blocks_held(rid) == -(-len(seq) // bs)  # the sequence's pages, counted once whatever the layers
        bm.free(rid)
    return [np.stack([np.asarray(x) for x in rows]) for rows in logits]


def _replay_one(eng, seq, n_prompt, lane=1):
    return _replay(eng, [seq], [n_prompt], (lane,))[0]


def _reference(eng, seq, n_prompt, wrong=(), params=None, told=None):
    return reference.full_logits(params or eng.params, jnp.asarray(seq), told or reference.numbers(eng.model_cfg),
                                 list(range(n_prompt - 1, len(seq))), wrong=wrong)


@pytest.fixture(scope="module")
def engine():
    return _engine()


# ----------------------------------------------------------------------
# (a) chunks, then decode, against the reference: logits
# ----------------------------------------------------------------------
@pytest.mark.parametrize("n_prompt, n_new", [
    (1, 6),      # a prompt of one token: the convolution sees three zeros
    (3, 9),      # a prompt shorter than the convolution, decode fills its tail
    (8, 5),      # exactly one chunk
    (21, 10),    # three chunks, the last ragged: state and tail cross two chunk boundaries, pads leave them alone
    (33, 3),     # the last chunk a single token
    (10, 70),    # decode for many steps past the prompt
])
def test_chunked_prefill_then_decode_match_the_reference(engine, n_prompt, n_new):
    seq = _tokens(n_prompt + n_new, seed=n_prompt)
    assert _distance(_replay_one(engine, seq, n_prompt), _reference(engine, seq, n_prompt)) < TOL
    assert engine.bm.blocks_in_use == 0


def test_two_lanes_run_beside_two_idle_ones_and_an_idle_lane_s_state_is_left_alone(engine):
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
        assert _distance(mine, _reference(engine, seq, n)) < TOL
    for n in names:
        if n.startswith("ssm_state"):
            assert np.array_equal(np.asarray(engine.cache[n][2]), before[n]), n


def test_a_lane_taken_over_by_a_successor_inherits_neither_state_nor_tail(engine):
    """The predecessor leaves a state and a tail in every Mamba layer of
    the lane; the successor's first chunk (start 0) reads zeros."""
    _replay_one(engine, _tokens(60, seed=21), 50, lane=2)
    assert all(np.abs(np.asarray(engine.cache[n][2])).max() > 0 for n, *_ in engine._spec.lane_state)
    second = _tokens(14, seed=22)
    assert _distance(_replay_one(engine, second, 2, lane=2), _reference(engine, second, 2)) < TOL


WRONG = (*reference.WRONG, "attention_one_layer_early")


@pytest.mark.parametrize("wrong", [None, *WRONG])
def test_a_wrong_reading_fails_the_tolerance(engine, wrong):
    """What the tolerance is for.  The reference told another model
    (``reference_jamba2.WRONG``; or the attention layers at offset 1 and
    not 2, the mixers' weights swapped to match) is more than ten times
    3e-6 from the program (3e-4 to 0.5 seen); told the truth (None) it is
    within it."""
    seq = _tokens(60, seed=3)
    mine = _replay_one(engine, seq, 45)
    if wrong is None:
        assert _distance(mine, _reference(engine, seq, 45)) < TOL
        return
    told, params, flags = runner.wrong_reference(reference.numbers(engine.model_cfg), engine.params, wrong)
    if wrong == "attention_one_layer_early":
        assert reference.layer_kinds(told) == ["mamba", "attention", "mamba", "mamba"] * 2
    assert _distance(mine, _reference(engine, seq, 45, flags, params, told)) > 10 * TOL


def test_a_state_carried_in_bfloat16_by_the_program_fails_the_tolerance():
    """The other side of ``state_bf16``: an engine whose lane state is
    rounded to bfloat16 between programs is far from the float32
    reference."""
    eng = _engine()
    seq = _tokens(50, seed=8)
    good = _replay_one(eng, seq, 20)
    spec = eng._spec

    def rounded(fn):
        def run(*a):
            out = fn(*a)
            cache = [v.astype(jnp.bfloat16).astype(v.dtype) if n.startswith("ssm_state") else v
                     for n, v in zip(spec.names, out[1:])]
            return (out[0], *cache)
        return run

    eng._prefill_jit, eng._decode_jit = rounded(eng._prefill_jit), rounded(eng._decode_jit)
    bad = _replay_one(eng, seq, 20)
    want = _reference(eng, seq, 20)
    assert _distance(good, want) < TOL < 10 * TOL < _distance(bad, want)


# ----------------------------------------------------------------------
# (b) the parts: the plain forms, the kernels in interpret mode
# ----------------------------------------------------------------------
def _scan_inputs(rows, N, D, seed):
    rng = np.random.default_rng(seed)

    def f(*s):
        return jnp.asarray(rng.normal(size=s), jnp.float32)

    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (rows, D))), jnp.float32)
    A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32)[:, None], (N, D))
    return f(rows, D), dt, A, f(rows, N), f(rows, N), f(D)


def test_the_plain_scan_is_the_recurrence_written_out_and_pads_leave_the_state_alone():
    T, N, D = 13, 4, 24
    x, dt, A, B, C, Dv = _scan_inputs(T, N, D, 0)
    state = jnp.asarray(np.random.default_rng(1).normal(size=(N, D)), jnp.float32)
    h, want = np.asarray(state, np.float64), []
    for t in range(9):
        h = np.exp(np.asarray(dt[t])[None] * np.asarray(A)) * h + np.asarray(B[t])[:, None] * np.asarray(dt[t] * x[t])[None]
        want.append((h * np.asarray(C[t])[:, None]).sum(0) + np.asarray(Dv * x[t]))
    y, after = mamba1.selective_scan_chunk(x, dt, A, B, C, Dv, state, jnp.int32(9))
    assert _distance(y[:9], np.stack(want)) < 1e-5 and _distance(after, h) < 1e-5
    # a chunk cut in two gives what the whole gives: the state crosses the cut
    y1, mid = mamba1.selective_scan_chunk(x[:5], dt[:5], A, B[:5], C[:5], Dv, state, jnp.int32(5))
    y2, end = mamba1.selective_scan_chunk(x[5:], dt[5:], A, B[5:], C[5:], Dv, mid, jnp.int32(4))
    assert _distance(jnp.concatenate([y1, y2])[:9], y[:9]) < 1e-6 and _distance(end, after) < 1e-6
    # one position a lane is the chunk's first position
    ys, hs = mamba1.ssm1_step(x[:1], dt[:1], A, B[:1], C[:1], Dv, state[None])
    assert _distance(ys[0], want[0]) < 1e-5
    ys, same = mamba1.ssm1_step(x[:1], dt[:1], A, B[:1], C[:1], Dv, state[None], jnp.asarray([False]))
    assert np.array_equal(np.asarray(same[0]), np.asarray(state))


@pytest.mark.parametrize("lanes, running", [(16, 16), (16, 5), (16, 1), (16, 0), (6, 4)])
def test_decode_kernel_in_interpret_mode_is_the_plain_step(lanes, running):
    """Blocks of eight lanes (of six where eight do not divide them):
    all running; some, so that a block holds running and idle lanes; one;
    none, so that no block is visited."""
    N, D = 16, 256
    assert pallas_mamba1.step_kernel_takes(lanes, N, D) and not pallas_mamba1.step_kernel_takes(lanes, 4, D)
    x, dt, A, B, C, Dv = _scan_inputs(lanes, N, D, lanes + running)
    state = jnp.asarray(np.random.default_rng(2).normal(size=(lanes, N, D)), jnp.float32)
    on = np.zeros(lanes, bool)
    on[np.random.default_rng(3).permutation(lanes)[:running]] = True
    y0, s0 = mamba1.ssm1_step(x, dt, A, B, C, Dv, state, jnp.asarray(on))
    y1, s1 = pallas_mamba1.mamba1_decode_step(x, dt, A, B, C, Dv, state, jnp.asarray(on), interpret=True)
    assert _distance(s0, s1) < 1e-6 and np.array_equal(np.asarray(s1)[~on], np.asarray(state)[~on])
    assert not running or _distance(np.asarray(y0)[on], np.asarray(y1)[on]) < 1e-5


@pytest.mark.parametrize("T, n_valid, D", [(32, 32, 256), (32, 9, 256), (8, 1, 128), (512, 300, 1280)])
def test_chunk_kernel_in_interpret_mode_is_the_plain_scan(T, n_valid, D):
    """Fewer positions than a grid step and two grid steps of 256 (the
    state crosses from one to the next in VMEM); one and several tiles of
    channels; pads behind the real positions."""
    N = 16
    assert pallas_mamba1.chunk_kernel_takes(T, N, D)
    assert not pallas_mamba1.chunk_kernel_takes(T + 4, N, D) and not pallas_mamba1.chunk_kernel_takes(T, N, D + 64)
    x, dt, A, B, C, Dv = _scan_inputs(T, N, D, T + n_valid)
    state = jnp.asarray(np.random.default_rng(4).normal(size=(N, D)), jnp.float32)
    y0, s0 = mamba1.selective_scan_chunk(x, dt, A, B, C, Dv, state, jnp.int32(n_valid))
    y1, s1 = pallas_mamba1.mamba1_chunk_scan(x, dt, A, B, C, Dv, state, jnp.int32(n_valid), interpret=True)
    assert _distance(s0, s1) < 1e-5 and _distance(y0[:n_valid], y1[:n_valid]) < 1e-5


def test_the_kernels_take_the_published_shapes():
    N, D = FULL.mamba_d_state, FULL.d_inner
    assert (N, D) == (16, 5120) and pallas_mamba1.step_kernel_takes(256, N, D)
    assert all(pallas_mamba1.chunk_kernel_takes(T, N, D) for T in (8, 32, 64, 128, 256, 512, 1024, 2048))
    assert not pallas_mamba1.chunk_kernel_takes(2048 + 128, N, D)
    assert not pallas_mamba1.step_kernel_takes(4, CFG.mamba_d_state, CFG.d_inner)  # the tiny preset: the plain forms


@pytest.mark.parametrize("n_rep", [4, 8, 16, 20, 32])
def test_grouped_query_kernel_pads_a_group_s_heads_to_whole_tiles(n_rep):
    """Twenty query heads on one K/V head (Jamba) are a tile and a
    quarter of bf16 rows: padded to 32 with heads of zeros.  4 and 8
    (Granite, Mellum) pad to 16 as they did, 16 (Nemotron) and 32 not at
    all.  Against the gathered reference path."""
    assert gqa_kernel.kernel_takes(n_rep, 128, 64, jnp.bfloat16)
    rng = np.random.default_rng(n_rep)
    B, G, Dh, bs, pages = 3, 1, 128, 16, 5

    def rand(*s):
        return jnp.asarray(rng.normal(size=s), jnp.bfloat16)

    pools = rand(2, 12 * bs, G * Dh), rand(2, 12 * bs, G * Dh)
    tables = jnp.asarray(rng.permutation(np.arange(1, 12))[:B * pages - 4].tolist() + [0] * 4, jnp.int32).reshape(B, pages)
    lengths = jnp.asarray([37, 0, 16], jnp.int32)
    args = (rand(B, G, n_rep, Dh), rand(B, G, Dh), rand(B, G, Dh), *pools, 1, tables, lengths)
    got = gqa_kernel.gqa_paged_decode_attention_kernel(*args, block_size=bs, interpret=True)
    want = attention.gqa_paged_decode_attention(*args, block_size=bs)
    assert got.shape == (B, G, n_rep, Dh) and _distance(got, want) < 0.03


def test_kernel_takes_twenty_queries_on_one_head_and_what_it_took_before():
    assert gqa_kernel.kernel_takes(20, 128, 64, jnp.bfloat16)
    for n_rep in (4, 8, 16):  # Granite, Mellum, Nemotron
        assert gqa_kernel.kernel_takes(n_rep, 128, 64, jnp.bfloat16)
    assert not gqa_kernel.kernel_takes(20, 64, 64, jnp.bfloat16)  # a head half a lane tile
    assert not gqa_kernel.kernel_takes(20, 128, 8, jnp.bfloat16)  # a page half a bf16 sublane tile


# ----------------------------------------------------------------------
# (c) the statement, the sizes
# ----------------------------------------------------------------------
def test_the_engine_holds_pages_for_the_attention_layers_and_two_arrays_a_mamba_layer():
    eng = LLMEngine(LLMConfig(model="jamba2_tiny", max_batch_size=3, num_blocks=70, block_size=BS))
    names = ("k_pages", "v_pages", *(n for i in range(N_M) for n in (f"conv_tail_{i}", f"ssm_state_{i}")))
    assert tuple(eng.cache) == names == eng._spec.names
    cfg = eng.model_cfg
    assert eng.k_pages.shape == eng.v_pages.shape == (N_A, 70 * BS, cfg.head_dim)  # 2 of 8 layers page, ONE K/V head
    assert eng.cache["conv_tail_0"].shape == (3, 3 * cfg.d_inner)
    assert eng.cache["ssm_state_5"].shape == (3, cfg.mamba_d_state, cfg.d_inner)  # N on the sublanes
    assert eng.cache["ssm_state_5"].dtype == jnp.float32
    assert eng._spec.reads_cache and eng._spec.prefill_chunk == 8 and eng.bm.state_slots == 3


def test_the_published_sizes_reckoned_again():
    assert FULL.layer_types.count(jamba.MAMBA) == 26 and FULL.n_layer == 28
    assert [i for i, kind in enumerate(FULL.layer_types) if kind == jamba.ATTENTION] == [7, 21]
    assert (FULL.d_model, FULL.n_head, FULL.n_kv_head, FULL.head_dim, FULL.vocab_size) == (2560, 20, 1, 128, 65536)
    assert (FULL.d_inner, FULL.mamba_d_state, FULL.mamba_d_conv, FULL.mamba_dt_rank) == (5120, 16, 4, 160)
    assert (FULL.intermediate_size, FULL.max_seq_len, FULL.layer_norm_epsilon) == (8192, 262144, 1e-6)
    spec = jamba.cache_spec(FULL, 64)
    assert (spec.paged_layers, spec.row_width, spec.prefill_chunk) == (2, 128, 2048)
    assert spec.lane_state[:2] == (("conv_tail_0", (15360,), jnp.bfloat16), ("ssm_state_0", (16, 5120), jnp.float32))
    lane = sum(int(np.prod(shape)) * jnp.dtype(dtype).itemsize for _, shape, dtype in spec.lane_state)
    assert lane == 26 * (327_680 + 30_720) == 9_318_400
    count = lambda tree: sum(int(np.prod(v.shape)) for v in jax.tree_util.tree_leaves(tree))  # noqa: E731
    shapes = jax.eval_shape(lambda: jamba.init_params(FULL))
    assert count(shapes["layers"][0]) == 104_161_472 and count(shapes["layers"][7]) == 76_682_240
    assert "lm_head" not in shapes and count(shapes["embed"]) == 167_772_160
    assert count(shapes) == 26 * 104_161_472 + 2 * 76_682_240 + 167_774_720 == 3_029_337_472  # 6.06 GB in bf16
    assert "jamba2_3b" in LLMConfig.__doc__ and "9.32 MB a lane" in LLMConfig.__doc__


def test_the_seeded_weights_have_fast_and_slow_channels():
    lp = jamba.init_params(CFG, jax.random.PRNGKey(2))["layers"][0]
    dt = np.asarray(jax.nn.softplus(lp["dt_bias"]))
    assert 1e-3 <= dt.min() < 4e-3 and 3e-2 < dt.max() <= 1e-1 + 1e-6
    A = -np.exp(np.asarray(lp["A_log"]))
    assert A.shape == (CFG.d_inner, CFG.mamba_d_state) and np.allclose(A[7], -np.arange(1, CFG.mamba_d_state + 1))
    assert all(np.asarray(lp[k] == 1).all() for k in ("dt_norm", "b_norm", "c_norm", "D", "norm1", "norm2"))


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
    want = np.asarray(reference.full_logits(eng.params, jnp.asarray(seq), reference.numbers(eng.model_cfg)))
    assert [int(want[i].argmax()) for i in range(len(prompt) - 1, len(seq) - 1)] == first
    # a sequence reserves ceil((prompt + max_tokens) / block) pages ONCE, not once a layer
    assert reserved == 2 * -(-(21 + 8) // BS)
    # two prompts of 21 tokens in chunks of 8: two whole and a tail of 5 in a bucket of 8
    assert stats["prefill_chunks"] == 6 and stats["prefill_bucket_tokens"] == 2 * 24
    assert stats["ssm_chunk_tokens"] == 2 * 21 * N_M
    # two lanes decode positions 21 .. 27
    assert stats["ssm_lane_steps"] == 2 * 7 * N_M
    assert stats["kv_positions_attended"] == N_A * 2 * sum(range(21, 28))
    assert stats["kv_positions_gathered"] == N_A * 2 * sum(-(-n // BS) * BS for n in range(21, 28))
    assert stats["kv_blocks_in_use"] == 0 and stats["state_slots_in_use"] == 0 and stats["state_slots_total"] == 4
    lane = N_M * (CFG.mamba_d_state * CFG.d_inner * 4 + 3 * CFG.d_inner * 4)  # the tail float32 here
    assert stats["state_bytes_held"] == 4 * lane
    report = stats["kv_leak_report"]
    assert report["blocks_in_use"] == 0 and report["total_allocs"] == report["total_frees"]


def test_preemption_by_recompute_and_an_early_join_give_the_same_tokens():
    """The hog is evicted mid-answer, prefilled again over prompt +
    answer so far (the lane's states and tails rebuilt by the chunks),
    and says what it would have said; a request that joins while another
    decodes says what it says alone."""
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

"""Kimi-Linear through the serving path, held to the plain float32
reference (``benchmark/reference_kimi_linear.py``) at the tiny preset on
the CPU: chunks of 64 tokens (one block of the delta rule's chunked
form, four sub-blocks), pages of 8, four KDA layers around one MLA
layer, a dense layer before four expert layers, 16 routed experts of
which 4 a token.

The tolerance on logits, 3e-4 absolute on logits of size about 0.7:
program and reference are both float32 here and differ in the FORM of
the delta rule (the program's triangular system a block of 64 positions
and its carried state, against the reference's recurrence a position at
a time), in the order of the attention's sums (online softmax over key
blocks, absorbed decode over gathered rows) and in the grouped matmul:
1e-6 seen.  The correction left out, one decay a head, a bfloat16 state
or a rotated shared key move logits by 1e-3 and more:
``test_a_wrong_model_fails_the_tolerance`` shows each.

The tolerance on the delta rule alone, 2e-5 relative to the largest
output: ``kda_chunk`` solves a unit triangular system of 64 unknowns in
float32 where ``kda_step`` adds 64 rank-one steps; 3e-6 seen at decays
from ``exp(-60)`` a position (a float32 zero after two) to ``exp(-1e-6)``.
"""

import asyncio
import dataclasses
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

from benchmark import reference_kimi_linear as reference  # noqa: E402
from ray_tpu.models import kimi_linear as kimi  # noqa: E402
from ray_tpu.ops import kda, pallas_kda, pallas_kda_chunk  # noqa: E402
from ray_tpu.ops import pallas_mla_paged_attention as mla_kernel  # noqa: E402
from ray_tpu.serve.llm import LLMConfig, LLMEngine  # noqa: E402
from ray_tpu.serve.llm.engine import FINISHED  # noqa: E402

TOL = 3e-4
KDA_TOL = 2e-5
BS = 8  # positions a page
CFG = kimi.KimiLinearConfig.kimi_linear_tiny(dtype=jnp.float32)
PUBLISHED = kimi.KimiLinearConfig.kimi_linear_48b_a3b()
CUT = kimi.KimiLinearConfig.kimi_linear_48b_a3b_8l_ep8()


def _engine(**kw):
    kw = {"max_batch_size": 4, "num_blocks": 200, "block_size": BS, "seed": 5, **kw}
    return LLMEngine(LLMConfig(model="kimi_linear_tiny", **kw))


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


FORWARDS = (jax.jit(lambda *a: kimi.prefill_chosen(*a), static_argnums=(1, 8)),
            jax.jit(lambda *a: kimi.decode_chosen(*a), static_argnums=(1, 6)))


def _replay(eng, seq, n_prompt, lane=1, most=None):
    """The sequence through the engine's own cache by the engine's own
    programs, and the results of the family's forwards on the way: the
    prompt in chunks of ``most`` (the last chunk's logits are the
    prompt's), then one decode step a position in lane ``lane``.
    -> logits [len(seq) - n_prompt + 1, V] for positions n_prompt - 1 ..."""
    cfg, bm, bs, lanes = eng.model_cfg, eng.bm, eng.bm.block_size, eng.config.max_batch_size
    pages = bm.blocks_needed(eng.max_ctx)
    rid = f"replay-{len(seq)}-{lane}-{most}"
    bm.allocate(rid, len(seq))
    most, logits = most or eng._spec.prefill_chunk, []
    for start in range(0, n_prompt, most):
        m = min(most, n_prompt - start)
        bucket = eng._prefill_bucket(m, most)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :m] = seq[start:start + m]
        bm.advance(rid, m)
        last, table = np.array([m - 1], np.int32), bm.block_table(rid, pages)
        chunk = FORWARDS[0](eng.params, cfg, eng.cache, toks, np.int32(start), last, table, np.int32(lane), bs)
        eng._run_on_cache(eng._prefill_jit, toks, bm.phys_indices(rid, start + m, bucket, start=start), last,
                          np.zeros(1, np.float32), eng._next_rng(), np.int32(start), table, np.int32(lane))
    logits.append(chunk[0][0])
    for pos in range(n_prompt, len(seq)):
        tok, lengths, write = (np.zeros(lanes, np.int32) for _ in range(3))
        tables = np.zeros((lanes, pages), np.int32)
        tok[lane], lengths[lane], tables[lane] = seq[pos], pos, bm.block_table(rid, pages)
        bm.advance(rid, 1)
        write[lane] = bm.phys_index(rid, pos)
        logits.append(FORWARDS[1](eng.params, cfg, eng.cache, tok, tables, lengths, bs)[0][lane])
        eng._run_on_cache(eng._decode_jit, tok, lengths, tables, write, np.zeros(lanes, np.float32),
                          eng._next_rng())
    bm.free(rid)
    return np.stack([np.asarray(x) for x in logits])


@pytest.fixture(scope="module")
def engine():
    return _engine()


# ----------------------------------------------------------------------
# (a) the delta rule: chunk form, step form, the reference's recurrence
# ----------------------------------------------------------------------
def _delta_inputs(T, H, dk, dv, lo, hi, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k = (jax.random.normal(key, (T, H, dk)) for key in ks[:2])
    q, k = (x / jnp.linalg.norm(x, axis=-1, keepdims=True) for x in (q, k))
    v = jax.random.normal(ks[2], (T, H, dv))
    a = jax.random.uniform(ks[3], (T, H, dk), minval=lo, maxval=hi)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    return q, k, v, a, beta, jax.random.normal(ks[5], (H, dk, dv))


def _by_steps(q, k, v, a, beta, state, n_valid):
    outs = []
    for t in range(n_valid):
        o, state = kda.kda_step(*(x[t][None] for x in (q, k, v, a, beta)), state[None])
        state = state[0]
        outs.append(o[0])
    return jnp.stack(outs), state


DECAYS = {"near_one": (-1e-4, -1e-6), "near_zero": (-60.0, -20.0), "both": (-60.0, -1e-6)}


@pytest.mark.parametrize("decays", list(DECAYS))
@pytest.mark.parametrize("T, n_valid", [
    (192, 192),  # three whole blocks
    (192, 150),  # pads: the last block's last 42 rows, across sub-block boundaries
    (192, 64),   # two whole blocks of pads
    (16, 9),     # less than a block: one sub-block
])
def test_chunk_form_is_the_step_form_is_the_reference_s_recurrence(decays, T, n_valid):
    """Exact in float32 down to the softplus's range: no power of alpha
    overflows whatever a block's decays span (``ops/kda.py`` says how),
    pads leave the state alone, and the state before the run counts."""
    q, k, v, a, beta, state = _delta_inputs(T, 2, 32, 16, *DECAYS[decays], seed=T + n_valid)
    want, want_state = _by_steps(q, k, v, a, beta, state, n_valid)
    got, got_state = jax.jit(kda.kda_chunk)(q, k, v, a, beta, state, n_valid)
    scale = float(jnp.abs(want).max())
    assert np.isfinite(np.asarray(got[:n_valid])).all()
    assert _distance(got[:n_valid], want) < KDA_TOL * scale
    # the state's error goes with what it started from (values up to 4) as with what it holds
    assert _distance(got_state, want_state) < KDA_TOL * float(jnp.maximum(jnp.abs(state).max(), jnp.abs(want_state).max()))
    # the reference's recurrence starts from no state: so does this comparison
    zero = jnp.zeros_like(state)
    ref, ref_state = reference.delta_rule(q[:n_valid], k[:n_valid], v[:n_valid], a[:n_valid], beta[:n_valid])
    got, got_state = jax.jit(kda.kda_chunk)(q, k, v, a, beta, zero, n_valid)
    assert _distance(got[:n_valid], ref) < KDA_TOL * scale
    # the state's error goes with the values that went in (v up to 4), not with what decay leaves of them
    assert _distance(got_state, ref_state) < KDA_TOL * float(jnp.abs(v).max())


def test_a_run_in_two_chunks_is_the_run_in_one():
    """Across a chunk boundary: the state after the first chunk's real
    positions is what the second starts from."""
    q, k, v, a, beta, state = _delta_inputs(256, 2, 32, 16, -3.0, -1e-3, seed=3)
    whole, whole_state = kda.kda_chunk(q, k, v, a, beta, state, 256)
    first, mid = kda.kda_chunk(*(x[:128] for x in (q, k, v, a, beta)), state, 128)
    second, end = kda.kda_chunk(*(x[128:] for x in (q, k, v, a, beta)), mid, 128)
    assert _distance(jnp.concatenate([first, second]), whole) < KDA_TOL
    assert _distance(end, whole_state) < KDA_TOL


def test_the_factored_form_would_overflow_where_the_chunk_form_does_not():
    """What the docstring says of powers of alpha: at a decay of
    ``exp(-60)`` a position the factored ``exp(-g)`` is infinite within
    two positions of a block, and the chunk form's every factor is at
    most 1."""
    q, k, v, a, beta, state = _delta_inputs(64, 1, 16, 16, -60.0, -59.0)
    g = jnp.cumsum(a, axis=0)
    assert not np.isfinite(np.asarray(jnp.exp(-g))).all()
    got, _ = kda.kda_chunk(q, k, v, a, beta, state, 64)
    assert np.isfinite(np.asarray(got)).all()


def test_repeated_keys_cost_the_chunk_form_no_digits():
    """A prompt of one token over and over: every key the same, beta 1,
    decays near 1.  The block's system is ``I`` + all ones below the
    diagonal, whose powers pass 1e18 (an inverse by a series of powers
    would lose every digit) and whose inverse is a 1 over a -1: the
    solve is a forward substitution, and the chunk form stays the step
    form."""
    T, H, d = 128, 1, 16
    k = jnp.full((T, H, d), 0.25)  # of length 1
    v = jax.random.normal(jax.random.PRNGKey(0), (T, H, d))
    a, beta, zero = jnp.full((T, H, d), -1e-6), jnp.ones((T, H)), jnp.zeros((H, d, d))
    want, want_state = _by_steps(k, k, v, a, beta, zero, T)
    got, got_state = kda.kda_chunk(k, k, v, a, beta, zero, T)
    assert _distance(got, want) < KDA_TOL and _distance(got_state, want_state) < KDA_TOL


@pytest.mark.parametrize("active", [(True, False, True, True, False), (False,) * 5, (True,) * 5],
                         ids=["some", "none", "all"])
def test_decode_kernel_updates_the_running_lanes_states_in_place(active):
    """``kda_decode_step`` in interpret mode against ``kda_step``: the
    running lanes' states and outputs (the same products summed in
    another order: 1e-6), an idle lane's state untouched to the bit."""
    B, H, dk, dv = 5, 4, 16, 128
    assert pallas_kda.kernel_takes(H, dk, dv) and pallas_kda.kernel_takes(32, 128, 128)
    assert not pallas_kda.kernel_takes(4, 16, 16)  # the tiny preset's: the plain form runs
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    q, k = (jax.random.normal(key, (B, H, dk)) for key in ks[:2])
    v = jax.random.normal(ks[2], (B, H, dv))
    a = jax.random.uniform(ks[3], (B, H, dk), minval=-30.0, maxval=-1e-5)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, H)))
    state = jax.random.normal(ks[5], (B, H, dk, dv))
    runs = jnp.asarray(active)
    want, want_state = kda.kda_step(q, k, v, a, beta, state, runs)
    got, got_state = pallas_kda.kda_decode_step(q, k, v, a, beta, state, runs, interpret=True)
    idle = ~np.asarray(runs)
    assert (np.asarray(got_state)[idle] == np.asarray(state)[idle]).all()
    assert _distance(got_state, want_state) < 1e-5
    assert idle.all() or _distance(np.asarray(got)[~idle], np.asarray(want)[~idle]) < 1e-5


# ----------------------------------------------------------------------
# (a') the chunk kernel (interpret mode) against the chunk form and the step form
# ----------------------------------------------------------------------
_KERNEL = functools.partial(pallas_kda_chunk.kda_chunk_scan, interpret=True)


@pytest.mark.parametrize("decays", list(DECAYS))
@pytest.mark.parametrize("T, n_valid", [
    (192, 192),  # three whole blocks
    (192, 150),  # pads: the last block's last 42 rows, across sub-block boundaries
    (192, 64),   # two whole blocks of pads, which the kernel does not compute
])
def test_chunk_kernel_is_the_chunk_form_is_the_step_form(decays, T, n_valid):
    """``kda_chunk_scan`` at a head of 128 x 128: the same powers (every
    one ``exp`` of a difference that is never positive), the system's
    inverse by substitution and block merges where the form calls XLA's
    solve, the state carried transposed: the form's tolerance holds
    against the recurrence, pads leave the state alone."""
    assert pallas_kda_chunk.kernel_takes(T, 2, 128, 128) and pallas_kda_chunk.kernel_takes(2048, 32, 128, 128)
    q, k, v, a, beta, state = _delta_inputs(T, 2, 128, 128, *DECAYS[decays], seed=T + n_valid)
    want, want_state = _by_steps(q, k, v, a, beta, state, n_valid)
    form, form_state = jax.jit(kda.kda_chunk)(q, k, v, a, beta, state, n_valid)
    got, got_state = _KERNEL(q, k, v, a, beta, state, n_valid)
    scale = float(jnp.abs(want).max())
    state_scale = float(jnp.maximum(jnp.abs(state).max(), jnp.abs(want_state).max()))
    assert np.isfinite(np.asarray(got)).all()
    assert _distance(got[:n_valid], want) < KDA_TOL * scale and _distance(got[:n_valid], form[:n_valid]) < KDA_TOL * scale
    assert _distance(got_state, want_state) < KDA_TOL * state_scale
    assert _distance(got_state, form_state) < KDA_TOL * state_scale


def test_repeated_keys_cost_the_chunk_kernel_no_digits():
    """``test_repeated_keys_cost_the_chunk_form_no_digits``' inputs at a
    head of 128: the kernel's inverse is substitution inside a sub-block
    and block merges between them, no series of powers of a system whose
    powers pass 1e18."""
    T, H, d = 128, 2, 128
    k = jnp.full((T, H, d), d ** -0.5)  # of length 1
    v = jax.random.normal(jax.random.PRNGKey(0), (T, H, d))
    a, beta, zero = jnp.full((T, H, d), -1e-6), jnp.ones((T, H)), jnp.zeros((H, d, d))
    want, want_state = _by_steps(k, k, v, a, beta, zero, T)
    got, got_state = _KERNEL(k, k, v, a, beta, zero, T)
    assert _distance(got, want) < KDA_TOL and _distance(got_state, want_state) < KDA_TOL


def test_the_kernel_s_run_in_two_chunks_is_its_run_in_one():
    """The first chunk's state, written after its last real position, is
    what the second starts from."""
    q, k, v, a, beta, state = _delta_inputs(256, 2, 128, 128, -3.0, -1e-3, seed=3)
    whole, whole_state = _KERNEL(q, k, v, a, beta, state, 256)
    first, mid = _KERNEL(*(x[:128] for x in (q, k, v, a, beta)), state, 100)  # 28 pads behind the first chunk
    assert _distance(mid, _by_steps(q, k, v, a, beta, state, 100)[1]) < KDA_TOL * 4
    first, mid = _KERNEL(*(x[:128] for x in (q, k, v, a, beta)), state, 128)
    second, end = _KERNEL(*(x[128:] for x in (q, k, v, a, beta)), mid, 128)
    assert _distance(jnp.concatenate([first, second]), whole) < KDA_TOL
    assert _distance(end, whole_state) < KDA_TOL


def test_the_state_before_the_run_counts_in_the_kernel():
    q, k, v, a, beta, state = _delta_inputs(64, 2, 128, 128, -0.5, -1e-3, seed=11)
    want, want_state = _by_steps(q, k, v, a, beta, state, 64)
    got, got_state = _KERNEL(q, k, v, a, beta, state, 64)
    from_nothing, _ = _KERNEL(q, k, v, a, beta, jnp.zeros_like(state), 64)
    scale = float(jnp.abs(want).max())
    assert _distance(got, want) < KDA_TOL * scale < 1e3 * KDA_TOL * scale < _distance(from_nothing, want)
    assert _distance(got_state, want_state) < KDA_TOL * float(jnp.abs(state).max())


@pytest.mark.parametrize("T, d", [(16, 128), (64, 16)], ids=["under_a_block", "a_head_of_16"])
def test_the_dispatcher_keeps_the_plain_form_where_the_kernel_s_tiling_does_not_fit(monkeypatch, T, d):
    """On a TPU too a bucket under a block of 64 tokens and a head whose
    columns are not whole lane tiles (the tiny preset's) take
    ``kda_chunk``: the kernel is not traced."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def never(*a, **kw):
        raise AssertionError("the kernel was traced")

    monkeypatch.setattr(pallas_kda_chunk, "kda_chunk_scan", never)
    assert not kda.chunk_kernel_takes(T, 2, d, d) and kda.chunk_kernel_takes(64, 2, 128, 128)
    x = _delta_inputs(T, 2, d, d, -1.0, -1e-3)
    got, got_state = kda.kda_chunk_scan(*x, T - 3)
    want, want_state = kda.kda_chunk(*x, T - 3)
    assert _distance(got, want) == 0.0 and _distance(got_state, want_state) == 0.0
    with pytest.raises(AssertionError, match="was traced"):
        kda.kda_chunk_scan(*_delta_inputs(64, 2, 128, 128, -1.0, -1e-3), 64)


# ----------------------------------------------------------------------
# (b) chunks, then decode, through the engine's cache against the reference
# ----------------------------------------------------------------------
@pytest.mark.parametrize("n_prompt, n_new", [
    (5, 6),      # one short program: less than a block of the delta rule
    (64, 4),     # exactly one chunk, one whole block
    (150, 8),    # three chunks, the last with pads inside a block
    (201, 3),    # four chunks, a tail of 9 in a bucket of 16
])
def test_chunked_prefill_then_decode_match_the_reference(engine, n_prompt, n_new):
    seq = _tokens(n_prompt + n_new, seed=n_prompt)
    got = _replay(engine, seq, n_prompt)
    want, *_ = reference.full_logits(engine.params, jnp.asarray(seq), engine.model_cfg,
                                    list(range(n_prompt - 1, len(seq))))
    assert _distance(got, want) < TOL
    assert engine.bm.blocks_in_use == 0


def test_chunked_prefill_is_one_program_prefill(engine):
    """The prompt as chunks of 64 through the lane's state and the pool,
    and as ONE program that reads neither (a chunk as long as the
    prompt's bucket: four blocks of the delta rule)."""
    seq = _tokens(150, seed=21)
    assert _distance(_replay(engine, seq, 150), _replay(engine, seq, 150, most=256)) < 1e-5


@pytest.mark.parametrize("wrong", reference.WRONG[1:])
def test_a_wrong_model_fails_the_tolerance(engine, wrong):
    """The cell's wrong-on-purpose readings and the NoPE one, each on
    the reference's side: the correction left out, one decay a head, a
    bfloat16 state, ``q_pe`` / ``k_pe`` rotated.  Each moves the logits
    past the tolerance the right model stays within."""
    seq = _tokens(150 + 8, seed=150)
    got = _replay(engine, seq, 150)
    at = list(range(149, len(seq)))
    right, *_ = reference.full_logits(engine.params, jnp.asarray(seq), engine.model_cfg, at)
    broken, *_ = reference.full_logits(engine.params, jnp.asarray(seq), engine.model_cfg, at, wrong=wrong)
    assert _distance(got, right) < TOL < _distance(got, broken)


def test_a_lane_taken_over_reads_zero_state_and_zero_tails(engine):
    """A new sequence in a lane whose last one left a state and tails:
    its first chunk (position 0) reads zeros whatever lies there."""
    seq = _tokens(40, seed=9)
    clean = _replay(engine, seq, 30, lane=2)
    for name, *_ in engine._spec.lane_state:
        engine.cache[name] = engine.cache[name].at[2].set(7.0)  # what a sequence before it might have left
    assert (np.asarray(engine.cache["kda_state_0"][2]) == 7.0).all()
    assert _distance(_replay(engine, seq, 30, lane=2), clean) == 0.0


def test_engine_serves_the_reference_s_tokens_and_counts_by_hand():
    """Through ``LLMEngine`` on the normal path: greedy tokens are the
    reference's argmax position by position, and the counters are what
    the steps did."""
    prompt = [int(t) for t in _tokens(70, seed=4)]

    async def go():
        eng = _engine()
        toks = await _drain(await eng.add_request(prompt, max_tokens=6))
        stats = eng.stats()
        await eng.stop()
        return eng, toks, stats

    eng, toks, stats = asyncio.run(go())
    seq = np.asarray(prompt + toks, np.int32)
    want, *_ = reference.full_logits(eng.params, jnp.asarray(seq), eng.model_cfg, list(range(69, len(seq) - 1)))
    assert toks == [int(t) for t in np.asarray(want).argmax(-1)]
    n_k, n_a = CFG.mixer_types.count(kimi.KDA), CFG.mixer_types.count(kimi.MLA)
    assert stats["kda_chunk_tokens"] == 70 * n_k and stats["prefill_chunks"] == 2
    assert stats["kda_chunk_kernel_tokens"] == 0  # the CPU's path is the plain form
    assert stats["kda_lane_steps"] == 5 * n_k  # five decode steps of one running lane
    assert stats["kv_positions_attended"] == sum(range(70, 75)) * n_a
    assert stats["mla_decode_calls"] == 5 * n_a
    assert stats["moe_layer_programs"] == 7 * 4 and stats["moe_pairs"] == stats["moe_pairs_held"] > 0
    # the state's bytes a step: every lane's arrays read and written
    assert stats["state_bytes_held"] == 4 * n_k * (4 * 16 * 16 * 4 + 3 * 3 * 64 * 4)


# ----------------------------------------------------------------------
# (c) the statement, the share, the published shapes
# ----------------------------------------------------------------------
def test_the_family_states_lane_state_and_a_latent_pool_without_v_together():
    spec = kimi.cache_spec(CUT, 64)
    assert spec.paged_layers == 2 and spec.row_width == 640 and not spec.v_pool and spec.prefill_chunk == 2048
    names = [n for n, *_ in spec.lane_state]
    assert names == [f"kda_{what}_{i}" for i in range(6) for what in ("tail_q", "tail_k", "tail_v", "state")]
    by_name = {n: (shape, dtype) for n, shape, dtype in spec.lane_state}
    assert by_name["kda_state_5"] == ((32, 128, 128), jnp.float32)
    assert by_name["kda_tail_k_0"] == ((3 * 4096,), jnp.bfloat16)
    lane = sum(int(np.prod(shape)) * jnp.dtype(dtype).itemsize for shape, dtype in by_name.values())
    assert lane == 6 * (32 * 128 * 128 * 4 + 3 * 3 * 4096 * 2) == 13_025_280
    assert spec.names[0] == "k_pages" and "v_pages" not in spec.names and spec.reads_cache
    with pytest.raises(ValueError, match="whole blocks"):
        kimi.cache_spec(dataclasses.replace(CUT, prefill_chunk=1000), 64)


def test_the_published_layers_and_the_cut_s():
    assert PUBLISHED.n_layer == 27 and PUBLISHED.mixer_types.count(kimi.MLA) == 7
    assert [n + 1 for n, kind in enumerate(PUBLISHED.mixer_types) if kind == kimi.MLA] == [4, 8, 12, 16, 20, 24, 27]
    assert CUT.mixer_types == tuple("KKKAKKKA") and CUT.experts_held == 32 and CUT.vocab_size == 20480
    assert CUT.latent_row == 640 and CUT.kda_inner == 4096 and kimi.softmax_scale(CUT) == 192 ** -0.5
    shapes = jax.eval_shape(lambda: kimi.init_params(CUT))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert n_params == 2_092_550_080  # 4.19 GB in bf16 (benchmark/configs/kimi-linear-48b-a3b.json)
    assert "wgu_dense" in shapes["layers"][0] and all("router" in lp for lp in shapes["layers"][1:])


def test_the_latent_kernel_takes_the_cell_s_row_thirty_two_lanes_a_call():
    """32 heads over rows of 640 stored columns, 512 of them values:
    half the compute block (2,048 positions), and the operands of 256
    lanes do not fit one call's VMEM: 32 lanes a call, 8 calls a layer.
    Mistral's 48 lanes over 384 columns stay one call at the whole block."""
    bf16 = jnp.bfloat16
    assert mla_kernel.kernel_takes(32, 640, 512, 64, bf16) and mla_kernel.block_positions(640, bf16) == 2048
    assert mla_kernel.lanes_a_call(256, 32, 640, 512, bf16) == 32
    assert mla_kernel.lanes_a_call(48, 32, 384, 256, bf16) == 48
    assert mla_kernel.lanes_a_call(20, 64, 640, 512, bf16) <= 20


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """An expert layer cut into shares of its routed experts: the
    shares' partial sums, the shared expert counted once, are the uncut
    layer's output (float32; the sums' order differs: 1e-6)."""
    cfg = dataclasses.replace(CFG, n_routed_experts=16, experts_held=16)
    params = kimi.init_params(cfg, jax.random.PRNGKey(3))
    lp = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(4), (24, cfg.d_model), jnp.float32)
    whole, _, top_e = kimi._feed_forward(x, lp, cfg, dense=False)
    shares = 8
    per = cfg.n_routed_experts // shares
    total = 0.0
    h = kimi.rmsnorm(x, lp["w_post"], cfg.rms_norm_eps)
    gate, up = jnp.split(h @ lp["wgu_shared"], 2, axis=-1)
    shared = (jax.nn.silu(gate) * up) @ lp["wd_shared"]
    for s in range(shares):
        part_cfg = dataclasses.replace(cfg, experts_first=s * per, experts_held=per)
        part_lp = {**lp, "wgu": lp["wgu"][s * per:(s + 1) * per], "wd": lp["wd"][s * per:(s + 1) * per]}
        y, counts, chose = kimi._feed_forward(x, part_lp, part_cfg, dense=False)
        assert (np.asarray(chose) == np.asarray(top_e)).all()  # every share's router is the whole router
        total = total + (y - shared)  # every share computes the shared expert: counted once, below
    assert _distance(total + shared, whole) < 1e-5


def test_the_router_s_bias_chooses_and_does_not_weigh():
    params = kimi.init_params(CFG, jax.random.PRNGKey(2))
    lp = dict(params["layers"][1])
    h = jax.random.normal(jax.random.PRNGKey(5), (12, CFG.d_model), jnp.float32)
    p0, e0 = kimi.route(h, lp, CFG)
    lp["router_bias"] = lp["router_bias"].at[3].add(10.0)  # expert 3 now always chosen
    p1, e1 = kimi.route(h, lp, CFG)
    assert (np.asarray(e1) == 3).any(-1).all()
    assert np.allclose(np.asarray(p1.sum(-1)), CFG.routed_scaling_factor, atol=1e-5)
    scores = jax.nn.sigmoid(h @ lp["router"])
    assert np.allclose(np.asarray(p1 / p1.sum(-1, keepdims=True)),
                       np.asarray(jnp.take_along_axis(scores, e1, -1) / jnp.take_along_axis(scores, e1, -1).sum(
                           -1, keepdims=True)), atol=1e-6)
    assert not (np.asarray(e0) == np.asarray(e1)).all()


def test_a_share_serves_through_the_engine_and_counts_what_it_held(monkeypatch):
    """A quarter of the experts and half the vocabulary through
    ``LLMEngine``: every routed pair is counted, only the held ones are
    computed, and tokens stay under the rows held."""
    monkeypatch.setattr(kimi.KimiLinearConfig, "kimi_linear_tiny", staticmethod(KimiTinyShare))
    async def go():
        eng = _engine()
        toks = await _drain(await eng.add_request([int(t) % 128 for t in _tokens(20, seed=8)], max_tokens=5))
        stats = eng.stats()
        await eng.stop()
        return eng, toks, stats

    eng, toks, stats = asyncio.run(go())
    assert eng.model_cfg.experts_held == 4 and eng.model_cfg.vocab_size == 128
    assert len(toks) == 5 and max(toks) < 128
    assert 0 < stats["moe_pairs"] == stats["moe_pairs_held"] < stats["moe_pairs_routed"]
    assert stats["moe_expert_slots"] == 4 * stats["moe_layer_programs"]


def KimiTinyShare(**kw):
    base = dataclasses.asdict(CFG)
    return kimi.KimiLinearConfig(**{**base, "experts_first": 4, "experts_held": 4, "vocab_size": 128, **kw})


def test_the_engine_counts_the_tokens_that_went_through_the_chunk_kernel(monkeypatch):
    """Heads of 128 x 128 and the dispatcher told it is on a TPU (the
    kernel in interpret mode): every chunk of a 128-token prompt is whole
    blocks, so ``kda_chunk_kernel_tokens`` is ``kda_chunk_tokens``, and
    the tokens are the plain form's."""
    wide = functools.partial(kimi.KimiLinearConfig.kimi_linear_tiny, kda_num_heads=2, kda_head_dim=128)
    monkeypatch.setattr(kimi.KimiLinearConfig, "kimi_linear_tiny", staticmethod(wide))
    prompt = [int(t) for t in _tokens(128, seed=6)]

    async def go():
        eng = _engine()
        toks = await _drain(await eng.add_request(prompt, max_tokens=4))
        stats = eng.stats()
        await eng.stop()
        return toks, stats

    plain, stats = asyncio.run(go())
    assert stats["kda_chunk_tokens"] > 0 == stats["kda_chunk_kernel_tokens"]
    monkeypatch.setattr(kda, "chunk_kernel_takes", pallas_kda_chunk.kernel_takes)
    monkeypatch.setattr(pallas_kda_chunk, "kda_chunk_scan", _KERNEL)
    toks, stats = asyncio.run(go())
    assert toks == plain
    assert stats["kda_chunk_kernel_tokens"] == stats["kda_chunk_tokens"] == 128 * CFG.mixer_types.count(kimi.KDA)

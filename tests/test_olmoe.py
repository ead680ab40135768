"""OLMoE through the serving path, held to the plain float32 reference
(``benchmark/reference_olmoe.py``) at the tiny preset on the CPU.

The tolerance, 2e-4 absolute on logits of size about 1: program and
reference are both float32 here and differ in the ORDER of their sums
only (the program sums a token's 8 expert rows after a sort, attends
through pages, normalises with rsqrt; the reference sums expert by
expert over every token).  A missing rotation, a missing QK norm or
renormalised expert weights move logits by 1e-2 and more:
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

from benchmark import reference_olmoe  # noqa: E402
from ray_tpu.models import olmoe  # noqa: E402
from ray_tpu.ops import moe  # noqa: E402
from ray_tpu.serve.llm import LLMConfig, LLMEngine  # noqa: E402
from ray_tpu.serve.llm.engine import FINISHED, _write_rows  # noqa: E402

TOL = 2e-4
BS = 8  # positions a page
CFG = olmoe.OlmoeConfig.olmoe_tiny(dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return olmoe.init_params(CFG, rng=jax.random.PRNGKey(7))


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n).astype(np.int32)


def _distance(a, b):
    return float(np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32)).max())


# ----------------------------------------------------------------------
# the model against the reference
# ----------------------------------------------------------------------
@pytest.mark.parametrize("n, bucket", [(8, 8), (13, 16), (32, 32)])
def test_prefill_logits_match_the_reference(params, n, bucket):
    """The last real position's logits of a prompt right-padded to its
    bucket: the pad changes nothing before it."""
    toks = _tokens(n, seed=n)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = toks
    logits, k, v, counters = olmoe.prefill_forward(
        params, CFG, jnp.asarray(padded), last_index=jnp.asarray([n - 1]))
    want = reference_olmoe.full_logits(params, jnp.asarray(toks[None]), CFG)
    assert _distance(logits[0], want[0, n - 1]) < TOL
    assert k.shape == v.shape == (CFG.n_layer, 1, bucket, CFG.n_head, CFG.d_model // CFG.n_head)
    pairs = bucket * CFG.num_experts_per_tok * CFG.n_layer
    assert counters.tolist()[0] == pairs  # pads are routed too: the program's work
    assert counters.tolist()[3:] == [CFG.num_experts * CFG.n_layer, CFG.n_layer]


def _prefill_then_decode(params, cfg, prompt, steps, forwards=olmoe):
    """Logits of every position of prompt + greedy answer through the
    paged cache: the prompt's from prefill (one call a position, the
    last index moved), the answer's from decode steps of one lane among
    three empty ones.  -> (logits [n + steps, V], the tokens)."""
    n = len(prompt)
    pages = cfg.max_seq_len // BS
    pool = (cfg.n_layer, (pages + 1) * BS, cfg.d_model)
    kp, vp = jnp.zeros(pool, cfg.dtype), jnp.zeros(pool, cfg.dtype)
    table = np.arange(1, pages + 1, dtype=np.int32)[::-1].copy()  # pages out of order
    phys = lambda pos: table[pos // BS] * BS + pos % BS  # noqa: E731
    prefill = jax.jit(lambda toks, last: forwards.prefill_forward(params, cfg, toks, last_index=last))
    rows = []
    for last in range(n):
        logits, k, v, _ = prefill(jnp.asarray(prompt[None]), jnp.asarray([last]))
        rows.append(np.asarray(logits[0]))
    where = jnp.asarray(phys(np.arange(n)))
    kp, vp = _write_rows(kp, k[:, 0], where), _write_rows(vp, v[:, 0], where)
    decode = jax.jit(lambda tok, kp, vp, tables, lengths: forwards.decode_forward_paged(
        params, cfg, tok, kp, vp, tables, lengths, BS))
    tables = np.zeros((4, pages), np.int32)
    tables[2] = table
    seq = list(prompt) + [int(rows[-1].argmax())]
    for _ in range(steps):
        cur = len(seq) - 1
        tok = np.zeros(4, np.int32)
        lengths = np.zeros(4, np.int32)
        tok[2], lengths[2] = seq[-1], cur
        logits, k_new, v_new, _ = decode(jnp.asarray(tok), kp, vp, jnp.asarray(tables),
                                         jnp.asarray(lengths))
        write = jnp.asarray([0, 0, phys(cur), 0])
        kp, vp = _write_rows(kp, k_new, write), _write_rows(vp, v_new, write)
        rows.append(np.asarray(logits[2]))
        seq.append(int(rows[-1].argmax()))
    return np.stack(rows), np.asarray(seq[:-1], np.int32)


def test_prefill_then_decode_matches_the_reference_at_every_position(params):
    got, seq = _prefill_then_decode(params, CFG, _tokens(13, seed=1), steps=8)
    want = reference_olmoe.full_logits(params, jnp.asarray(seq[None]), CFG)[0]
    assert got.shape == want.shape == (21, CFG.vocab_size)
    assert _distance(got, want) < TOL


def _own_logits(params, tokens, cfg, rotate=True, qk_norm=True, renormalise=False):
    """The test's own dense OLMoE (numpy, float64), with a switch on each
    part a wrong implementation could leave out."""
    f = lambda a: np.asarray(a, np.float64)  # noqa: E731
    norm = lambda x, w: f(w) * x / np.sqrt((x * x).mean(-1, keepdims=True) + cfg.rms_norm_eps)  # noqa: E731
    T, H = len(tokens), cfg.n_head
    half = cfg.d_model // H // 2
    ang = np.arange(T)[:, None] * cfg.rope_theta ** (-np.arange(half) / half)

    def rot(x):  # [T, H, Dh]
        x1, x2 = x[..., :half], x[..., half:]
        c, s = np.cos(ang)[:, None], np.sin(ang)[:, None]
        return np.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)

    x = f(params["embed"])[tokens]
    for lp in params["layers"]:
        q, k, v = np.split(norm(x, lp["w_in"]) @ f(lp["wqkv"]), 3, -1)
        if qk_norm:
            q, k = norm(q, lp["w_qn"]), norm(k, lp["w_kn"])
        q, k, v = (t.reshape(T, H, -1) for t in (q, k, v))
        if rotate:
            q, k = rot(q), rot(k)
        s = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(2 * half)
        s = np.where(np.tril(np.ones((T, T), bool)), s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        att = np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True), v).reshape(T, -1)
        x = x + att @ f(lp["wo"])
        h2 = norm(x, lp["w_post"])
        z = h2 @ f(lp["router"])
        prob = np.exp(z - z.max(-1, keepdims=True))
        prob /= prob.sum(-1, keepdims=True)
        for t in range(T):
            chosen = np.argsort(-prob[t])[:cfg.num_experts_per_tok]
            w = prob[t, chosen] / (prob[t, chosen].sum() if renormalise else 1.0)
            for e, we in zip(chosen, w):
                g, u = np.split(h2[t] @ f(lp["wgu"][e]), 2)
                x[t] += we * ((g / (1 + np.exp(-g)) * u) @ f(lp["wd"][e]))
    return norm(x, params["norm"]) @ f(params["lm_head"])


def test_a_broken_model_fails_the_tolerance(params):
    """The reference agrees with a third, independent implementation; a
    model without the rotation, without the QK norm or with renormalised
    expert weights is 50 tolerances and more away from it."""
    toks = _tokens(24, seed=2)
    want = np.asarray(reference_olmoe.full_logits(params, jnp.asarray(toks[None]), CFG)[0])
    assert _distance(_own_logits(params, toks, CFG), want) < TOL
    for broken in ({"rotate": False}, {"qk_norm": False}, {"renormalise": True}):
        assert _distance(_own_logits(params, toks, CFG, **broken), want) > 1e-2, broken


def test_expert_weights_are_not_renormalised(params):
    """norm_topk_prob false: a token's 8 (here 2) weights are its softmax
    probabilities as they are and sum to less than 1; with the flag set
    the program renormalises, and the reference follows the flag."""
    x = jax.random.normal(jax.random.PRNGKey(3), (16, CFG.d_model))
    lp = params["layers"][0]
    seen = {}
    real = moe.moe_experts

    def spy(h, top_p, top_e, *a, **kw):
        seen["p"] = np.asarray(top_p)
        return real(h, top_p, top_e, *a, **kw)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(moe, "moe_experts", spy)
        olmoe._experts(x, lp, CFG)
        assert seen["p"].shape == (16, 2) and (seen["p"].sum(-1) < 0.999).all()
        renorm = dataclasses.replace(CFG, norm_topk_prob=True)
        olmoe._experts(x, lp, renorm)
        np.testing.assert_allclose(seen["p"].sum(-1), 1.0, rtol=1e-6)
    toks = _tokens(12, seed=4)
    got, _ = _prefill_then_decode(params, renorm, toks, steps=0)
    assert _distance(got, reference_olmoe.full_logits(params, jnp.asarray(toks[None]), renorm)[0]) < TOL


# ----------------------------------------------------------------------
# routing without drops
# ----------------------------------------------------------------------
E, K, D, F = 8, 2, 32, 16


def _routing(kind, T=24):
    """(h, top_p, top_e, wgu, wd) with distinct weights a token."""
    rng = np.random.default_rng(5)
    h = rng.standard_normal((T, D)).astype(np.float32)
    wgu = (0.2 * rng.standard_normal((E, D, 2 * F))).astype(np.float32)
    wd = (0.2 * rng.standard_normal((E, F, D))).astype(np.float32)
    if kind == "balanced":  # T * K pairs, the same number an expert
        top_e = (np.arange(T * K) % E).reshape(T, K)
    elif kind == "all_to_one":  # every token's first choice is expert 3
        top_e = np.stack([np.full(T, 3), 4 + np.arange(T) % 4], axis=1)
    else:  # "empty": experts 1 and 6 receive nothing
        top_e = np.array([0, 2, 3, 4, 5, 7])[(np.arange(T * K) % 6)].reshape(T, K)
    # no ties: every weight of a token differs
    top_p = (0.05 + 0.4 * rng.random((T, K)) + 0.001 * np.arange(K)).astype(np.float32)
    return h, top_p, top_e.astype(np.int32), wgu, wd


def _loop_over_experts(h, top_p, top_e, wgu, wd):
    y = np.zeros_like(h, dtype=np.float64)
    for t in range(h.shape[0]):
        for p, e in zip(top_p[t], top_e[t]):
            g, u = np.split(h[t].astype(np.float64) @ wgu[e], 2)
            y[t] += p * ((g / (1 + np.exp(-g)) * u) @ wd[e])
    return y


@pytest.mark.parametrize("kind, hit, peak", [("balanced", 8, 6), ("all_to_one", 5, 24), ("empty", 6, 8)])
def test_moe_experts_matches_a_loop_over_experts(kind, hit, peak):
    args = _routing(kind)
    y, counters = jax.jit(moe.moe_experts)(*map(jnp.asarray, args))
    assert _distance(y, _loop_over_experts(*args)) < 1e-5
    # no pair dropped, whatever the imbalance: T * K rows came out of the experts
    assert counters.tolist() == [24 * K, hit, peak]


def test_a_pair_the_grouped_matmul_skips_is_not_counted(monkeypatch):
    """The pairs are counted from what the grouped matmul returns, not
    from the group sizes it was given: one that leaves expert 3's rows
    out (as a capacity would) counts 24 pairs fewer."""
    def skips_expert_3(rows, weights, group_sizes):
        group = jnp.repeat(jnp.arange(E), group_sizes, total_repeat_length=rows.shape[0])
        out = jax.lax.ragged_dot(rows, weights, group_sizes)
        return jnp.where((group == 3)[:, None], 0, out).astype(rows.dtype)

    monkeypatch.setattr(moe, "grouped_matmul", skips_expert_3)
    _, counters = moe.moe_experts(*map(jnp.asarray, _routing("all_to_one")))
    assert counters.tolist() == [24 * K - 24, 5, 24]


def _combine_cases():
    for k in (4, 6, 8, 10):
        for held in (None, (3, 5)):
            for gated in (True, False):
                # one case whose tokens are not whole sublane tiles of 8
                T = 21 if (k, held, gated) == (6, (3, 5), False) else 24
                yield pytest.param(T, k, held, gated, id=f"k{k}-{'share' if held else 'all'}-{'gated' if gated else 'relu2'}-T{T}")


@pytest.mark.parametrize("T, k, held, gated", _combine_cases())
def test_combine_matches_a_loop_over_tokens(monkeypatch, T, k, held, gated):
    """What `moe_experts` makes of the second grouped matmul's rows, held to
    a plain loop: a token's row is the float32 sum, pair by pair, of the
    bf16 row of each HELD pair times its weight, rounded once.  Where a
    share is held the rows behind the groups are NaN, as a kernel that
    never wrote them may leave them: none reaches `y` or the count."""
    of, f = 12, 16
    E = of if held is None else held[1]
    rng = np.random.default_rng(k * 7 + T)
    h = jnp.asarray(rng.standard_normal((T, D)), jnp.bfloat16)
    top_e = np.stack([rng.permutation(of)[:k] for _ in range(T)]).astype(np.int32)
    top_p = (0.05 + 0.4 * rng.random((T, k))).astype(np.float32)
    wgu = jnp.asarray(0.2 * rng.standard_normal((E, D, 2 * f) if gated else (E, f, D)), jnp.bfloat16)
    wd = jnp.asarray(0.2 * rng.standard_normal((E, f, D)), jnp.bfloat16)
    returned = []

    def nan_behind_the_groups(rows, weights, group_sizes, transposed=False):
        if transposed:
            weights = jnp.swapaxes(weights, 1, 2)
        out = jax.lax.ragged_dot(rows, weights, group_sizes).astype(rows.dtype)
        if held is not None:
            out = jnp.where((jnp.arange(rows.shape[0]) < group_sizes.sum())[:, None], out, jnp.nan)
        returned.append(out)
        return out

    monkeypatch.setattr(moe, "grouped_matmul", nan_behind_the_groups)
    y, counters = moe.moe_experts(h, jnp.asarray(top_p), jnp.asarray(top_e), wgu, wd, held=held, gated=gated)

    first, count = held or (0, of)
    is_held = (top_e >= first) & (top_e < first + count)
    key = np.where(is_held, top_e - first, count).reshape(T * k)
    row_of = np.empty(T * k, np.int64)
    row_of[np.argsort(key, kind="stable")] = np.arange(T * k)  # where each pair's row lies, sorted by expert
    rows = np.asarray(returned[-1].astype(jnp.float32))
    assert held is None or np.isnan(rows[is_held.sum():]).all()
    want = np.zeros((T, D), np.float32)
    for t in range(T):
        for j in range(k):
            if is_held[t, j]:
                want[t] += rows[row_of[t * k + j]] * top_p[t, j]
    want = np.asarray(jnp.asarray(want).astype(jnp.bfloat16).astype(jnp.float32))
    got = np.asarray(y.astype(jnp.float32))
    assert y.dtype == jnp.bfloat16 and np.isfinite(got).all()
    # a fused multiply-add may round a sum's last float32 bit the other way: one bf16 step at most
    assert (np.abs(got - want) <= np.abs(want) * 2.0**-7).all() and (got == want).mean() > 0.99
    assert int(counters[0]) == is_held.sum() > 0
    assert int(counters[1]) == len(np.unique(top_e[is_held]))


def test_grouped_matmul_kernel_in_interpret_mode_matches_the_plain_path():
    """The Pallas kernel the chip runs, on the CPU: uneven groups, an
    empty one, a group that straddles two row tiles, rows that do not
    fill the last tile."""
    rng = np.random.default_rng(6)
    sizes = np.array([5, 0, 19, 1, 7, 0, 12, 4], np.int32)  # 48 rows, tiles of 16
    rows = jnp.asarray(rng.standard_normal((sizes.sum(), 128)), jnp.float32)
    w = jnp.asarray(0.1 * rng.standard_normal((E, 128, 256)), jnp.float32)
    want = jax.lax.ragged_dot(rows, w, jnp.asarray(sizes))
    got = moe.moe_gmm(rows, w, jnp.asarray(sizes), interpret=True, tiling=(16, 128, 128))
    assert _distance(got, want) < 1e-4
    got = moe.moe_gmm(rows[:44], w, jnp.asarray(sizes).at[7].set(0), interpret=True,
                      tiling=(16, 128, 128))
    assert _distance(got, want[:44]) < 1e-4
    assert [moe._tile_rows(m) for m in (64, 256, 1024, 4096, 16384)] == [64, 64, 128, 256, 256]


# every (rows, k, n, transposed) the three served presets hand the grouped
# matmul in their cells: a decode step's rows (lanes x experts a token) and
# a prompt chunk's (bucket x experts a token), gate-and-up (or up) then down.
# OLMoE's and Mistral-Small-4's tilings are today's, literally: PR 40 may
# not move their programs.  None: only the properties below are held.
_SERVED_GMM = {
    "olmoe.decode.up": (256, 2048, 2048, False, (64, 2048, 1024)),
    "olmoe.decode.down": (256, 1024, 2048, False, (64, 1024, 1024)),
    "olmoe.chunk256.up": (2048, 2048, 2048, False, (256, 2048, 1024)),
    "olmoe.chunk256.down": (2048, 1024, 2048, False, (256, 1024, 1024)),
    "olmoe.chunk2048.up": (16384, 2048, 2048, False, (256, 2048, 1024)),
    "olmoe.chunk2048.down": (16384, 1024, 2048, False, (256, 1024, 1024)),
    "mistral.decode.up": (192, 4096, 4096, False, (64, 2048, 1024)),
    "mistral.decode.down": (192, 2048, 4096, False, (64, 2048, 1024)),
    "mistral.chunk4096.up": (16384, 4096, 4096, False, (256, 2048, 1024)),
    "mistral.chunk4096.down": (16384, 2048, 4096, False, (256, 2048, 1024)),
    "nemotron.decode.up": (768, 2688, 1856, True, None),
    "nemotron.decode.down": (768, 1856, 2688, False, None),
    "nemotron.chunk1024.up": (6144, 2688, 1856, True, None),
    "nemotron.chunk1024.down": (6144, 1856, 2688, False, None),
    "nemotron.chunk2048.up": (12288, 2688, 1856, True, None),
    "nemotron.chunk2048.down": (12288, 1856, 2688, False, None),
}


@pytest.mark.parametrize("case", list(_SERVED_GMM))
def test_grouped_matmul_tiles_divide_the_contraction(case):
    """No k step of the kernel is a remainder (megablox masks one with a
    float32 round trip over the whole weight tile), a weight tile is at
    most 4 MB of bf16, and its lane extent is whole lane tiles of 128 or
    the whole extent, as a Pallas block must be."""
    m, k, n, _, today = _SERVED_GMM[case]  # either way round, a weight tile is tk x tn
    tm, tk, tn = moe.gmm_tiling(m, k, n)
    if today is not None:
        assert (tm, tk, tn) == today
    assert k % tk == 0
    assert tk * tn * 2 <= 4 * 2**20
    assert tm == moe._tile_rows(m)
    assert (tk % 128 == 0 or tk == k) and (tn % 128 == 0 or tn == n)
    assert -(-n // tn) <= -(-n // 1024)  # no more n tiles than 1,024 made


@pytest.mark.parametrize("k, n, transposed, chosen", [
    (2688, 200, False, (64, 896, 200)),
    (2688, 1100, False, (64, 896, 1100)),  # the up projection: n whole beyond a tile of 1,024
    (2688, 1100, True, (64, 896, 1100)),  # and held transposed, as Nemotron-H's is
    (896, 2688, False, (64, 896, 896)),  # the down projection: n in three tiles alike
])
def test_grouped_matmul_kernel_through_the_chosen_tiling_at_widths_the_tiles_do_not_divide(
        k, n, transposed, chosen):
    """Nemotron-H's arithmetic at a cut size, through the tiling the
    function chooses and no override: k = 3 x 128 x 7 over a ceiling of
    2,048 (three k steps of 896, none masked), n not whole lanes of 128
    or not whole tiles of 1,024, an empty group, and rows behind the
    groups that belong to none (a share's absent pairs)."""
    rng = np.random.default_rng(40)
    sizes = np.array([5, 0, 19, 1, 7], np.int32)
    m = int(sizes.sum()) + 9  # 9 rows of no group
    assert moe.gmm_tiling(m, k, n) == chosen
    rows = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(0.05 * rng.standard_normal((len(sizes), k, n)), jnp.float32)
    want = jax.lax.ragged_dot(rows, w, jnp.asarray(sizes))[: sizes.sum()]
    if transposed:
        w = jnp.swapaxes(w, 1, 2)
    got = moe.moe_gmm(rows, w, jnp.asarray(sizes), interpret=True, transposed=transposed)
    assert got.shape == (m, n)
    assert _distance(got[: sizes.sum()], want) < 1e-3


# ----------------------------------------------------------------------
# through LLMEngine
# ----------------------------------------------------------------------
async def _drain(req):
    toks = []
    while True:
        ev = await req.out.get()
        if ev is FINISHED:
            return toks
        toks.append(ev["token"])


def _engine(model):
    return LLMEngine(LLMConfig(model=model, max_batch_size=4, num_blocks=64, block_size=BS))


def test_engine_serves_olmoe_tiny_and_counts_its_experts():
    """The same prompt twice gives the same tokens, which are the
    reference's greedy tokens; blocks return to zero; the counters rise
    by what the programs were given."""
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]  # bucket 16

    async def main():
        eng = _engine("olmoe_tiny")
        first = await _drain(await eng.add_request(prompt, max_tokens=6))
        before = eng.stats()
        second = await _drain(await eng.add_request(prompt, max_tokens=6))
        after = eng.stats()
        await eng.stop()
        return eng, first, second, before, after

    eng, first, second, before, after = asyncio.run(main())
    assert first == second and len(first) == 6
    cfg = eng.model_cfg
    seq = np.asarray(prompt + first, np.int32)
    want = np.asarray(reference_olmoe.full_logits(eng.params, jnp.asarray(seq[None]), cfg)[0])
    assert [int(want[i].argmax()) for i in range(len(prompt) - 1, len(seq) - 1)] == first
    assert after["kv_blocks_in_use"] == 0
    # one request: a prefill of 16 rows, then 5 decode programs of 4 lanes
    d = {k: after[k] - before[k] for k in olmoe.COUNTERS}
    programs, rows = 1 + 5, 16 + 5 * 4
    assert d["moe_pairs"] == rows * cfg.num_experts_per_tok * cfg.n_layer
    assert d["moe_layer_programs"] == programs * cfg.n_layer
    assert d["moe_expert_slots"] == programs * cfg.n_layer * cfg.num_experts
    assert programs * cfg.n_layer <= d["moe_experts_hit"] <= d["moe_expert_slots"]
    # the largest group: no smaller than the mean, no larger than the rows
    assert d["moe_pairs"] / cfg.num_experts <= d["moe_peak_rows"] <= rows * cfg.n_layer


def test_a_gpt2_preset_has_no_expert_counters_and_the_program_it_had():
    """The seam leaves GPT-2 where it was: no ``moe_*`` in stats(), and
    a decode program that returns the lanes' tokens alone."""

    async def main():
        eng = _engine("tiny")
        toks = await _drain(await eng.add_request([1, 2, 3], max_tokens=3))
        stats = eng.stats()
        await eng.stop()
        return eng, toks, stats

    eng, toks, stats = asyncio.run(main())
    assert len(toks) == 3 and not [k for k in stats if k.startswith("moe")]
    assert type(eng.model_cfg).__name__ == "GPT2Config"
    with pytest.raises(ValueError, match="olmoe_1b_7b_12l"):
        LLMConfig(model="olmoe_huge").model_config()
    with pytest.raises(ValueError, match="unknown model preset"):
        LLMConfig(model="_replace").model_config()

"""Mistral-Small-4 through the serving path, held to the plain float32
reference (``benchmark/reference_mistral_small_4.py``) at the tiny preset
on the CPU: chunks of 64 tokens, pages of 8, ``original_max_position_
embeddings`` 32 (so ``a(t)`` moves inside a prompt of 150 tokens), 32
routed experts of which 4 a token.

The tolerance, 3e-4 absolute on logits of size about 0.1: program and
reference are both float32 here and differ in the ORDER of their sums
(the program's online softmax over key blocks, its absorbed decode and
its sorted grouped matmul, against the reference's one softmax a query
over expanded keys and its loop over experts): 1e-7 to 2e-6 seen.  A
missing query scale, a half-split rotation or an unnormalised router move
logits by 1e-3 and more: ``test_a_broken_model_fails_the_tolerance``
shows each.
"""

import asyncio
import dataclasses
import math
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import reference_mistral_small_4 as reference  # noqa: E402
from ray_tpu.models import mistral4 as m4  # noqa: E402
from ray_tpu.ops import mla, moe  # noqa: E402
from ray_tpu.ops import pallas_mla_chunk_attention as chunk_kernel  # noqa: E402
from ray_tpu.ops.attention import mla_paged_decode_attention  # noqa: E402
from ray_tpu.ops.pallas_mla_paged_attention import mla_paged_decode_attention_kernel  # noqa: E402
from ray_tpu.serve.llm import LLMConfig, LLMEngine  # noqa: E402
from ray_tpu.serve.llm.engine import FINISHED  # noqa: E402

TOL = 3e-4
BS = 8  # positions a page
CFG = m4.Mistral4Config.mistral_small_4_tiny(dtype=jnp.float32)
PUBLISHED = m4.Mistral4Config.mistral_small_4()


def _engine(**kw):
    kw = {"max_batch_size": 4, "num_blocks": 200, "block_size": BS, "seed": 5, **kw}
    return LLMEngine(LLMConfig(model="mistral_small_4_tiny", **kw))


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
    return (jax.jit(lambda *a: m4.prefill_chunk(*a), static_argnums=(1, 8)),
            jax.jit(lambda *a: m4.decode_forward_cached(*a), static_argnums=(1, 6)))


FORWARDS = _forwards()


def _replay(eng, seq, n_prompt, lane=1, cfg=None, forwards=FORWARDS, most=None):
    """The sequence through the engine's own cache by the engine's own
    programs, and the logits of the family's forwards on the way: the
    prompt in chunks of ``most`` (the last chunk's logits are the
    prompt's), then one decode step a position in lane ``lane``.
    -> logits [len(seq) - n_prompt + 1, V] for positions n_prompt - 1 .."""
    cfg = cfg or eng.model_cfg
    bm, bs, lanes = eng.bm, eng.bm.block_size, eng.config.max_batch_size
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


@pytest.fixture(scope="module")
def engine():
    return _engine()


# ----------------------------------------------------------------------
# (a) chunks, then decode, against the reference: logits
# ----------------------------------------------------------------------
@pytest.mark.parametrize("n_prompt, n_new", [
    (5, 6),      # one short program, decode under the original context
    (64, 4),     # exactly one chunk
    (150, 8),    # three chunks; a(t) is 1.14-1.16 here (positions 128-159 of 32)
    (201, 3),    # four chunks, a tail of 9 in a bucket of 16
])
def test_chunked_prefill_then_paged_decode_match_the_reference(engine, n_prompt, n_new):
    seq = _tokens(n_prompt + n_new, seed=n_prompt)
    got = _replay(engine, seq, n_prompt)
    want, _ = reference.full_logits(engine.params, jnp.asarray(seq), engine.model_cfg,
                                    list(range(n_prompt - 1, len(seq))))
    assert _distance(got, want) < TOL
    assert engine.bm.blocks_in_use == 0


def test_chunked_prefill_is_one_program_prefill(engine):
    """The prompt as chunks of 64 through the cache, and as ONE program
    that reads no cache (a chunk as long as the prompt's bucket)."""
    seq = _tokens(150, seed=21)
    chunks = _replay(engine, seq, 150)
    whole = _replay(engine, seq, 150, most=256)
    assert _distance(chunks, whole) < 1e-5


@pytest.mark.parametrize("broken", ["no_query_scale", "half_split_rotation", "router_not_normalised",
                                    "no_softmax_mscale"])
def test_a_broken_model_fails_the_tolerance(monkeypatch, broken):
    engine = _engine()  # its own: a program the engine traces under the patch stays in its cache
    cfg = engine.model_cfg
    if broken == "no_query_scale":
        cfg = dataclasses.replace(cfg, llama_4_scaling_beta=0.0)
    elif broken == "router_not_normalised":
        cfg = dataclasses.replace(cfg, norm_topk_prob=False)
    elif broken == "no_softmax_mscale":
        cfg = dataclasses.replace(cfg, mscale_all_dim=0.0)
    else:
        def half_split(x, pos, cfg):
            half = x.shape[-1] // 2
            ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(m4.yarn_inv_freq(cfg), jnp.float32)
            cos, sin = jnp.cos(ang), jnp.sin(ang)
            a, b = x[..., :half], x[..., half:]
            return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)

        monkeypatch.setattr(m4, "_rope", half_split)
    seq = _tokens(150 + 4, seed=31)
    got = _replay(engine, seq, 150, cfg=cfg, forwards=_forwards())
    want, _ = reference.full_logits(engine.params, jnp.asarray(seq), engine.model_cfg,
                                    list(range(149, len(seq))))
    assert _distance(got, want) > 1.5 * TOL


# ----------------------------------------------------------------------
# (b) the absorbed path against the expanded one; the kernel
# ----------------------------------------------------------------------
def test_absorbed_decode_is_expanded_attention(engine):
    """Position p's logits from a decode step (absorbed, over latent
    pages) and from a prefill chunk that ends at p (expanded keys and
    values of the same cache)."""
    seq = _tokens(100, seed=41)
    decode = _replay(engine, seq, 96)[-1]            # fed seq[99] at position 99
    prefill = _replay(engine, seq, 100)[0]           # the prompt's last position, 99
    assert _distance(decode, prefill) < 1e-5


def test_absorbed_queries_score_what_expanded_keys_score():
    rng = np.random.default_rng(3)
    H, nope, rope, kv, dv = CFG.n_head, CFG.qk_nope_head_dim, CFG.qk_rope_head_dim, CFG.kv_lora_rank, CFG.v_head_dim
    wukv = jnp.asarray(rng.normal(size=(kv, H * (nope + dv))), jnp.float32)
    q_nope, q_rope = (jnp.asarray(rng.normal(size=(3, H, n)), jnp.float32) for n in (nope, rope))
    row = jnp.asarray(rng.normal(size=(CFG.latent_row,)), jnp.float32)
    q = m4.absorbed_queries(q_nope, q_rope, wukv, CFG)
    assert q.shape == (3, H, CFG.latent_row) and not np.asarray(q[..., kv + rope:]).any()
    k_nope = (row[:kv] @ wukv).reshape(H, nope + dv)[:, :nope]
    want = jnp.einsum("bhd,hd->bh", q_nope, k_nope) + jnp.einsum("bhd,d->bh", q_rope, row[kv:kv + rope])
    assert _distance(jnp.einsum("bhw,w->bh", q, row), want) < 1e-4


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mla_paged_kernel_reads_the_lanes_pages(dtype):
    """The kernel in interpret mode against the ``jax.numpy`` path:
    lengths 0, 1, a page less one, several pages (two parts of a compute
    block), physical pages shuffled."""
    rng = np.random.default_rng(0)
    B, H, W, V, bs, per = 4, 8, 256, 128, 16, 40
    n_blocks = 1 + B * per
    pool = jnp.asarray(rng.normal(size=(2, n_blocks * bs, W)), dtype)
    lengths = np.array([0, 1, bs - 1, 600], np.int32)
    tables = rng.permutation(np.arange(1, n_blocks)).reshape(B, per).astype(np.int32)
    for b in range(B):
        tables[b, -(-lengths[b] // bs):] = 0
    q = jnp.asarray(0.2 * rng.normal(size=(B, H, W)), dtype)
    own = jnp.asarray(rng.normal(size=(B, W)), dtype)
    args = (q, own, pool, 1, jnp.asarray(tables), jnp.asarray(lengths))
    want = mla_paged_decode_attention(*args, block_size=bs, v_width=V)
    got = mla_paged_decode_attention_kernel(*args, block_size=bs, v_width=V, interpret=True)
    assert got.shape == (B, H, V)
    assert _distance(got, want) < (2e-5 if dtype == jnp.float32 else 3e-2)
    # a lane with nothing cached attends its own row alone
    assert _distance(got[0], jnp.broadcast_to(own[0, :V], (H, V))) < 1e-6
    if dtype == jnp.float32:  # and the gather is the attention it says: lane 3, by hand
        pos = np.arange(600)
        rows = np.asarray(pool)[1][tables[3][pos // bs] * bs + pos % bs]
        K = np.concatenate([rows, np.asarray(own)[3][None]])
        s = np.asarray(q)[3] @ K.T
        p = np.exp(s - s.max(-1, keepdims=True))
        assert _distance((p / p.sum(-1, keepdims=True)) @ K[:, :V], want[3]) < 1e-5


# the latent widths of a tiny chunk: two heads, a row of 128 columns of which 48 are live
_CHUNK_CFG = types.SimpleNamespace(qk_nope_head_dim=16, qk_rope_head_dim=16, kv_lora_rank=32, v_head_dim=32,
                                   latent_row=128)


def _chunk_inputs(T, start, dtype, cfg=_CHUNK_CFG, H=2, seed=0):
    """(q_nope, q_rope, ctx, wukv): a chunk of T queries at ``start``
    over whole key blocks of rows that hold it."""
    rng = np.random.default_rng(seed + T + start)
    nope, rope, kv, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank, cfg.v_head_dim
    C = -(-(start + T) // mla.K_BLOCK) * mla.K_BLOCK
    q_nope, q_rope = (jnp.asarray(0.5 * rng.normal(size=(T, H, n)), dtype) for n in (nope, rope))
    ctx = jnp.asarray(rng.normal(size=(C, cfg.latent_row)), dtype)
    wukv = jnp.asarray(0.2 * rng.normal(size=(kv, H * (nope + dv))), dtype)
    return q_nope, q_rope, ctx, wukv


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("T,start,n_valid", [
    (64, 0, 64), (64, 512, 40), (64, 700, 64),
    (1024, 0, 1024), (1024, 512, 1000), (1024, 700, 300),
    (1536, 0, 1536), (1536, 512, 1500), (1536, 700, 100),
    # past the kernel's largest tile: a second, ragged one, whole or all pads
    (4608, 700, 4608), (4608, 512, 4000),
])
def test_mla_chunk_kernel_is_the_loop(T, start, n_valid, dtype):
    """The kernel in interpret mode against the XLA loop of
    ``expanded_attention``: a chunk at the context's start, behind whole
    key blocks and behind a part of one; every query real, the last ones
    pads, and all but the first few (chunks of pads, a tile of pads:
    zeros)."""
    cfg = _CHUNK_CFG
    q_nope, q_rope, ctx, wukv = _chunk_inputs(T, start, dtype)
    # the loop takes whole blocks of queries: 1,536 and 4,608 are three and nine of 512
    want = mla.expanded_attention(q_nope, q_rope, ctx, wukv, start, n_valid, cfg, q_block=min(T, 512))
    got = chunk_kernel.mla_chunk_attention_kernel(
        jnp.concatenate([q_nope, q_rope], axis=-1), ctx, wukv, start, n_valid, nope=cfg.qk_nope_head_dim,
        rope=cfg.qk_rope_head_dim, kv=cfg.kv_lora_rank, dv=cfg.v_head_dim, interpret=True)
    assert got.shape == want.shape == (T, 2 * cfg.v_head_dim) and got.dtype == dtype
    assert _distance(got[:n_valid], want[:n_valid]) < (2e-5 if dtype == jnp.float32 else 3e-2)
    # queries past the last real one's 1,024 (the loop's tile) visit nothing
    assert not np.asarray(got[-(-n_valid // 1024) * 1024:], np.float32).any()


def _primitives(jaxpr, into=("jit", "pjit", "closed_call")):
    """The primitives a jaxpr runs, those of the jits it calls among
    them; what a kernel or a loop holds inside is its own."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        if eqn.primitive.name in into:
            names.extend(_primitives(next(v for v in eqn.params.values() if hasattr(v, "jaxpr")).jaxpr))
    return names


def test_expanded_attention_takes_the_kernel_without_a_choice_on_a_tpu(monkeypatch):
    """At Mistral's widths: with a ``keep_of`` the loop and no kernel,
    whatever the backend; without one, on a TPU, the kernel and no
    loop; off the TPU the loop."""
    cfg = types.SimpleNamespace(qk_nope_head_dim=64, qk_rope_head_dim=64, kv_lora_rank=256, v_head_dim=128,
                                latent_row=384)
    q_nope, q_rope, ctx, wukv = _chunk_inputs(128, 512, jnp.bfloat16, cfg=cfg)

    def traced(keep_of):
        return _primitives(jax.make_jaxpr(lambda *a: mla.expanded_attention(*a, 512, 128, cfg, keep_of=keep_of))(
            q_nope, q_rope, ctx, wukv).jaxpr)

    def every(first, n):
        return jnp.ones((n, ctx.shape[0]), bool)

    off_tpu = traced(None)
    assert "while" in off_tpu and "pallas_call" not in off_tpu
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    free, chosen = traced(None), traced(every)
    assert "pallas_call" in free and "while" not in free
    assert "while" in chosen and "pallas_call" not in chosen


# ----------------------------------------------------------------------
# (c) the shares add up
# ----------------------------------------------------------------------
def _moe_experts_before(h, top_p, top_e, wgu, wd):
    """``ops.moe.moe_experts`` as it was before it took a share (and before
    PR 43 changed how a token's k rows are put together)."""
    T, d = h.shape
    k = top_e.shape[1]
    E = wgu.shape[0]
    expert = top_e.reshape(T * k)
    order = jnp.argsort(expert, stable=True)
    group_sizes = jnp.bincount(expert, length=E).astype(jnp.int32)
    rows = h[order // k]
    gate, up = jnp.split(moe.grouped_matmul(rows, wgu, group_sizes), 2, axis=-1)
    out = moe.grouped_matmul(jax.nn.silu(gate) * up, wd, group_sizes)
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
def test_all_experts_held_is_the_function_it_was_bit_for_bit(dtype):
    rng = np.random.default_rng(1)
    T, d, f, E, k = 37, 64, 32, 8, 2
    h = jnp.asarray(rng.normal(size=(T, d)), dtype)
    wgu = jnp.asarray(0.1 * rng.normal(size=(E, d, 2 * f)), dtype)
    wd = jnp.asarray(0.1 * rng.normal(size=(E, f, d)), dtype)
    top_p, top_e = _routing(T, E, k, seed=2)
    want_y, want_c = _moe_experts_before(h, top_p, top_e, wgu, wd)
    for held in (None, (0, E)):
        y, c = moe.moe_experts(h, top_p, top_e, wgu, wd, held=held)
        got, want = np.asarray(y, np.float32), np.asarray(want_y, np.float32)
        # bit for bit where the result is rounded to bfloat16; in float32 to a step of the largest addend: the
        # combine is a jit of its own since PR 43, and a compiled multiply-add rounds once where two operations round twice
        assert np.array_equal(got, want) if dtype == jnp.bfloat16 else np.abs(got - want).max() <= 2.0**-23 * np.abs(want).max()
        assert np.asarray(c).tolist() == np.asarray(want_c).tolist() == [T * k, E, int(c[2])]
    # the values are the earlier function's (k = 2: two additions, in either order); the program is not
    # since PR 43, which gathers the pairs' rows once and never lays them out as [T, k, d]
    before = jax.make_jaxpr(_moe_experts_before)(h, top_p, top_e, wgu, wd)
    after = jax.make_jaxpr(lambda *a: moe.moe_experts(*a))(h, top_p, top_e, wgu, wd)
    assert f"[{T},{k},{d}]" in str(before) and f"[{T},{k},{d}]" not in str(after)


@pytest.mark.parametrize("first, count", [(0, 8), (8, 8), (24, 8), (5, 3)])
def test_a_share_computes_its_own_pairs_and_no_others(first, count):
    rng = np.random.default_rng(4)
    T, d, f, E, k = 29, 64, 32, 32, 4
    h = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    wgu = jnp.asarray(0.1 * rng.normal(size=(E, d, 2 * f)), jnp.float32)
    wd = jnp.asarray(0.1 * rng.normal(size=(E, f, d)), jnp.float32)
    top_p, top_e = _routing(T, E, k, seed=5)
    y, c = moe.moe_experts(h, top_p, top_e, wgu[first:first + count], wd[first:first + count],
                           held=(first, count))
    here = (np.asarray(top_e) >= first) & (np.asarray(top_e) < first + count)
    # the dense way, the held experts alone
    want = np.zeros((T, d), np.float32)
    for t in range(T):
        for p, e in zip(np.asarray(top_p)[t], np.asarray(top_e)[t]):
            if first <= e < first + count:
                gate, up = np.split(np.asarray(h)[t] @ np.asarray(wgu)[e], 2)
                want[t] += p * ((gate / (1 + np.exp(-gate)) * up) @ np.asarray(wd)[e])
    assert _distance(y, want) < 1e-5
    assert not np.asarray(y)[~here.any(-1)].any()  # a token with no held expert gets nothing
    hit = len(set(np.asarray(top_e)[here].tolist()))
    assert np.asarray(c).tolist()[:2] == [int(here.sum()), hit]


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips of 8 of the 32 experts each: their parts, the shared
    expert counted once, are the uncut reference's expert half."""
    params = m4.init_params(CFG, jax.random.PRNGKey(7))
    lp = params["layers"][1]
    x = jnp.asarray(np.random.default_rng(8).normal(size=(50, CFG.d_model)), jnp.float32)
    c = {k: getattr(CFG, k) for k in reference._KEYS}
    want, want_e = reference.expert_half(x, lp, c)
    h = np.asarray(reference.rmsnorm(x, lp["w_post"], CFG.rms_norm_eps))
    gate, up = np.split(h @ np.asarray(lp["wgu_shared"]), 2, axis=-1)
    shared = (gate / (1 + np.exp(-gate)) * up) @ np.asarray(lp["wd_shared"])
    total, held_pairs = shared.copy(), 0
    for first in range(0, 32, 8):
        cfg = dataclasses.replace(CFG, experts_first=first, experts_held=8)
        share = dict(lp, wgu=lp["wgu"][first:first + 8], wd=lp["wd"][first:first + 8])
        y, counts, _ = m4._experts(x, share, cfg)
        total += np.asarray(y) - shared
        routed, held, computed = np.asarray(counts)[:3].tolist()
        assert routed == 50 * 4 and held == computed
        held_pairs += held
        # the reference given the same share says what this chip says
        ref_share, _ = reference.expert_half(x, share, dict(c, experts_first=first))
        assert _distance(y, ref_share) < 1e-5
    assert held_pairs == 50 * 4  # every pair is some chip's
    assert _distance(total, want) < 1e-5
    assert np.asarray(want_e).shape == (50, 4)


# ----------------------------------------------------------------------
# (d) positions: YaRN, m^2, a(t), the pairs
# ----------------------------------------------------------------------
def test_yarn_frequencies_and_scales_by_hand():
    f = m4.yarn_inv_freq(PUBLISHED)
    assert len(f) == 32
    # the ramp runs from pair 12 to pair 25: floor(12.88), ceil(24.92)
    assert f[0] == 1.0                                                # kept
    assert f[12] == pytest.approx(10 ** -1.5, rel=1e-12)              # the last kept: 0.0316228
    assert f[18] == pytest.approx(10 ** -2.25 * (6 / 13 / 128 + 7 / 13), rel=1e-12)  # 3.04827e-3
    assert f[25] == pytest.approx(10 ** -3.125 / 128, rel=1e-12)      # the first wholly interpolated
    assert f[31] == pytest.approx(10 ** -3.875 / 128, rel=1e-12)      # 1.04181e-6
    assert f[18] == pytest.approx(3.04827e-3, rel=1e-5)
    assert np.allclose(np.asarray(reference.yarn_frequencies(
        {k: getattr(PUBLISHED, k) for k in reference._KEYS})), f, rtol=1e-6)
    # m = 0.1 ln 128 + 1 = 1.4852030; the scale 128^-0.5 m^2
    assert m4.softmax_scale(PUBLISHED) == pytest.approx(0.194970, rel=1e-5)
    assert m4.softmax_scale(PUBLISHED) == pytest.approx((0.1 * math.log(128) + 1) ** 2 / math.sqrt(128))
    a = np.asarray(m4.query_scale(jnp.asarray([0, 8191, 8192, 32768]), PUBLISHED))
    assert a[0] == a[1] == 1.0
    assert a[2] == pytest.approx(1 + 0.1 * math.log(2), rel=1e-6)     # 1.0693147
    assert a[3] == pytest.approx(1.1609438, rel=1e-6)                 # 1 + 0.1 ln 5


@pytest.mark.parametrize("pos", [0, 8191, 8192, 32768])
def test_interleaved_pairs_are_the_half_split_rotation_on_permuted_columns(pos):
    rng = np.random.default_rng(pos)
    x = jnp.asarray(rng.normal(size=(3, 64)), jnp.float32)
    got = np.asarray(m4._rope(x, jnp.full((3,), pos), PUBLISHED))
    # evens first, then odds: pair i is columns (i, 32 + i) of the half-split form
    perm = np.concatenate([np.arange(0, 64, 2), np.arange(1, 64, 2)])
    ang = pos * np.asarray(m4.yarn_inv_freq(PUBLISHED))
    xp = np.asarray(x)[:, perm]
    a, b = xp[:, :32], xp[:, 32:]
    half = np.concatenate([a * np.cos(ang) - b * np.sin(ang), b * np.cos(ang) + a * np.sin(ang)], axis=-1)
    # float32 angles: 8192 rad carries 5e-4 of rounding
    assert _distance(got[:, perm], half) < (2e-5 if pos == 0 else 2e-3)
    if pos == 0:
        assert _distance(got, x) == 0.0
    # the reference rotates position by position from 0: its row `pos` is this
    if pos <= 8192:
        c = {k: getattr(PUBLISHED, k) for k in reference._KEYS}
        ref = reference.rotate(jnp.broadcast_to(x[0], (pos + 1, 64)), c)[pos]
        assert _distance(ref, got[0]) < 1e-3  # float32 angles of 8192 rad


# ----------------------------------------------------------------------
# (e) the statement, the engine
# ----------------------------------------------------------------------
@pytest.mark.parametrize("model, names", [
    ("tiny", ("k_pages", "v_pages")),
    ("olmoe_tiny", ("k_pages", "v_pages")),
    ("minicpm_sala_tiny", ("k_pages", "v_pages", "ck_pages", "lightning_state_0", "lightning_state_1")),
    ("mistral_small_4_tiny", ("k_pages",)),
])
def test_the_engine_holds_what_the_family_states_and_no_more(model, names):
    eng = LLMEngine(LLMConfig(model=model, max_batch_size=3, num_blocks=70, block_size=BS))
    assert tuple(eng.cache) == names == eng._spec.names
    assert eng._spec.v_pool == ("v_pages" in names)
    if model == "mistral_small_4_tiny":
        cfg = eng.model_cfg
        assert cfg.latent_row == 128 and eng.k_pages.shape == (cfg.n_layer, 70 * BS, 128)
        assert eng._spec.reads_cache and eng._spec.prefill_chunk == 64 and eng.bm.state_slots == 0
        with pytest.raises(KeyError):
            eng.v_pages


def test_the_published_row_is_320_values_in_three_lane_tiles():
    spec = m4.cache_spec(PUBLISHED, 64)
    assert (spec.paged_layers, spec.row_width, spec.v_pool, spec.prefill_chunk) == (36, 384, False, 4096)
    held = m4.Mistral4Config.mistral_small_4_6l_ep4()
    assert (held.n_layer, held.experts_first, held.experts_held, held.n_routed_experts) == (6, 0, 32, 128)
    assert (held.vocab_size, held.published_vocab_size) == (32768, 131072)
    assert m4.cache_spec(held, 64).paged_layers == 6
    # the sizes of the issue's arithmetic: a layer outside its routed experts, an expert
    shapes = jax.eval_shape(lambda: m4.init_params(dataclasses.replace(held, n_layer=1)))
    layer = {k: int(np.prod(v.shape)) for k, v in shapes["layers"][0].items()}
    assert layer["wgu"] + layer["wd"] == 32 * 25_165_824
    assert sum(layer.values()) - layer["wgu"] - layer["wd"] == 53_748_992  # 53.75M
    assert "mistral_small_4_6l_ep4" in LLMConfig.__doc__


def test_engine_serves_the_reference_s_tokens_and_counts_its_pairs():
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
    L = eng.model_cfg.n_layer
    assert stats["moe_pairs_routed"] == 4 * L * rows
    assert stats["moe_pairs_held"] == stats["moe_pairs"] == stats["moe_pairs_routed"]  # all 32 held here
    assert stats["moe_expert_slots"] == 32 * L * (6 + stats["steps"])
    assert stats["moe_layer_programs"] == L * (6 + stats["steps"])
    assert 0 < stats["kv_positions_attended"] <= stats["kv_positions_gathered"]
    assert stats["kv_positions_gathered"] % (BS * L) == 0
    assert stats["kv_blocks_in_use"] == 0 and stats["state_slots_total"] == 0


def test_a_share_serves_through_the_engine_and_counts_what_it_held(monkeypatch):
    """The tiny preset holding experts 8-15 of 32: the engine's tokens
    are the reference's given the same share, and about a quarter of the
    pairs are held, every one of them computed."""
    monkeypatch.setattr(m4.Mistral4Config, "mistral_small_4_tiny", staticmethod(
        lambda **kw: Mistral4TinyShare(**kw)))
    prompt = _tokens(100, seed=9).tolist()

    async def main():
        eng = _engine()
        toks = await _drain(await eng.add_request(prompt, max_tokens=6))
        stats = eng.stats()
        await eng.stop()
        return eng, toks, stats

    eng, toks, stats = asyncio.run(main())
    assert eng.params["layers"][0]["wgu"].shape[0] == 8
    seq = np.asarray(prompt + toks, np.int32)
    want = np.asarray(reference.full_logits(eng.params, jnp.asarray(seq), eng.model_cfg)[0])
    assert [int(want[i].argmax()) for i in range(len(prompt) - 1, len(seq) - 1)] == toks
    assert 0 < stats["moe_pairs_held"] == stats["moe_pairs"] < stats["moe_pairs_routed"] // 2
    assert stats["moe_expert_slots"] == 8 * 2 * stats["moe_layer_programs"] // 2


def Mistral4TinyShare(**kw):
    base = m4.Mistral4Config(**{f.name: getattr(CFG, f.name) for f in dataclasses.fields(CFG)})
    return dataclasses.replace(base, experts_first=8, experts_held=8, **kw)


def test_preemption_by_recompute_and_an_early_join_give_the_same_tokens():
    """The hog is evicted mid-answer, prefilled again over prompt +
    answer so far (two chunks), and says what it would have said; a
    request that joins while another decodes says what it says alone."""
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

"""The plain reference of the Mistral-Small-4 family: forward pass in
float32 ``jax.numpy`` at ``highest`` matmul precision, attention in its
NON-absorbed form (keys and values expanded for every head from the
latent, a causal mask, one softmax over every earlier position), the
experts the dense way (every held expert's output for every token times
the token's weight for it, zero where the expert is not among its 4).
No kernel, no cache, no chunks, no pages, no sort, no grouped matmul,
and nothing imported from the program.  It reads the program's
parameter tree (``embed``, ``layers`` of ``w_in, wdq, w_qn, wuq, wdkv,
w_kvn, wukv, wo, w_post, router, wgu_shared, wd_shared, wgu, wd``,
``norm``, ``lm_head``): that tree is the interface.

The model (mistralai/Mistral-Small-4-119B-2603 ``config.json``,
``model_type: mistral4``; keys in brackets), a layer on the residual
stream x of one sequence, ``rmsnorm(x, w) = w x rsqrt(mean(x^2) +
1e-6)``:

    h  = rmsnorm(x, w_in)
    cq = rmsnorm(h Wdq, w_qn)                        [q_lora_rank 1024]
    q  = cq Wuq: 32 heads of [q_nope 64 | q_rope 64] [qk_nope_head_dim, qk_rope_head_dim]
    [c | kr] = h Wdkv;  c = rmsnorm(c, w_kvn)        [kv_lora_rank 256 | 64]
    q_rope, kr rotated at the position over pairs (2i, 2i+1) [rope_interleave],
        frequencies YaRN (below)                     [rope_parameters]
    [k_nope 64 | v 128] of head i = c Wukv[i]        [v_head_dim]
    score(t, s<=t) = a(t) (q_nope.k_nope + q_rope.kr) 128^-0.5 m^2
    x  = x + (softmax(score) v, heads side by side) Wo
    h2 = rmsnorm(x, w_post)
    p  = softmax(h2 Wr) over all 128                 [n_routed_experts]
    the 4 largest p, divided by their sum, times 1   [num_experts_per_tok, norm_topk_prob, routed_scaling_factor]
    x  = x + SwiGLU_shared(h2) + sum over those of the 4 that are HELD of p_e SwiGLU_e(h2)
         SwiGLU(h) = (silu(h Wg) * (h Wu)) Wd, width 2048 [moe_intermediate_size, n_shared_experts 1]

then rmsnorm and the untied head over the rows of the vocabulary held.
YaRN: pair i of 32 turns at ``f_i = 10000^(-2i/64)``; with ``low =
floor(64 ln(8192 / (32 * 2 pi)) / (2 ln 10000)) = 12`` and ``high =
ceil(64 ln(8192 / (1 * 2 pi)) / (2 ln 10000)) = 25``, ``r_i = clip((i -
low) / (high - low), 0, 1)`` and the frequency used is ``f_i / 128 * r_i
+ f_i (1 - r_i)``; cos and sin times ``mscale / mscale_all_dim`` = 1.
``m = 0.1 * mscale_all_dim * ln(128) + 1``; ``a(t) = 1 + 0.1 ln(1 +
floor(t / 8192))``.

The SHARE (``first``, ``count`` of the routed experts; the rows of the
vocabulary the tree holds): the router scores all 128 experts and keeps
4 a token; of those, the experts ``first .. first + count - 1`` alone
are in the tree and add their part; the others add nothing, here as in
the program (the partial sum that one chip of an expert-parallel
deployment computes).

ASSUMED, because the catalog's row of the source does not carry it
(``benchmark/configs/mistral-small-4.json`` lists the same):

- the router scores by softmax (no ``scoring_func`` key; the family's
  earlier sparse models route by softmax, DeepSeek-V3 by a sigmoid with
  a bias the config does not carry either);
- the query scale ``a(t)`` above, from ``llama_4_scaling_beta`` 0.1 and
  ``original_max_position_embeddings`` 8192, applied to the whole query;
- the order of YaRN's ramp (fast pairs kept, slow pairs interpolated,
  as DeepSeek-V3's published code computes it);
- bf16 parameters; weights seeded random (normal 0.02, norm weights 1);
- ``n_group`` and ``topk_group`` 1 mean no group limit; the vision
  tower is left out.

The weights stay in the program's dtype; one layer's attention weights,
and within a layer one expert at a time, are cast to float32; the
projections and the experts go a block of ``ROWS`` positions at a time
and attention ``QUERIES`` queries at a time, so that the reference of a
9k-token sequence fits beside the engine's weights and cache on the
chip.  Only a process that holds the chip (or a CPU rehearsal) imports
this.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROWS = 1024  # positions a block of the projections and the experts
QUERIES = 128  # queries a block of attention: their scores over every position are [32, QUERIES, T]


def rmsnorm(x, w, eps):
    return w.astype(F32) * x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)


def yarn_frequencies(c: dict) -> jnp.ndarray:
    d, base, orig = c["qk_rope_head_dim"], c["rope_theta"], c["original_max_position_embeddings"]
    low = max(math.floor(d * math.log(orig / (c["beta_fast"] * 2 * math.pi)) / (2 * math.log(base))), 0)
    high = min(math.ceil(d * math.log(orig / (c["beta_slow"] * 2 * math.pi)) / (2 * math.log(base))), d - 1)
    high = high + 0.001 if low == high else high
    i = jnp.arange(d // 2, dtype=F32)
    f = base ** (-2.0 * i / d)
    r = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return f / c["rope_factor"] * r + f * (1.0 - r)


def yarn_m(c, mscale):
    return 1.0 if c["rope_factor"] <= 1 else 0.1 * mscale * math.log(c["rope_factor"]) + 1.0


def rotate(x, c):
    """x [T, ..., D] at positions 0..T-1 over pairs (2i, 2i + 1)."""
    T = x.shape[0]
    ang = jnp.arange(T, dtype=F32)[:, None] * yarn_frequencies(c)[None, :]
    ang = ang.reshape(T, *([1] * (x.ndim - 2)), -1)
    ratio = yarn_m(c, c["mscale"]) / yarn_m(c, c["mscale_all_dim"])
    cos, sin = jnp.cos(ang) * ratio, jnp.sin(ang) * ratio
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def by_rows(f, x):
    """f over x [T, ...] a block of ROWS positions at a time."""
    T = x.shape[0]
    pad = -T % ROWS
    xp = jnp.concatenate([x, jnp.zeros((pad, *x.shape[1:]), x.dtype)]) if pad else x
    out = jax.lax.map(f, xp.reshape(-1, ROWS, *x.shape[1:]))
    return jax.tree.map(lambda o: o.reshape(-1, *o.shape[2:])[:T], out)


def attention(q, k, v, c):
    """q, k [T, H, 128], v [T, H, 128] -> [T, H * 128]: every query over
    every earlier position, QUERIES queries at a time."""
    T, H, _ = q.shape
    scale = (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5 * yarn_m(c, c["mscale_all_dim"]) ** 2
    pos = jnp.arange(T)

    def rows(xs):
        qb, tb = xs
        a = 1.0 + c["llama_4_scaling_beta"] * jnp.log1p(
            (tb // c["original_max_position_embeddings"]).astype(F32))
        s = jnp.einsum("thd,khd->htk", qb, k) * scale * a[None, :, None]
        s = jnp.where(pos[None, None, :] <= tb[None, :, None], s, -jnp.inf)
        return jnp.einsum("htk,khd->thd", jax.nn.softmax(s, axis=-1), v)

    pad = -T % QUERIES
    qp = jnp.concatenate([q, jnp.zeros((pad, *q.shape[1:]), F32)])
    o = jax.lax.map(rows, (qp.reshape(-1, QUERIES, *q.shape[1:]), jnp.arange(T + pad).reshape(-1, QUERIES)))
    return o.reshape(T + pad, -1)[:T]


def expert_weights(h2, router, c):
    """[N, E] float32 over ALL the router's experts: a token's weight
    for each of its top experts, zero for the others; and the experts
    chosen [N, k] (lowest number first among equals, as ``top_k``)."""
    p = jax.nn.softmax(h2 @ router.astype(F32), axis=-1)
    top_p, top_e = jax.lax.top_k(p, c["num_experts_per_tok"])
    if c["norm_topk_prob"]:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    top_p = top_p * c["routed_scaling_factor"]
    w = jnp.zeros_like(p).at[jnp.arange(p.shape[0])[:, None], top_e].set(top_p)
    return w, top_e


def expert_half(x, lp, c):
    """What the experts add to x [N, d]: the shared expert's output and
    the held routed experts' weighted ones; and the experts chosen."""
    first, count = c["experts_first"], lp["wgu"].shape[0]
    h2 = rmsnorm(x, lp["w_post"], c["rms_norm_eps"])
    w, top_e = expert_weights(h2, lp["router"], c)
    gate, up = jnp.split(h2 @ lp["wgu_shared"].astype(F32), 2, axis=-1)
    y = (jax.nn.silu(gate) * up) @ lp["wd_shared"].astype(F32)

    def one_expert(e, y):
        gate, up = jnp.split(h2 @ lp["wgu"][e].astype(F32), 2, axis=-1)
        return y + w[:, first + e, None] * ((jax.nn.silu(gate) * up) @ lp["wd"][e].astype(F32))

    return jax.lax.fori_loop(0, count, one_expert, y), top_e


@functools.partial(jax.jit, static_argnames=("cfg",))
def layer(x, lp, *, cfg):
    """One block on x [T, d] float32 -> (x, the experts each token chose
    [T, k]).  `cfg`: a tuple of (name, value) pairs."""
    c = dict(cfg)
    eps, T, H = c["rms_norm_eps"], x.shape[0], c["n_head"]
    nope, rope, kv, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["kv_lora_rank"], c["v_head_dim"]
    wdq, wuq, wdkv = lp["wdq"].astype(F32), lp["wuq"].astype(F32), lp["wdkv"].astype(F32)
    wukv = lp["wukv"].astype(F32)

    def project(xb):
        h = rmsnorm(xb, lp["w_in"], eps)
        q = rmsnorm(h @ wdq, lp["w_qn"], eps) @ wuq
        ckr = h @ wdkv
        c_lat = rmsnorm(ckr[:, :kv], lp["w_kvn"], eps)
        return q, c_lat @ wukv, ckr[:, kv:]

    q, knv, k_r = by_rows(project, x)
    q, knv = q.reshape(T, H, nope + rope), knv.reshape(T, H, nope + dv)
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], c)], axis=-1)
    k_r = jnp.broadcast_to(rotate(k_r, c)[:, None, :], (T, H, rope))
    k = jnp.concatenate([knv[..., :nope], k_r], axis=-1)
    att = attention(q, k, knv[..., nope:], c)
    wo = lp["wo"].astype(F32)
    x = x + by_rows(lambda ob: ob @ wo, att)

    y, top_e = by_rows(lambda xb: expert_half(xb, lp, c), x)
    return x + y, top_e


_KEYS = ("rms_norm_eps", "n_head", "qk_nope_head_dim", "qk_rope_head_dim", "kv_lora_rank", "v_head_dim",
         "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor", "experts_first",
         "rope_theta", "rope_factor", "original_max_position_embeddings", "beta_fast", "beta_slow",
         "mscale", "mscale_all_dim", "llama_4_scaling_beta")


def full_logits(params, tokens, cfg, positions=None):
    """tokens [T] of ONE sequence -> (logits [len(positions), rows held]
    float32 at `positions` (all of them when None), the experts every
    token chose in every layer [L, T, k]).  `cfg` gives the attributes
    named in _KEYS; the experts held are ``cfg.experts_first`` on, as
    many as the tree holds."""
    sizes = tuple((k, getattr(cfg, k)) for k in _KEYS)
    # on the TPU a float32 matmul runs as bf16 passes unless told otherwise
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        chose = []
        for lp in params["layers"]:
            x, top_e = layer(x, lp, cfg=sizes)
            chose.append(top_e)
        if positions is not None:
            x = x[jnp.asarray(positions)]
        return _head(x, params["norm"], params["lm_head"], eps=float(cfg.rms_norm_eps)), jnp.stack(chose)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, lm_head, *, eps):
    # under jit the head's cast to float32 fuses into the matmul
    return rmsnorm(x, norm, eps) @ lm_head.astype(F32)

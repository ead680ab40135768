"""The plain reference of the Mellum 2 family: forward pass in float32
``jax.numpy`` at ``highest`` matmul precision, attention as one softmax a
query over a full ``[T, T]`` mask by the layer's kind (every earlier
position, or the ``sliding_window`` last ones) with each K/V head
repeated for its query heads, both rotations written out from the
numbers of ``rope_parameters``, the experts the dense way (every
expert's output for every token times the token's weight for it, zero
where the expert is not among its 8).  No kernel, no cache, no ring, no
chunks, no pages, no batching, no sort, no grouped matmul, and nothing
imported from the program.  It reads the program's parameter tree
(``embed``, ``norm``, ``lm_head``, ``layers`` of ``norm1, wqkv, w_qn,
w_kn, wo, norm2, router, wgu`` (an expert's ``[gate | up]`` side by
side, ``[d, 2f]``), ``wd``): that tree is the interface.

The model (JetBrains/Mellum2-12B-A2.5B-Instruct ``config.json``,
``model_type: mellum``; keys in brackets).  ``rmsnorm(x, w) = w x
rsqrt(mean(x^2) + 1e-6)`` [rms_norm_eps].

    x = E[tok]
    for each layer, its attention by [layer_types]:
        y = rmsnorm(x, w1);  x = x + Attention_kind(y) W_o
        y = rmsnorm(x, w2);  x = x + Experts(y)
    logits = rmsnorm(x, w_f) W_head                      [tie_word_embeddings false]

    attention  q 32 heads of 128, k and v 4 heads of 128 = y W_qkv   [num_attention_heads, num_key_value_heads,
                                                          head_dim]; query head i reads K/V head i // 8
               q, k = rmsnorm over each head's 128 columns (one weight of 128 for all heads)   [ASSUMED]
               rotation, half-split (rotate_half): pair i of a head turns by t f_i, and
                 x1' = c (x1 cos - x2 sin), x2' = c (x2 cos + x1 sin), x1 | x2 the head's halves
                 sliding_attention  f_i = 500000^(-2i/128), c = 1          [rope_parameters.sliding_attention]
                 full_attention     YaRN: f_i blended between 500000^(-2i/128) and that over 16 [factor] by
                                    the linear ramp between the pairs that turn 32 [beta_fast] and 1
                                    [beta_slow] times in 8,192 positions [original_max_position_embeddings];
                                    c = 1.2772588722239782 [attention_factor]: a score carries c^2
               score(t, j) = q_t . k_j / sqrt(128); causal (j <= t); in a sliding_attention layer
                 also t - j < 1024 [sliding_window]: 1,024 keys, the query's own among them
               out = softmax(score) v
    Experts    g = softmax(y W_r) over all 64 in float32   [num_experts]
               the 8 of largest g [num_experts_per_tok], their weights divided by their sum [norm_topk_prob]
               sum over those 8 of p_e W_down,e (silu(y W_gate,e) * (y W_up,e))
                                                          [hidden_act silu, moe_intermediate_size 896]

DEPARTURES from the published description, and what is ASSUMED because
the catalog's row of the source does not settle it
(``benchmark/configs/mellum2-12b-a2.5b.json`` lists the same):

- the QK norm per head: the row's ``config`` has no key for it; every
  key name of the attention and expert part is the Qwen3-MoE
  convention's, whose published code norms q and k per head with no key;
- NO multi-token-prediction head: the row's ``described_as.other`` says
  "MTP head", its ``config`` has no key that sizes one, so none is built;
- the router's softmax is taken before the top-k (over all 64);
- the window's edge is ``t - j < sliding_window`` (the ``transformers``
  sliding mask);
- ``intermediate_size`` 7168, ``max_window_layers`` and
  ``use_sliding_window`` are read by nothing (every layer is sparse and
  ``layer_types`` decides);
- weights seeded random (normal 0.02 in bf16, norm weights 1).

``numbers`` takes what the equations need off a config as plain
numbers; a wrong-on-purpose reading edits that dict (another window,
none, the kinds' rotations swapped) and must fail the comparison.

The weights stay in the program's dtype; one layer's are cast to
float32 at a time, and within the experts one expert at a time; the
projections and the experts go a block of ``ROWS`` positions at a time
and attention ``QUERIES`` queries at a time, so that the reference of a
5k-token sequence fits beside the engine's weights and cache on the
chip.  Only a process that holds the chip (or a CPU rehearsal) imports
this.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROWS = 1024  # positions a block of the projections and the experts
QUERIES = 64  # queries a block of attention: their scores over every position are [32, QUERIES, T]


def numbers(cfg) -> dict:
    """What the equations read, off a config with the program's
    attribute names, as plain numbers under the source's key names."""
    theta = float(cfg.rope_theta)
    return {
        "layer_types": list(cfg.layer_types), "rms_norm_eps": float(cfg.layer_norm_epsilon),
        "num_attention_heads": cfg.n_head, "num_key_value_heads": cfg.n_kv_head, "head_dim": cfg.head_dim,
        "sliding_window": cfg.sliding_window, "num_experts_per_tok": cfg.num_experts_per_tok,
        "norm_topk_prob": bool(cfg.norm_topk_prob),
        "rope_parameters": {
            "sliding_attention": {"rope_type": "default", "rope_theta": theta},
            "full_attention": {
                "rope_type": "yarn", "rope_theta": theta, "factor": float(cfg.yarn_factor),
                "original_max_position_embeddings": cfg.original_max_position_embeddings,
                "beta_fast": float(cfg.beta_fast), "beta_slow": float(cfg.beta_slow),
                "attention_factor": float(cfg.attention_factor)},
        },
    }


def rmsnorm(x, w, eps):
    return w.astype(F32) * x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)


def by_rows(f, x):
    """f over x [T, ...] a block of ROWS positions at a time."""
    T = x.shape[0]
    pad = -T % ROWS
    xp = jnp.concatenate([x, jnp.zeros((pad, *x.shape[1:]), x.dtype)]) if pad else x
    out = jax.lax.map(f, xp.reshape(-1, ROWS, *x.shape[1:]))
    return jax.tree.map(lambda o: o.reshape(-1, *o.shape[2:])[:T], out)


def swiglu(ab):
    a, b = jnp.split(ab, 2, axis=-1)
    return jax.nn.silu(a) * b


# ----------------------------------------------------------------------
# positions
# ----------------------------------------------------------------------
def frequencies(rope: dict, dim: int) -> tuple:
    """(the ``dim / 2`` pairs' frequencies, the factor on cos and sin) of
    one entry of ``rope_parameters``, written out."""
    base = rope["rope_theta"]
    plain = [base ** (-2.0 * i / dim) for i in range(dim // 2)]
    if rope["rope_type"] == "default":
        return plain, 1.0
    assert rope["rope_type"] == "yarn", rope["rope_type"]
    orig, factor = rope["original_max_position_embeddings"], rope["factor"]

    def pair_that_turns(rotations):
        # the pair whose wavelength fits `rotations` times into the original context
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(pair_that_turns(rope["beta_fast"])), 0)
    high = min(math.ceil(pair_that_turns(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i, f in enumerate(plain):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)  # 0: the plain frequency; 1: that over factor
        out.append(f / factor * ramp + f * (1.0 - ramp))
    return out, rope.get("attention_factor", 0.1 * math.log(factor) + 1.0)


def rotate(x, rope: dict):
    """x [T, heads, dim] at positions 0 .. T - 1, halves x1 | x2."""
    dim = x.shape[-1]
    freqs, c = frequencies(rope, dim)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None, None] * jnp.asarray(freqs, F32)
    cos, sin = c * jnp.cos(ang), c * jnp.sin(ang)
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ----------------------------------------------------------------------
# attention
# ----------------------------------------------------------------------
def attention_part(y, lp, c, kind):
    """Grouped-query attention on normed tokens y [T, d] of one
    sequence, QUERIES queries at a time over the whole mask of the
    layer's kind -> (what it adds [T, d], the keys as attended: normed
    and rotated, a position one row of the 4 K/V heads [T, 512])."""
    T = y.shape[0]
    Hq, Hk, hd, eps = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"], c["rms_norm_eps"]
    rope = c["rope_parameters"][kind]
    window = c["sliding_window"] if kind == "sliding_attention" else None
    wqkv = lp["wqkv"].astype(F32)
    qkv = by_rows(lambda yb: yb @ wqkv, y)
    q = rotate(rmsnorm(qkv[:, :Hq * hd].reshape(T, Hq, hd), lp["w_qn"], eps), rope)
    cached = rotate(rmsnorm(qkv[:, Hq * hd:(Hq + Hk) * hd].reshape(T, Hk, hd), lp["w_kn"], eps), rope)
    k = jnp.repeat(cached, Hq // Hk, axis=1)
    v = jnp.repeat(qkv[:, (Hq + Hk) * hd:].reshape(T, Hk, hd), Hq // Hk, axis=1)
    pos = jnp.arange(T)

    def rows(xs):
        qb, tb = xs
        s = jnp.einsum("thd,khd->htk", qb, k) / math.sqrt(hd)
        seen = pos[None, :] <= tb[:, None]
        if window is not None:
            seen &= tb[:, None] - pos[None, :] < window
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("htk,khd->thd", jax.nn.softmax(s, axis=-1), v)

    pad = -T % QUERIES
    qp = jnp.concatenate([q, jnp.zeros((pad, Hq, hd), F32)])
    o = jax.lax.map(rows, (qp.reshape(-1, QUERIES, Hq, hd), jnp.arange(T + pad).reshape(-1, QUERIES)))
    wo = lp["wo"].astype(F32)
    return by_rows(lambda ob: ob @ wo, o.reshape(T + pad, Hq * hd)[:T]), cached.reshape(T, Hk * hd)


# ----------------------------------------------------------------------
# the experts
# ----------------------------------------------------------------------
def expert_weights(y, lp, c):
    """[N, E] float32: a token's weight for each of its chosen experts
    (its softmax over all experts, over the chosen ones' sum where
    ``norm_topk_prob``), zero for the others; and the experts chosen [N,
    k] (lowest number first among equals, as ``top_k``)."""
    g = jax.nn.softmax(y @ lp["router"].astype(F32), axis=-1)
    top_g, top_e = jax.lax.top_k(g, c["num_experts_per_tok"])
    if c["norm_topk_prob"]:
        top_g = top_g / top_g.sum(-1, keepdims=True)
    w = jnp.zeros_like(g).at[jnp.arange(g.shape[0])[:, None], top_e].set(top_g)
    return w, top_e


def expert_part(y, lp, c):
    """What the experts add on normed tokens y [N, d], every expert on
    every token; and the experts chosen."""
    w, top_e = expert_weights(y, lp, c)

    def one_expert(e, out):
        return out + w[:, e, None] * (swiglu(y @ lp["wgu"][e].astype(F32)) @ lp["wd"][e].astype(F32))

    return jax.lax.fori_loop(0, lp["wgu"].shape[0], one_expert, jnp.zeros_like(y)), top_e


# ----------------------------------------------------------------------
# the model
# ----------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("kind", "spec"))
def layer(x, lp, *, kind, spec):
    """One layer on x [T, d] float32 -> (x, the experts each token chose
    [T, k], the layer's keys as attended [T, 512]).  `spec`: ``numbers``
    as JSON."""
    c = json.loads(spec)
    att, keys = attention_part(rmsnorm(x, lp["norm1"], c["rms_norm_eps"]), lp, c, kind)
    x = x + att
    y = rmsnorm(x, lp["norm2"], c["rms_norm_eps"])
    out, top_e = by_rows(lambda yb: expert_part(yb, lp, c), y)
    return x + out, top_e, keys


def full_logits(params, tokens, cfg, positions=None, keys=False):
    """tokens [T] of ONE sequence -> (logits [len(positions), V] float32
    at `positions` (all of them when None), the experts every token chose
    in every layer [L, T, k]) and, where `keys`, every layer's keys as
    attended [L, T, 512] (what a cache of this model holds).  `cfg`: a
    config with the program's attribute names, or ``numbers`` of one
    (edited, for a wrong-on-purpose reading)."""
    c = cfg if isinstance(cfg, dict) else numbers(cfg)
    spec = json.dumps(c, sort_keys=True)
    # on the TPU a float32 matmul runs as bf16 passes unless told otherwise
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        chose, cached = [], []
        for kind, lp in zip(c["layer_types"], params["layers"]):
            x, top_e, k = layer(x, lp, kind=kind, spec=spec)
            chose.append(top_e)
            cached.append(k)
        if positions is not None:
            x = x[jnp.asarray(positions)]
        out = _head(x, params["norm"], params["lm_head"], eps=c["rms_norm_eps"]), jnp.stack(chose)
        return (*out, jnp.stack(cached)) if keys else out


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, lm_head, *, eps):
    # under jit the head's cast to float32 fuses into the matmul
    return rmsnorm(x, norm, eps) @ lm_head.astype(F32)

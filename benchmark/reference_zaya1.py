"""The plain reference of the ZAYA1 family: forward pass in float32
``jax.numpy`` at ``highest`` matmul precision over one WHOLE sequence at
once: position ``t - 1`` is read by shifting the sequence (zeros before
position 0), attention is one softmax a query over every earlier
position with each K/V head repeated for its query heads, the experts
the dense way (every held expert's output for every token times the
token's weight for it: ``p[e]`` for the one it chose, zero for the
others).  No kernel, no cache, no tails, no chunks, no pages, no sort,
no grouped matmul, and nothing imported from the program.  It reads the
program's parameter tree (``ray_tpu/models/zaya.py`` lists the leaves):
that tree is the interface.  There is no head leaf: the head is
``embed``.

The model (Zyphra/ZAYA1-8B ``config.json``, ``model_type: zaya``; keys
in brackets; the form of the two mechanisms from Zyphra, "Compressed
Convolutional Attention", arXiv:2510.04476, and the ZAYA1 technical
report, arXiv:2511.17127).  ``rmsnorm(x, w) = w x rsqrt(mean(x^2) +
1e-5)`` [rms_norm_eps].  d = 2048, H = 8 query heads, G = 2 K/V heads,
hd = 128, R = 256 [hidden_size, num_attention_heads,
num_key_value_heads, head_dim, router_hidden_size].

    x = E[tok];  r_{-1} = 0
    for each of the layers (every one ``hybrid``)                 [layer_types]
        x = a1 * x + b1 * CCA(rmsnorm(x, w1))
        x = a2 * x + b2 * MoE(rmsnorm(x, w2), r_{l-1});  hand r_l on
    logits = rmsnorm(x, w_f) E^T                                  [tie_word_embeddings]

    CCA   [q~ | k~ | v1 | v2] = y W         8 x 128 | 2 x 128 | 128 | 128;  s = [q~ | k~], 10 heads
          c0_t = b0 + w0[:, 0] * s_{t-1} + w0[:, 1] * s_t                      [cca_time0 2] depthwise
          c1_t[h] = b1[h] + c0_{t-1}[h] W1[h, 0] + c0_t[h] W1[h, 1]            [cca_time1 2] grouped by head
          [q^c | k^c] = c1;  s_{-1} = c0_{-1} = 0
          q[h] = q^c[h] + (q~[h] + k~[h // 4]) / 2                             the q-k mean, from the latents
          k[g] = k^c[g] + (k~[g] + mean of q~[h] over the heads of g) / 2      BEFORE the convolutions
          q[h] = sqrt(128) q[h] / |q[h]|;  k[g] = tau_g sqrt(128) k[g] / |k[g]|
          the first 64 of each head's 128 values rotated, theta 5,000,000, half-split   [partial_rotary_factor,
                                                                                         rope_parameters.hybrid]
          v_t = [y_t W_v1 | y_{t-1} W_v2]                                      the value shift; y_{-1} = 0
          score(t, s <= t) = q . k / sqrt(128), query head h on K/V head h // 4;  out = softmax(score) v W_o
    MoE   u = y W_dn;  r_l = u + gamma r_{l-1}                                 depth averaging
          z = rmsnorm(r_l, w_r);  a = gelu(z W_1 + c_1);  a = gelu(a W_2 + c_2)
          p = softmax(a W_3) over 17: the 16 experts and output 16, no expert  [num_experts 16]
          e = argmax(p + beta)                                                 [num_experts_per_tok 1]
          p[e] W_d,e (silu(y W_g,e) * (y W_u,e)) where e < 16 is held, 0 where e = 16   [hidden_act silu,
                                                                                         moe_intermediate_size 2048]

DEPARTURES from the two papers, and what is ASSUMED because the
catalog's row of the source does not settle it
(``benchmark/configs/zaya1-8b.json`` lists the same): each is a comment
at its line below.  ``WRONG`` names eight models that are NOT this one,
each one mechanism off: the tests and the builder's readings hold the
program to be far from every one of them.

The weights stay in the program's dtype; one layer's are cast to
float32 at a time, and within the experts one expert at a time; the
projections and the experts go a block of ``ROWS`` positions at a time,
attention ``QUERIES`` queries at a time and the head a block of the
vocabulary's rows at a time, so that the reference of a sequence of
16,384 fits beside the engine's weights and cache on the chip.  Only a
process that holds the chip (or a CPU rehearsal) imports this.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROWS = 1024  # positions a block of the projections and the experts
QUERIES = 64  # queries a block of attention: their scores over every position are [8, QUERIES, T]
HEAD_ROWS = 32768  # about as many rows of the vocabulary a block of the head
WRONG = ("no_value_shift", "no_qk_mean", "conv1_depthwise", "rotate_all", "gamma_0", "skip_is_an_expert",
         "p_is_1", "scales_1")


def rmsnorm(x, w, eps):
    return w.astype(F32) * x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)


def by_rows(f, x):
    """f over x (an array [T, ...], or a tuple of them) a block of ROWS
    positions at a time."""
    T = jax.tree.leaves(x)[0].shape[0]
    pad = -T % ROWS

    def blocks(a):
        ap = jnp.concatenate([a, jnp.zeros((pad, *a.shape[1:]), a.dtype)]) if pad else a
        return ap.reshape(-1, ROWS, *a.shape[1:])

    out = jax.lax.map(f, jax.tree.map(blocks, x))
    return jax.tree.map(lambda o: o.reshape(-1, *o.shape[2:])[:T], out)


def before(a):
    """a [T, ...] one position later: row t is position t - 1, zeros before position 0."""
    return jnp.concatenate([jnp.zeros_like(a[:1]), a[:-1]])


def rotate(x, rot, theta):
    """x [T, heads, hd]: the first ``rot`` values of each head turned by
    the position, half-split (the pair (i, i + rot / 2) by ``t
    theta^(-2i/rot)``); the others pass."""
    half = rot // 2
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None, None] * theta ** (-jnp.arange(half, dtype=F32) / half)
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang),
                            x[..., rot:]], axis=-1)


# ----------------------------------------------------------------------
# compressed convolutional attention
# ----------------------------------------------------------------------
def cca_part(y, lp, c, wrong):
    """CCA on normed tokens y [T, d] of one sequence."""
    T = y.shape[0]
    H, G, hd = c["n_head"], c["n_kv_head"], c["head_dim"]
    R, S, vh = H // G, (H + G) * hd, G // 2 * hd
    w = lp["wqkv"].astype(F32)
    sv = by_rows(lambda yb: yb @ w, y)
    s, v1, v2 = sv[:, :S], sv[:, S:S + vh], sv[:, S + vh:]
    # ASSUMED: the order of the steps (projections, the two convolutions, the q-k mean, norm and
    # temperature, rotation) is the paper's; the row gives only the two kernel sizes and the head counts
    w0, w1 = lp["conv0_w"].astype(F32), lp["conv1_w"].astype(F32)
    c0 = lp["conv0_b"].astype(F32) + w0[:, 0] * before(s) + w0[:, 1] * s  # no activation behind either convolution
    if wrong == "conv1_depthwise":
        w1 = w1 * jnp.eye(hd, dtype=F32)
    c1 = (jnp.einsum("thd,hde->the", before(c0).reshape(T, H + G, hd), w1[:, 0])
          + jnp.einsum("thd,hde->the", c0.reshape(T, H + G, hd), w1[:, 1])
          + lp["conv1_b"].astype(F32).reshape(H + G, hd))
    q_lat, k_lat = s[:, :H * hd].reshape(T, H, hd), s[:, H * hd:].reshape(T, G, hd)
    mean = 0.0 if wrong == "no_qk_mean" else 0.5
    q = c1[:, :H] + mean * (q_lat + jnp.repeat(k_lat, R, axis=1))
    k = c1[:, H:] + mean * (k_lat + q_lat.reshape(T, G, R, hd).mean(2))
    # ASSUMED: sqrt(hd) on BOTH normed sides, so that the softmax scale stays hd^-0.5 and a score is
    # tau sqrt(hd) cos; DEPARTURE: 1e-12 under the root, so that a row of zeros stays zeros
    q = q * jax.lax.rsqrt((q * q).mean(-1, keepdims=True) + 1e-12)
    k = k * jax.lax.rsqrt((k * k).mean(-1, keepdims=True) + 1e-12) * lp["tau"].astype(F32)[None, :, None]
    # ASSUMED: rotation AFTER the norm (a rotation keeps a norm, so only the order with tau could differ)
    rot = hd if wrong == "rotate_all" else int(hd * c["partial_rotary_factor"])
    q, k = rotate(q, rot, c["rope_theta"]), rotate(k, rot, c["rope_theta"])
    # ASSUMED: the value shift takes half of the K/V HEADS (head 1 of 2), not half of each head's columns
    v = jnp.concatenate([v1, v2 if wrong == "no_value_shift" else before(v2)], axis=-1).reshape(T, G, hd)
    k, v = jnp.repeat(k, R, axis=1), jnp.repeat(v, R, axis=1)
    pos = jnp.arange(T)

    def rows(xs):
        qb, tb = xs
        scores = jnp.einsum("thd,khd->htk", qb, k) * hd ** -0.5
        scores = jnp.where(pos[None, None, :] <= tb[None, :, None], scores, -jnp.inf)
        return jnp.einsum("htk,khd->thd", jax.nn.softmax(scores, axis=-1), v)

    pad = -T % QUERIES
    qp = jnp.concatenate([q, jnp.zeros((pad, H, hd), F32)])
    o = jax.lax.map(rows, (qp.reshape(-1, QUERIES, H, hd), jnp.arange(T + pad).reshape(-1, QUERIES)))
    wo = lp["wo"].astype(F32)
    return by_rows(lambda ob: ob @ wo, o.reshape(T + pad, H * hd)[:T])


# ----------------------------------------------------------------------
# the router and the experts
# ----------------------------------------------------------------------
def router(y, r, lp, c, wrong):
    """y [N, d], r [N, R] the state the layer before handed on -> (this
    layer's state, the probabilities [N, 17], the output chosen [N])."""
    gamma = 0.0 if wrong == "gamma_0" else lp["router_gamma"].astype(F32)
    r = y @ lp["router_down"].astype(F32) + gamma * r
    # ASSUMED: the router's depth (two hidden layers of R with biases), its RMSNorm and its
    # activation; gelu is the exact one (erf), not the tanh form
    a = rmsnorm(r, lp["router_norm"], c["layer_norm_epsilon"])
    for w, b in (("router_w1", "router_b1"), ("router_w2", "router_b2")):
        a = jax.nn.gelu(a @ lp[w].astype(F32) + lp[b].astype(F32), approximate=False)
    p = jax.nn.softmax(a @ lp["router_w3"].astype(F32), axis=-1)
    return r, p, jnp.argmax(p + lp["router_beta"].astype(F32), axis=-1)  # the biases choose and do not weigh


def expert_part(y, r, lp, c, wrong):
    """What the experts add on normed tokens y [N, d]: each token's OWN
    expert's output times the probability the router gave it, where it
    is held; nothing where it chose output ``num_experts``, no expert.
    And the router's state, and the output chosen [N, 1]."""
    first, count, E = c["experts_first"], lp["w_in"].shape[0], c["num_experts"]
    r, p, e = router(y, r, lp, c, wrong)
    # ASSUMED: p[e] is not renormalised over the experts alone
    p_e = jnp.ones_like(p[:, 0]) if wrong == "p_is_1" else jnp.take_along_axis(p, e[:, None], axis=-1)[:, 0]
    # ASSUMED: the skip output exists in this release and yields 0
    to = jnp.where(e == E, E - 1, e) if wrong == "skip_is_an_expert" else e

    def one_expert(j, out):
        gate, up = jnp.split(y @ lp["w_in"][j].astype(F32), 2, axis=-1)
        mine = jnp.where(to == first + j, p_e, 0.0)
        return out + mine[:, None] * ((jax.nn.silu(gate) * up) @ lp["w_down"][j].astype(F32))

    return jax.lax.fori_loop(0, count, one_expert, jnp.zeros_like(y)), r, e[:, None]


# ----------------------------------------------------------------------
# the model
# ----------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("cfg", "wrong"))
def layer(x, r, lp, *, cfg, wrong):
    """One layer on x [T, d] and r [T, R] float32 -> (x, r, the output
    each token chose [T, 1]).  `cfg`: a tuple of (name, value) pairs."""
    c = dict(cfg)
    # ASSUMED: the residual scales are four vectors of d a layer, with no bias
    a1, b1, a2, b2 = (jnp.ones_like(lp[k], F32) if wrong == "scales_1" else lp[k].astype(F32)
                      for k in ("a1", "b1", "a2", "b2"))
    x = a1 * x + b1 * cca_part(rmsnorm(x, lp["norm1"], c["layer_norm_epsilon"]), lp, c, wrong)
    y = rmsnorm(x, lp["norm2"], c["layer_norm_epsilon"])
    out, r, e = by_rows(lambda yr: expert_part(*yr, lp, c, wrong), (y, r))
    return a2 * x + b2 * out, r, e


_KEYS = ("layer_norm_epsilon", "n_head", "n_kv_head", "head_dim", "partial_rotary_factor", "rope_theta",
         "num_experts", "experts_first")


def full_logits(params, tokens, cfg, positions=None, wrong=None):
    """tokens [T] of ONE sequence -> (logits [len(positions), V] float32
    at `positions` (all of them when None), the output every token chose
    in every layer [L, T, 1] (``num_experts``: none), every layer's
    router state [L, T, R]).  `cfg` gives the attributes named in _KEYS;
    the experts held are ``cfg.experts_first`` on, as many as the tree
    holds.  `wrong`: one of WRONG, a model with that mechanism off."""
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"no wrong model named {wrong!r}")
    sizes = tuple((k, getattr(cfg, k)) for k in _KEYS)
    # on the TPU a float32 matmul runs as bf16 passes unless told otherwise
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        r = jnp.zeros((x.shape[0], params["layers"][0]["router_down"].shape[1]), F32)
        chose, states = [], []
        for lp in params["layers"]:
            x, r, e = layer(x, r, lp, cfg=sizes, wrong=wrong)
            chose.append(e)
            states.append(r)
        if positions is not None:
            x = x[jnp.asarray(positions)]
        return (_head(x, params["norm"], params["embed"], eps=float(cfg.layer_norm_epsilon)),
                jnp.stack(chose), jnp.stack(states))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, embed, *, eps):
    """The tied head, a block of the vocabulary's rows at a time (the
    whole embedding in float32 is 2.1 GB)."""
    V = embed.shape[0]
    blocks = max(1, V // HEAD_ROWS)
    if V % blocks:
        blocks = 1
    y = rmsnorm(x, norm, eps)
    out = jax.lax.map(lambda rows: y @ rows.astype(F32).T, embed.reshape(blocks, V // blocks, -1))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], V)

"""The plain reference of the Granite 4.0-H family: forward pass in
float32 ``jax.numpy`` at ``highest`` matmul precision, the Mamba-2 scan
one position after another (``jax.lax.scan`` over the recurrence as it is
written, no chunked form), the convolution as four shifted sums over the
whole sequence, attention as one softmax a query over every earlier
position with each K/V head repeated for its query heads, the experts
the dense way (every held expert's output for every token times the
token's weight for it, zero where the expert is not among its 10).  No
kernel, no cache, no chunks, no pages, no state carried in, no sort, no
grouped matmul, and nothing imported from the program.  It reads the
program's parameter tree (``embed``, ``norm``, ``layers`` of ``norm1``,
by kind ``in_proj, conv_w, conv_b, A_log, D, dt_bias, w_gn, out_proj`` |
``wqkv, wo``, then ``norm2, router, w_in`` (an expert's ``[a | b]`` side
by side, ``[d, 2f]``), ``w_down, w_in_shared, w_down_shared``): that
tree is the interface.  There is no head leaf: the head is ``embed``.

The model (ibm-granite/granite-4.0-h-small ``config.json``,
``model_type: granitemoehybrid``; keys in brackets).  ``rmsnorm(x, w) =
w x rsqrt(mean(x^2) + 1e-5)`` [rms_norm_eps].

    x = 12 E[tok]                                        [embedding_multiplier]
    for each layer, its mixer by [layer_types]:
        x = x + 0.22 Mixer(rmsnorm(x, w1))               [residual_multiplier]
        y = rmsnorm(x, w2);  x = x + 0.22 (Experts(y) + Shared(y))
    logits = rmsnorm(x, w_f) E_held^T / 16               [logits_scaling, tie_word_embeddings]

    mamba      [z | xBC | dt] = y W_in       8192 | 8448 | 128 [mamba_expand 2 x hidden_size 4096 =
                                              mamba_n_heads 128 x mamba_d_head 64; + 2 x mamba_n_groups 1
                                              x mamba_d_state 128; mamba_n_heads]
               xBC_t = silu(b_c + sum_{j=0..3} w_c[:, j] xBC_{t-3+j})   [mamba_d_conv 4, mamba_conv_bias], zeros before 0
               xBC -> x [128, 64] | B [1, 128] | C [1, 128]
               dt = softplus(dt + dt_bias);  A = -exp(A_log)
               head h:  S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  o_t = S_t C_t + D x_t
               u = o * silu(z);  u = w_n u rsqrt(mean over all 8,192 columns of u^2 + 1e-5);  out = u W_out
    attention  q 32 heads of 128, k and v 8 heads of 128 = y W_qkv   [num_attention_heads, num_key_value_heads]
               query head i reads K/V head i // 4; score(t, s<=t) = 0.0078125 q.k [attention_multiplier];
               no rotation [position_embedding_type nope]; out = softmax(score) v W_o
    Experts    g = y W_r over all 72                      [num_local_experts]
               the 10 of largest g; p = softmax over those 10 alone   [num_experts_per_tok]
               sum over those of the 10 that are HELD of p_e W_down,e (silu(a_e) * b_e), [a_e | b_e] = y W_in,e
                                                          [hidden_act silu, intermediate_size 768]
    Shared     W_down,s (silu(a) * b), [a | b] = y W_in,s  [shared_intermediate_size 1536]

The SHARE (``experts_first`` and as many routed experts as the tree
holds; the rows of the vocabulary the tree holds): the router scores all
72 experts and keeps 10 a token; of those, the held ones alone are in
the tree and add their part; the others add nothing, here as in the
program (the partial sum that one chip of an expert-parallel pair
computes).

DEPARTURES from the published code, and what is ASSUMED because the
catalog's row of the source does not settle it
(``benchmark/configs/granite-4.0-h-small.json`` lists the same):

- ``intermediate_size`` 768 is read as ONE routed expert's width: the
  config has no key of its own for it and the published
  ``GraniteMoeHybridParallelExperts`` is sized by it;
- ``head_dim = hidden_size / num_attention_heads = 128`` (the row gives
  none); ``rope_theta`` is in the config and unused under ``nope``;
- the gate is applied BEFORE the norm of the mixer's output (the
  published ``GraniteMoeHybridRMSNormGated``);
- the scan's state is float32 throughout (here everything is); the
  published code runs the scan through fused kernels whose order of
  summation differs from this recurrence's, which is the definition;
- weights seeded random (normal 0.02, norm weights 1; the convolution's
  weights and bias uniform in +-0.5, ``A_log = log U(1, 16)``,
  ``dt_bias`` the inverse softplus of a log-uniform step in ``[0.001,
  0.1]``, ``D = 1``: a Mamba-2 mixer's published initialisation).

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

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROWS = 1024  # positions a block of the projections and the experts
QUERIES = 64  # queries a block of attention: their scores over every position are [32, QUERIES, T]


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
# mamba
# ----------------------------------------------------------------------
def convolution(xbc, w, b):
    """xbc [T, C] -> silu(b + sum_j w[:, j] xbc_{t-K+1+j}), zeros before
    position 0."""
    T, K = xbc.shape[0], w.shape[1]
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    acc = b.astype(F32) + sum(w[:, j].astype(F32) * padded[j:j + T] for j in range(K))
    return jax.nn.silu(acc)


def scan(x, dt, A, B, C, D):
    """The recurrence, a position at a time from a state of zeros.  x
    [T, H, P], dt [T, H] after its softplus, A, D [H], B, C [T, N] (one
    group: every head reads them) -> o [T, H, P]."""
    H, P = x.shape[1:]

    def step(S, at):
        x_t, dt_t, B_t, C_t = at
        S = jnp.exp(dt_t * A)[:, None, None] * S + (dt_t[:, None] * x_t)[:, :, None] * B_t[None, None, :]
        return S, (S * C_t[None, None, :]).sum(-1) + D[:, None] * x_t

    _, o = jax.lax.scan(step, jnp.zeros((H, P, B.shape[1]), F32), (x, dt, B, C))
    return o


def mamba_part(y, lp, c):
    """The Mamba-2 mixer on normed tokens y [T, d] of one sequence."""
    T = y.shape[0]
    H, P, N = c["mamba_num_heads"], c["mamba_head_dim"], c["ssm_state_size"]
    assert c["n_groups"] == 1, "this reference writes the one group the source has"
    inner = H * P
    w_in = lp["in_proj"].astype(F32)
    zxd = by_rows(lambda yb: yb @ w_in, y)
    z, xbc, dt = zxd[:, :inner], zxd[:, inner:inner + inner + 2 * N], zxd[:, -H:]
    xbc = convolution(xbc, lp["conv_w"], lp["conv_b"])
    x, B, C = xbc[:, :inner].reshape(T, H, P), xbc[:, inner:inner + N], xbc[:, inner + N:]
    dt = jax.nn.softplus(dt + lp["dt_bias"].astype(F32))
    o = scan(x, dt, -jnp.exp(lp["A_log"].astype(F32)), B, C, lp["D"].astype(F32))
    u = o.reshape(T, inner) * jax.nn.silu(z)
    u = u * jax.lax.rsqrt((u * u).mean(-1, keepdims=True) + c["layer_norm_epsilon"]) * lp["w_gn"].astype(F32)
    w_out = lp["out_proj"].astype(F32)
    return by_rows(lambda ub: ub @ w_out, u)


# ----------------------------------------------------------------------
# attention
# ----------------------------------------------------------------------
def attention_part(y, lp, c):
    """Grouped-query attention on normed tokens y [T, d] of one
    sequence: every query over every earlier position, QUERIES queries
    at a time; no rotation; the scores times ``attention_multiplier``."""
    T = y.shape[0]
    Hq, Hk, hd = c["n_head"], c["n_kv_head"], c["head_dim"]
    wqkv = lp["wqkv"].astype(F32)
    qkv = by_rows(lambda yb: yb @ wqkv, y)
    q = qkv[:, :Hq * hd].reshape(T, Hq, hd)
    k = jnp.repeat(qkv[:, Hq * hd:(Hq + Hk) * hd].reshape(T, Hk, hd), Hq // Hk, axis=1)
    v = jnp.repeat(qkv[:, (Hq + Hk) * hd:].reshape(T, Hk, hd), Hq // Hk, axis=1)
    pos = jnp.arange(T)

    def rows(xs):
        qb, tb = xs
        s = jnp.einsum("thd,khd->htk", qb, k) * c["attention_multiplier"]
        s = jnp.where(pos[None, None, :] <= tb[None, :, None], s, -jnp.inf)
        return jnp.einsum("htk,khd->thd", jax.nn.softmax(s, axis=-1), v)

    pad = -T % QUERIES
    qp = jnp.concatenate([q, jnp.zeros((pad, Hq, hd), F32)])
    o = jax.lax.map(rows, (qp.reshape(-1, QUERIES, Hq, hd), jnp.arange(T + pad).reshape(-1, QUERIES)))
    wo = lp["wo"].astype(F32)
    return by_rows(lambda ob: ob @ wo, o.reshape(T + pad, Hq * hd)[:T])


# ----------------------------------------------------------------------
# the experts
# ----------------------------------------------------------------------
def expert_weights(y, lp, c):
    """[N, E] float32 over ALL the router's experts: a token's weight
    for each of its chosen experts (the softmax over the chosen logits
    alone), zero for the others; and the experts chosen [N, k] (lowest
    number first among equals, as ``top_k``)."""
    g = y @ lp["router"].astype(F32)
    top_g, top_e = jax.lax.top_k(g, c["num_experts_per_tok"])
    p = jax.nn.softmax(top_g, axis=-1)
    w = jnp.zeros_like(g).at[jnp.arange(g.shape[0])[:, None], top_e].set(p)
    return w, top_e


def expert_part(y, lp, c):
    """What the experts add on normed tokens y [N, d]: the shared
    expert's output and the held routed experts' weighted ones; and the
    experts chosen."""
    first, count = c["experts_first"], lp["w_in"].shape[0]
    w, top_e = expert_weights(y, lp, c)
    out = swiglu(y @ lp["w_in_shared"].astype(F32)) @ lp["w_down_shared"].astype(F32)

    def one_expert(e, out):
        return out + w[:, first + e, None] * (swiglu(y @ lp["w_in"][e].astype(F32)) @ lp["w_down"][e].astype(F32))

    return jax.lax.fori_loop(0, count, one_expert, out), top_e


# ----------------------------------------------------------------------
# the model
# ----------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("kind", "cfg"))
def layer(x, lp, *, kind, cfg):
    """One layer on x [T, d] float32 -> (x, the experts each token chose
    [T, k]).  `cfg`: a tuple of (name, value) pairs."""
    c = dict(cfg)
    y = rmsnorm(x, lp["norm1"], c["layer_norm_epsilon"])
    mixer = mamba_part if kind == "mamba" else attention_part
    x = x + c["residual_multiplier"] * mixer(y, lp, c)
    y = rmsnorm(x, lp["norm2"], c["layer_norm_epsilon"])
    out, top_e = by_rows(lambda yb: expert_part(yb, lp, c), y)
    return x + c["residual_multiplier"] * out, top_e


_KEYS = ("layer_norm_epsilon", "n_head", "n_kv_head", "head_dim", "mamba_num_heads", "mamba_head_dim",
         "ssm_state_size", "n_groups", "num_experts_per_tok", "experts_first", "residual_multiplier",
         "attention_multiplier")


def full_logits(params, tokens, cfg, positions=None):
    """tokens [T] of ONE sequence -> (logits [len(positions), rows held]
    float32 at `positions` (all of them when None), the experts every
    token chose in every layer [L, T, k]).  `cfg` gives ``layer_types``
    (a mixer's kind a layer), ``embedding_multiplier``,
    ``logits_scaling`` and the attributes named in _KEYS; the experts
    held are ``cfg.experts_first`` on, as many as the tree holds."""
    sizes = tuple((k, getattr(cfg, k)) for k in _KEYS)
    # on the TPU a float32 matmul runs as bf16 passes unless told otherwise
    with jax.default_matmul_precision("highest"):
        x = cfg.embedding_multiplier * params["embed"][tokens].astype(F32)
        chose = []
        for kind, lp in zip(cfg.layer_types, params["layers"]):
            x, top_e = layer(x, lp, kind=kind, cfg=sizes)
            chose.append(top_e)
        if positions is not None:
            x = x[jnp.asarray(positions)]
        return (_head(x, params["norm"], params["embed"], eps=float(cfg.layer_norm_epsilon),
                      scaling=float(cfg.logits_scaling)), jnp.stack(chose))


@functools.partial(jax.jit, static_argnames=("eps", "scaling"))
def _head(x, norm, embed, *, eps, scaling):
    # under jit the head's cast to float32 fuses into the matmul
    return rmsnorm(x, norm, eps) @ embed.astype(F32).T / scaling

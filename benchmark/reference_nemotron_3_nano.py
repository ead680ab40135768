"""The plain reference of the Nemotron-H family: forward pass in float32
``jax.numpy`` at ``highest`` matmul precision, the Mamba-2 scan one
position after another (``jax.lax.scan`` over the recurrence as it is
written, no chunked form), the convolution as four shifted sums over the
whole sequence, attention as one softmax a query over every earlier
position with each K/V head repeated for its query heads, the experts
the dense way (every held expert's output for every token times the
token's weight for it, zero where the expert is not among its 6).  No
kernel, no cache, no chunks, no pages, no state carried in, no sort, no
grouped matmul, and nothing imported from the program.  It reads the
program's parameter tree (``embed``, ``layers`` of ``norm`` and by kind
``in_proj, conv_w, conv_b, A_log, D, dt_bias, w_gn, out_proj`` | ``wqkv,
wo`` | ``router, b_sel, w_up`` (an expert's up projection TRANSPOSED,
``[f, d]``), ``w_down, w_up_shared, w_down_shared``, ``norm``,
``lm_head``): that tree is the interface.

The model (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 ``config.json``,
``model_type: nemotron_h``; keys in brackets).  ``x = E[tok]``; for each
layer ``x = x + Part(rmsnorm(x, w))`` with ``rmsnorm(x, w) = w x
rsqrt(mean(x^2) + 1e-5)`` [layer_norm_epsilon], the part by the layer's
letter [hybrid_override_pattern]:

    M   [z | xBC | dt] = y W_in            4096 | 6144 | 64 [mamba_num_heads 64 x mamba_head_dim 64;
                                            + 2 x n_groups 8 x ssm_state_size 128; mamba_num_heads]
        xBC_t = silu(b_c + sum_{j=0..3} w_c[:, j] xBC_{t-3+j})   [conv_kernel 4, use_conv_bias], zeros before 0
        xBC -> x [64, 64] | B [8, 128] | C [8, 128]
        dt = softplus(dt + dt_bias);  A = -exp(A_log)
        head h of group g = h // 8:  S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  o_t = S_t C_t + D x_t
        u = o * silu(z);  u = w_n u rsqrt(mean over each group's 512 columns of u^2 + 1e-5);  out = u W_out
    *   q 32 heads of 128, k and v 2 heads of 128 = y W_qkv    [num_attention_heads, num_key_value_heads, head_dim]
        query head i reads K/V head i // 16; score(t, s<=t) = q.k 128^-0.5; no rotation; out = softmax(score) v W_o
    E   s = sigmoid(y W_r) over all 128    [n_routed_experts]
        the 6 of largest s + b_sel         [num_experts_per_tok; n_group 1, topk_group 1: no group limit]
        w_e = 2.5 s_e / sum of the 6 s     [norm_topk_prob, routed_scaling_factor]
        out = W_down,s relu(y W_up,s)^2 + sum over those of the 6 that are HELD of w_e W_down,e relu(y W_up,e)^2
                                           [mlp_hidden_act relu2, moe_intermediate_size 1856,
                                            moe_shared_expert_intermediate_size 3712]

then rmsnorm and the untied head over the rows of the vocabulary held.

The SHARE (``experts_first`` and as many routed experts as the tree
holds; the rows of the vocabulary the tree holds): the router scores all
128 experts and keeps 6 a token; of those, the held ones alone are in
the tree and add their part; the others add nothing, here as in the
program (the partial sum that one chip of an expert-parallel deployment
computes).

DEPARTURES from the published code, and what is ASSUMED because the
catalog's row of the source does not settle it
(``benchmark/configs/nemotron-3-nano.json`` lists the same):

- no positional rotation in attention: the published ``nemotron_h``
  modelling code applies none (``rope_theta`` and
  ``partial_rotary_factor`` are in the config and unused by it);
- the router scores by a sigmoid and selects with a bias ``b_sel`` that
  does not enter the weights: the config carries DeepSeek-V3's router
  keys (``n_group``, ``topk_group``, ``norm_topk_prob``,
  ``routed_scaling_factor``) and no ``scoring_func``; the published code
  adds 1e-20 to the sum it divides by, and so does this;
- ``d_inner = mamba_num_heads x mamba_head_dim = 4096``, not ``expand x
  hidden_size`` (5,376): the published mixer sizes its projections so;
- the gate is applied BEFORE the grouped norm (the published
  ``MambaRMSNormGated`` with ``norm_before_gate`` false);
- the scan's state is float32 throughout (here everything is); the
  published code runs the scan through fused kernels whose order of
  summation differs from this recurrence's, which is the definition;
- ``rescale_prenorm_residual`` is an initialisation rule and
  ``residual_in_fp32`` false: the stream is what the layers add up to;
- weights seeded random (normal 0.02, norm weights 1; the convolution's
  weights and bias uniform in +-0.5, ``A_log = log U(1, 16)``,
  ``dt_bias`` the inverse softplus of a log-uniform step in
  ``[time_step_min, time_step_max]``, ``D = 1``: a Mamba-2 mixer's
  published initialisation; ``b_sel`` normal 0.02; ``out_proj``,
  ``w_down`` and ``w_down_shared`` zero-sum over the hidden axis, so that
  the positive mean of silu and relu^2 adds no vector common to every
  token).

The weights stay in the program's dtype; one layer's are cast to
float32 at a time, and within an expert layer one expert at a time; the
projections and the experts go a block of ``ROWS`` positions at a time
and attention ``QUERIES`` queries at a time, so that the reference of a
2k-token sequence fits beside the engine's weights and cache on the
chip.  Only a process that holds the chip (or a CPU rehearsal) imports
this.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROWS = 1024  # positions a block of the projections and the experts
QUERIES = 128  # queries a block of attention: their scores over every position are [32, QUERIES, T]


def rmsnorm(x, w, eps):
    return w.astype(F32) * x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)


def by_rows(f, x):
    """f over x [T, ...] a block of ROWS positions at a time."""
    T = x.shape[0]
    pad = -T % ROWS
    xp = jnp.concatenate([x, jnp.zeros((pad, *x.shape[1:]), x.dtype)]) if pad else x
    out = jax.lax.map(f, xp.reshape(-1, ROWS, *x.shape[1:]))
    return jax.tree.map(lambda o: o.reshape(-1, *o.shape[2:])[:T], out)


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


# ----------------------------------------------------------------------
# M
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
    [T, H, P], dt [T, H] after its softplus, A, D [H], B, C [T, G, N] ->
    o [T, H, P]."""
    H, P = x.shape[1:]
    R = H // B.shape[1]

    def step(S, at):
        x_t, dt_t, B_t, C_t = at
        B_h, C_h = jnp.repeat(B_t, R, axis=0), jnp.repeat(C_t, R, axis=0)  # [H, N]: a group's heads share them
        S = jnp.exp(dt_t * A)[:, None, None] * S + (dt_t[:, None] * x_t)[:, :, None] * B_h[:, None, :]
        return S, (S * C_h[:, None, :]).sum(-1) + D[:, None] * x_t

    _, o = jax.lax.scan(step, jnp.zeros((H, P, B.shape[2]), F32), (x, dt, B, C))
    return o


def mamba_part(y, lp, c):
    """The Mamba-2 mixer on normed tokens y [T, d] of one sequence."""
    T = y.shape[0]
    H, P, G, N = c["mamba_num_heads"], c["mamba_head_dim"], c["n_groups"], c["ssm_state_size"]
    inner = H * P
    w_in = lp["in_proj"].astype(F32)
    zxd = by_rows(lambda yb: yb @ w_in, y)
    z, xbc, dt = zxd[:, :inner], zxd[:, inner:inner + inner + 2 * G * N], zxd[:, -H:]
    xbc = convolution(xbc, lp["conv_w"], lp["conv_b"])
    x = xbc[:, :inner].reshape(T, H, P)
    B = xbc[:, inner:inner + G * N].reshape(T, G, N)
    C = xbc[:, inner + G * N:].reshape(T, G, N)
    dt = jax.nn.softplus(dt + lp["dt_bias"].astype(F32))
    o = scan(x, dt, -jnp.exp(lp["A_log"].astype(F32)), B, C, lp["D"].astype(F32))
    u = (o.reshape(T, inner) * jax.nn.silu(z)).reshape(T, G, inner // G)
    u = u * jax.lax.rsqrt((u * u).mean(-1, keepdims=True) + c["layer_norm_epsilon"])
    u = u.reshape(T, inner) * lp["w_gn"].astype(F32)
    w_out = lp["out_proj"].astype(F32)
    return by_rows(lambda ub: ub @ w_out, u)


# ----------------------------------------------------------------------
# *
# ----------------------------------------------------------------------
def attention_part(y, lp, c):
    """Grouped-query attention on normed tokens y [T, d] of one
    sequence: every query over every earlier position, QUERIES queries
    at a time; no rotation."""
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
        s = jnp.einsum("thd,khd->htk", qb, k) * hd ** -0.5
        s = jnp.where(pos[None, None, :] <= tb[None, :, None], s, -jnp.inf)
        return jnp.einsum("htk,khd->thd", jax.nn.softmax(s, axis=-1), v)

    pad = -T % QUERIES
    qp = jnp.concatenate([q, jnp.zeros((pad, Hq, hd), F32)])
    o = jax.lax.map(rows, (qp.reshape(-1, QUERIES, Hq, hd), jnp.arange(T + pad).reshape(-1, QUERIES)))
    wo = lp["wo"].astype(F32)
    return by_rows(lambda ob: ob @ wo, o.reshape(T + pad, Hq * hd)[:T])


# ----------------------------------------------------------------------
# E
# ----------------------------------------------------------------------
def expert_weights(y, lp, c):
    """[N, E] float32 over ALL the router's experts: a token's weight
    for each of its chosen experts, zero for the others; and the experts
    chosen [N, k] (lowest number first among equals, as ``top_k``)."""
    s = jax.nn.sigmoid(y @ lp["router"].astype(F32))
    _, top_e = jax.lax.top_k(s + lp["b_sel"].astype(F32), c["num_experts_per_tok"])
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    if c["norm_topk_prob"]:
        top_s = top_s / (top_s.sum(-1, keepdims=True) + 1e-20)
    top_s = top_s * c["routed_scaling_factor"]
    w = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], top_e].set(top_s)
    return w, top_e


def expert_part(y, lp, c):
    """What the experts add on normed tokens y [N, d]: the shared
    expert's output and the held routed experts' weighted ones; and the
    experts chosen."""
    first, count = c["experts_first"], lp["w_up"].shape[0]
    w, top_e = expert_weights(y, lp, c)
    out = relu2(y @ lp["w_up_shared"].astype(F32)) @ lp["w_down_shared"].astype(F32)

    def one_expert(e, out):
        return out + w[:, first + e, None] * (relu2(y @ lp["w_up"][e].astype(F32).T) @ lp["w_down"][e].astype(F32))

    return jax.lax.fori_loop(0, count, one_expert, out), top_e


# ----------------------------------------------------------------------
# the model
# ----------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("kind", "cfg"))
def layer(x, lp, *, kind, cfg):
    """One layer on x [T, d] float32 -> (x, the experts each token chose
    [T, k], or None where the layer has none).  `cfg`: a tuple of (name,
    value) pairs."""
    c = dict(cfg)
    y = rmsnorm(x, lp["norm"], c["layer_norm_epsilon"])
    if kind == "M":
        return x + mamba_part(y, lp, c), None
    if kind == "*":
        return x + attention_part(y, lp, c), None
    out, top_e = by_rows(lambda yb: expert_part(yb, lp, c), y)
    return x + out, top_e


_KEYS = ("layer_norm_epsilon", "n_head", "n_kv_head", "head_dim", "mamba_num_heads", "mamba_head_dim",
         "ssm_state_size", "n_groups", "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
         "experts_first")


def full_logits(params, tokens, cfg, positions=None):
    """tokens [T] of ONE sequence -> (logits [len(positions), rows held]
    float32 at `positions` (all of them when None), the experts every
    token chose in every expert layer [Le, T, k]).  `cfg` gives
    ``pattern`` (a letter a layer) and the attributes named in _KEYS;
    the experts held are ``cfg.experts_first`` on, as many as the tree
    holds."""
    sizes = tuple((k, getattr(cfg, k)) for k in _KEYS)
    # on the TPU a float32 matmul runs as bf16 passes unless told otherwise
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        chose = []
        for kind, lp in zip(cfg.pattern, params["layers"]):
            x, top_e = layer(x, lp, kind=kind, cfg=sizes)
            if top_e is not None:
                chose.append(top_e)
        if positions is not None:
            x = x[jnp.asarray(positions)]
        return _head(x, params["norm"], params["lm_head"], eps=float(cfg.layer_norm_epsilon)), jnp.stack(chose)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, lm_head, *, eps):
    # under jit the head's cast to float32 fuses into the matmul
    return rmsnorm(x, norm, eps) @ lm_head.astype(F32)

"""The plain reference of the Jamba family: forward pass in float32
``jax.numpy`` at ``highest`` matmul precision, the Mamba-1 scan one
position after another over the whole sequence (``jax.lax.scan`` over
the recurrence as it is written, the state ``[d_inner, N]`` as the
equations print it), the convolution as four shifted products, attention
as one softmax a query over every earlier position with the ONE K/V head
repeated for its 20 query heads, the dense SwiGLU, the tied head.  No
kernel, no cache, no chunks, no pages, no lanes, no state carried in, no
batching, and nothing imported from the program.  It reads the program's
parameter tree (``embed``, ``norm``, ``layers`` of ``norm1, norm2,
w_gate_up, w_down`` and by kind ``in_proj, conv_w, conv_b, x_proj,
dt_norm, b_norm, c_norm, dt_proj, dt_bias, A_log, D, out_proj`` | ``wqkv,
wo``): that tree is the interface.

The model (ai21labs/AI21-Jamba2-3B ``config.json``, ``model_type:
jamba``; keys in brackets).  ``x = E[tok]``; for each layer i

    x = x + Mixer_i(rmsnorm(x, w_in));  x = x + W_down (silu(y W_gate) * (y W_up)),  y = rmsnorm(x, w_ff)

with ``rmsnorm(x, w) = w x rsqrt(mean(x^2) + 1e-6)`` [rms_norm_eps,
intermediate_size 8192, hidden_act silu; num_experts 1: no router], the
mixer attention where ``i % 14 == 7`` [attn_layer_period,
attn_layer_offset] and Mamba elsewhere:

    mamba      [x | z] = y W_in                      5120 | 5120 [mamba_expand 2 x hidden_size 2560; mamba_proj_bias false]
               x_t = silu(b_c + sum_{j=0..3} w_c[:, j] x_{t-3+j})   [mamba_d_conv 4, mamba_conv_bias], zeros before 0
               [d | B | C] = x W_x                   160 | 16 | 16 [mamba_dt_rank, mamba_d_state]
               d, B, C = rmsnorm(d, w_dt), rmsnorm(B, w_B), rmsnorm(C, w_C)
               dt = softplus(d W_dt + b_dt);  A = -exp(A_log)      [5120, 16]
               h_t = exp(dt_t[:, None] A) h_{t-1} + (dt_t x_t)[:, None] B_t[None, :];  o_t = h_t C_t + D x_t
               out = (o * silu(z)) W_out
    attention  q 20 heads of 128, k and v ONE head of 128 = y W_qkv     [num_attention_heads, num_key_value_heads]
               every query head reads the one K/V head; score(t, s<=t) = q.k 128^-0.5; NO rotation, no position
               embedding; out = softmax(score) v W_o

then rmsnorm and the embedding transposed as the head [tie_word_embeddings].

DEPARTURES from the published description, and what is ASSUMED because
the catalog's row of the source does not settle it
(``benchmark/configs/jamba2-3b.json`` lists the same):

- the order of the layer kinds: the row gives none; the published
  ``JambaConfig.layers_block_type`` reads ``attn_layer_period`` and
  ``attn_layer_offset`` as above (layers 7 and 21 of 28 attend);
- ``head_dim = hidden_size / num_attention_heads = 128``: the config
  has no key for it;
- ``expert_layer_period`` and ``expert_layer_offset`` choose among layers
  that all have ONE expert: every layer's second half is the plain MLP;
- parameters bf16 (the row carries no ``torch_dtype``); here they are
  cast to float32, a layer at a time;
- the scan's state is float32 throughout (here everything is); the
  published cache keeps the model's dtype, and the published code runs
  the scan through a fused kernel whose order of summation differs from
  this recurrence's, which is the definition;
- weights seeded random (normal 0.02, every norm weight 1; the
  convolution's weights and bias uniform in +-0.5, ``A_log[c, n] = log(n
  + 1)``, ``b_dt`` the inverse softplus of a log-uniform step in ``[1e-3,
  1e-1]``, ``D = 1``: the Mamba-1 convention; the config has no
  ``time_step`` key).

WRONG ON PURPOSE (``wrong``, a tuple of names, empty in every check that
decides ``correct``): the builder's readings and the tests tell this
reference ANOTHER model, which the program must then be far from:
``no_dt_norm``, ``no_b_norm``, ``no_c_norm`` (an inner norm left out),
``no_dt_bias``, ``a_is_a_log`` (``A = A_log`` without ``-exp``),
``no_conv_bias``, ``no_d`` (``D x`` left out), ``gate_before_scan``
(``x * silu(z)`` goes into the convolution's output's place and the
scan's output is not gated), ``rotation`` (rotary positions, theta
10000, on q and k), ``state_bf16`` (the state rounded to bfloat16 after
every position, as a cache in the model's dtype would hold it).  A wrong
ORDER of layers is told through ``numbers`` and a tree whose mixers are
swapped to match (``benchmark/runners/serve_jamba2.py:wrong_reference``).

One layer's weights are cast to float32 at a time; the projections go a
block of ``ROWS`` positions at a time and attention ``QUERIES`` queries
at a time, so that the reference of a 2k-token sequence fits beside the
engine's weights and cache on the chip (12.1 GB of float32 weights never
stand beside the engine's 9.3 GB).  Only a process that holds the chip
(or a CPU rehearsal) imports this.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROWS = 1024  # positions a block of the projections
QUERIES = 128  # queries a block of attention: their scores over every position are [20, QUERIES, T]
WRONG = ("no_dt_norm", "no_b_norm", "no_c_norm", "no_dt_bias", "a_is_a_log", "no_conv_bias", "no_d",
         "gate_before_scan", "rotation", "state_bf16")


def numbers(cfg) -> dict:
    """What this reference needs of the program's config, by the
    source's key names."""
    return {"rms_norm_eps": float(cfg.layer_norm_epsilon), "num_hidden_layers": cfg.n_layer,
            "attn_layer_period": cfg.attn_layer_period, "attn_layer_offset": cfg.attn_layer_offset,
            "num_attention_heads": cfg.n_head, "num_key_value_heads": cfg.n_kv_head,
            "hidden_size": cfg.d_model, "mamba_d_state": cfg.mamba_d_state, "mamba_dt_rank": cfg.mamba_dt_rank}


def layer_kinds(told: dict) -> list:
    return ["attention" if i % told["attn_layer_period"] == told["attn_layer_offset"] else "mamba"
            for i in range(told["num_hidden_layers"])]


def rmsnorm(x, w, eps):
    return w.astype(F32) * x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)


def by_rows(f, x):
    """f over x [T, ...] a block of ROWS positions at a time."""
    T = x.shape[0]
    pad = -T % ROWS
    xp = jnp.concatenate([x, jnp.zeros((pad, *x.shape[1:]), x.dtype)]) if pad else x
    out = jax.lax.map(f, xp.reshape(-1, ROWS, *x.shape[1:]))
    return jax.tree.map(lambda o: o.reshape(-1, *o.shape[2:])[:T], out)


# ----------------------------------------------------------------------
# mamba
# ----------------------------------------------------------------------
def convolution(x, w, b):
    """x [T, C] -> silu(b + sum_j w[:, j] x_{t-K+1+j}), zeros before
    position 0: four shifted products."""
    T, K = x.shape[0], w.shape[1]
    padded = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), F32), x])
    return jax.nn.silu(b + sum(w[:, j].astype(F32) * padded[j:j + T] for j in range(K)))


def scan(x, dt, A, B, C, round_state=False):
    """The recurrence, a position at a time from a state of zeros.  x,
    dt [T, D] (dt after its softplus), A [D, N], B, C [T, N] -> ``h_t
    C_t`` [T, D]."""

    def step(h, at):
        x_t, dt_t, B_t, C_t = at
        h = jnp.exp(dt_t[:, None] * A) * h + (dt_t * x_t)[:, None] * B_t[None, :]
        if round_state:
            # not a cast there and back, which the TPU's compiler removes as excess precision
            h = jax.lax.reduce_precision(h, exponent_bits=8, mantissa_bits=7)
        return h, h @ C_t

    _, o = jax.lax.scan(step, jnp.zeros(A.shape, F32), (x, dt, B, C))
    return o


def mamba_part(y, lp, c, wrong):
    """The Mamba-1 mixer on normed tokens y [T, d] of one sequence."""
    R, N, eps = c["mamba_dt_rank"], c["mamba_d_state"], c["rms_norm_eps"]
    w_in = lp["in_proj"].astype(F32)
    xz = by_rows(lambda yb: yb @ w_in, y)
    x, z = jnp.split(xz, 2, axis=-1)
    conv_b = 0.0 if "no_conv_bias" in wrong else lp["conv_b"].astype(F32)
    x = convolution(x, lp["conv_w"], conv_b)
    if "gate_before_scan" in wrong:
        x = x * jax.nn.silu(z)
    d, B, C = jnp.split(x @ lp["x_proj"].astype(F32), [R, R + N], axis=-1)
    if "no_dt_norm" not in wrong:
        d = rmsnorm(d, lp["dt_norm"], eps)
    if "no_b_norm" not in wrong:
        B = rmsnorm(B, lp["b_norm"], eps)
    if "no_c_norm" not in wrong:
        C = rmsnorm(C, lp["c_norm"], eps)
    dt = d @ lp["dt_proj"].astype(F32)
    if "no_dt_bias" not in wrong:
        dt = dt + lp["dt_bias"].astype(F32)
    dt = jax.nn.softplus(dt)
    A_log = lp["A_log"].astype(F32)
    o = scan(x, dt, A_log if "a_is_a_log" in wrong else -jnp.exp(A_log), B, C, "state_bf16" in wrong)
    if "no_d" not in wrong:
        o = o + lp["D"].astype(F32) * x
    if "gate_before_scan" not in wrong:
        o = o * jax.nn.silu(z)
    w_out = lp["out_proj"].astype(F32)
    return by_rows(lambda ob: ob @ w_out, o)


# ----------------------------------------------------------------------
# attention
# ----------------------------------------------------------------------
def _rotate(x, pos, theta=10000.0):
    """Rotary positions, half-split: what this model does NOT apply
    (``rotation`` of WRONG)."""
    half = x.shape[-1] // 2
    ang = pos.astype(F32)[:, None, None] * theta ** (-jnp.arange(half, dtype=F32) / half)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def attention_part(y, lp, c, wrong):
    """Attention on normed tokens y [T, d] of one sequence: every query
    over every earlier position, QUERIES queries at a time; the K/V
    heads repeated for their query heads; no rotation."""
    T = y.shape[0]
    Hq, Hk = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["hidden_size"] // Hq
    wqkv = lp["wqkv"].astype(F32)
    qkv = by_rows(lambda yb: yb @ wqkv, y)
    q = qkv[:, :Hq * hd].reshape(T, Hq, hd)
    k = qkv[:, Hq * hd:(Hq + Hk) * hd].reshape(T, Hk, hd)
    v = jnp.repeat(qkv[:, (Hq + Hk) * hd:].reshape(T, Hk, hd), Hq // Hk, axis=1)
    pos = jnp.arange(T)
    if "rotation" in wrong:
        q, k = _rotate(q, pos), _rotate(k, pos)
    k = jnp.repeat(k, Hq // Hk, axis=1)

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
# the model
# ----------------------------------------------------------------------
def mlp_part(y, lp):
    w_gu, w_down = lp["w_gate_up"].astype(F32), lp["w_down"].astype(F32)

    def rows(yb):
        a, b = jnp.split(yb @ w_gu, 2, axis=-1)
        return (jax.nn.silu(a) * b) @ w_down

    return by_rows(rows, y)


@functools.partial(jax.jit, static_argnames=("kind", "told", "wrong"))
def layer(x, lp, *, kind, told, wrong=()):
    """One layer on x [T, d] float32 -> x.  `told`: a tuple of (name,
    value) pairs (``numbers``)."""
    c = dict(told)
    y = rmsnorm(x, lp["norm1"], c["rms_norm_eps"])
    x = x + (mamba_part(y, lp, c, wrong) if kind == "mamba" else attention_part(y, lp, c, wrong))
    return x + mlp_part(rmsnorm(x, lp["norm2"], c["rms_norm_eps"]), lp)


def full_logits(params, tokens, told, positions=None, wrong=()):
    """tokens [T] of ONE sequence -> logits [len(positions), V] float32
    at `positions` (all of them when None).  `told`: ``numbers(cfg)``;
    `wrong`: names of WRONG, none in a check that decides ``correct``."""
    unknown = set(wrong) - set(WRONG)
    if unknown:
        raise ValueError(f"no wrong-on-purpose reading named {sorted(unknown)}")
    pairs = tuple(sorted(told.items()))
    # on the TPU a float32 matmul runs as bf16 passes unless told otherwise
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        for kind, lp in zip(layer_kinds(told), params["layers"]):
            x = layer(x, lp, kind=kind, told=pairs, wrong=tuple(wrong))
        if positions is not None:
            x = x[jnp.asarray(positions)]
        return _head(x, params["norm"], params["embed"], eps=told["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, embed, *, eps):
    # under jit the head's cast to float32 fuses into the matmul
    return rmsnorm(x, norm, eps) @ embed.astype(F32).T

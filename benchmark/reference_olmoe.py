"""The plain reference of the OLMoE family: forward pass in float32
``jax.numpy``, einsum attention with a causal mask, and the experts the
dense way: EVERY expert's output for EVERY token, times that token's
weight for it (zero where the expert is not among its 8), summed.  No
kernel, no cache, no sort, no grouped matmul, and nothing imported from
the program.  It reads the program's parameter tree (``embed``,
``layers`` of ``w_in, wqkv, w_qn, w_kn, wo, w_post, router, wgu, wd``,
``norm``, ``lm_head``): that tree is the interface.

The model as published (allenai/OLMoE-1B-7B-0125-Instruct ``config.json``
and arXiv:2409.02060), a layer on the residual stream x:

    h  = rmsnorm(x, w_in)
    q  = rmsnorm(h Wq, w_qn);  k = rmsnorm(h Wk, w_kn);  v = h Wv
         (the norm over all d columns, before the split into heads)
    q, k rotated (rotate_half convention, theta 10000, a token's index)
    x  = x + causal_attention(q, k, v) Wo
    h2 = rmsnorm(x, w_post)
    p  = softmax(h2 Wr) over the experts; the 8 largest p, NOT
         renormalised (norm_topk_prob false)
    x  = x + sum over those 8 of p_e * (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e

then rmsnorm and the untied head; rmsnorm(x, w) = w * x * rsqrt(mean(x^2)
+ 1e-05).  Departures: none from the mathematics; the weights are
seeded random, as the program's.

The weights stay in the program's dtype; one layer's attention weights,
and within a layer one expert at a time, are cast to float32, so that
the reference fits beside the engine's 10 GB of weights on the chip.
Only a process that holds the chip (or a CPU rehearsal) imports this.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rmsnorm(x, w, eps):
    return w.astype(F32) * x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)


def rope(x, theta):
    """x [B, T, H, Dh] at positions 0..T-1: (x1, x2) -> (x1 cos - x2 sin,
    x2 cos + x1 sin) with x1, x2 the two halves of a head."""
    T, half = x.shape[1], x.shape[-1] // 2
    inv_freq = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None, :]  # [T, half]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def expert_weights(h2, router, top_k, renormalise):
    """[N, E] float32: a token's softmax probability for each of its
    top_k experts, zero for the others."""
    p = jax.nn.softmax(h2 @ router.astype(F32), axis=-1)
    kth = jnp.sort(p, axis=-1)[:, -top_k][:, None]
    w = jnp.where(p >= kth, p, 0.0)
    return w / w.sum(-1, keepdims=True) if renormalise else w


@functools.partial(jax.jit, static_argnames=("n_head", "top_k", "theta", "eps", "renormalise"))
def layer(x, lp, *, n_head, top_k, theta, eps, renormalise):
    """One block on x [B, T, d] float32."""
    B, T, d = x.shape
    h = rmsnorm(x, lp["w_in"], eps)
    q, k, v = jnp.split(h @ lp["wqkv"].astype(F32), 3, axis=-1)
    q, k = rmsnorm(q, lp["w_qn"], eps), rmsnorm(k, lp["w_kn"], eps)
    q, k, v = (t.reshape(B, T, n_head, d // n_head) for t in (q, k, v))
    q, k = rope(q, theta), rope(k, theta)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(d // n_head))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v).reshape(B, T, d)
    x = x + att @ lp["wo"].astype(F32)

    h2 = rmsnorm(x, lp["w_post"], eps).reshape(B * T, d)
    w = expert_weights(h2, lp["router"], top_k, renormalise)

    def one_expert(e, y):
        gate, up = jnp.split(h2 @ lp["wgu"][e].astype(F32), 2, axis=-1)
        return y + w[:, e, None] * ((jax.nn.silu(gate) * up) @ lp["wd"][e].astype(F32))

    y = jax.lax.fori_loop(0, lp["wgu"].shape[0], one_expert, jnp.zeros_like(h2))
    return x + y.reshape(B, T, d)


def full_logits(params, tokens, cfg):
    """[B, T] token ids -> [B, T, vocab] float32 logits of every
    position.  `cfg` gives ``n_head``, ``num_experts_per_tok``,
    ``rope_theta``, ``rms_norm_eps`` and ``norm_topk_prob``."""
    # on the TPU a float32 matmul runs as bf16 passes unless told otherwise
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        for lp in params["layers"]:
            x = layer(x, lp, n_head=cfg.n_head, top_k=cfg.num_experts_per_tok,
                      theta=float(cfg.rope_theta), eps=float(cfg.rms_norm_eps),
                      renormalise=bool(cfg.norm_topk_prob))
        x = rmsnorm(x, params["norm"], float(cfg.rms_norm_eps))
        return x @ params["lm_head"].astype(F32)

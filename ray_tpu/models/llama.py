"""Llama-family decoder in Flax, TPU-first.

Same design rules as models/gpt2.py (bf16 compute / f32 params, static
shapes, fused attention via ops.attention; a mesh layout comes from
``train.sharding`` with partition rules that name q/k/v/o_proj and
gate/up/down_proj):

- RMSNorm (no bias anywhere),
- rotary position embeddings applied to q/k,
- grouped-query attention (n_kv_head < n_head repeats KV per group),
- SwiGLU MLP (gate * silu(up) -> down).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    n_layer: int = 32
    n_head: int = 32
    n_kv_head: int = 8
    d_model: int = 4096
    d_ff: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    mesh: Any = None
    sp_axis: Optional[str] = None

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=512, n_layer=2, n_head=4, n_kv_head=2, d_model=128,
            d_ff=256, max_seq_len=128, remat=False, **kw
        )

    @staticmethod
    def llama_7b(**kw) -> "LlamaConfig":
        return LlamaConfig(**kw)

    @staticmethod
    def llama_1b(**kw) -> "LlamaConfig":
        return LlamaConfig(
            n_layer=16, n_head=16, n_kv_head=8, d_model=2048, d_ff=5504, **kw
        )


class RMSNorm(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), self.cfg.param_dtype)
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        out = x.astype(jnp.float32) * jax.lax.rsqrt(var + self.cfg.rms_eps)
        return (out * scale).astype(self.cfg.dtype)


def rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embeddings over the last dim of [B, T, H, D]."""
    _, T, _, D = x.shape
    half = D // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]  # [T, half]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    )
    return rotated.astype(x.dtype)


class LlamaAttention(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, _ = x.shape
        d_head = cfg.d_model // cfg.n_head
        dense = lambda n, feats: nn.Dense(  # noqa: E731
            feats, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name=n
        )
        q = dense("q_proj", cfg.n_head * d_head)(x).reshape(B, T, cfg.n_head, d_head)
        k = dense("k_proj", cfg.n_kv_head * d_head)(x).reshape(B, T, cfg.n_kv_head, d_head)
        v = dense("v_proj", cfg.n_kv_head * d_head)(x).reshape(B, T, cfg.n_kv_head, d_head)
        q = rope(q, cfg.rope_theta)
        k = rope(k, cfg.rope_theta)
        # GQA: repeat KV heads to match query heads (XLA fuses the
        # broadcast into the attention matmuls).
        rep = cfg.n_head // cfg.n_kv_head
        if rep > 1:
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)

        from ray_tpu.ops.attention import causal_attention

        out = causal_attention(q, k, v, mesh=cfg.mesh, sp_axis=cfg.sp_axis)
        out = out.reshape(B, T, cfg.n_head * d_head)
        return dense("o_proj", cfg.d_model)(out)


class LlamaMLP(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = lambda n, feats: nn.Dense(  # noqa: E731
            feats, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name=n
        )
        return dense("down_proj", cfg.d_model)(
            nn.silu(dense("gate_proj", cfg.d_ff)(x)) * dense("up_proj", cfg.d_ff)(x)
        )


class LlamaBlock(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        x = x + LlamaAttention(self.cfg, name="attn")(RMSNorm(self.cfg, name="ln_attn")(x))
        x = x + LlamaMLP(self.cfg, name="mlp")(RMSNorm(self.cfg, name="ln_mlp")(x))
        return x


class Llama(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        x = nn.Embed(
            cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="token_embed",
        )(tokens)
        block_cls = LlamaBlock
        if cfg.remat:
            block_cls = nn.remat(LlamaBlock, prevent_cse=False)
        for i in range(cfg.n_layer):
            x = block_cls(cfg, name=f"h_{i}")(x)
        x = RMSNorm(cfg, name="ln_f")(x)
        return nn.Dense(
            cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="lm_head",
        )(x)


def init_params(cfg: LlamaConfig, rng=None, batch: int = 2):
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    tokens = jnp.zeros((batch, min(cfg.max_seq_len, 128)), dtype=jnp.int32)
    return Llama(cfg).init(rng, tokens)["params"]


def loss_fn(params, tokens, targets, cfg: LlamaConfig):
    from ray_tpu.models.common import next_token_loss

    return next_token_loss(Llama(cfg).apply({"params": params}, tokens), targets)


def make_train_step(cfg: LlamaConfig, optimizer):
    from ray_tpu.models import common

    return common.make_train_step(loss_fn, cfg, optimizer)


def num_params(params) -> int:
    from ray_tpu.models.common import num_params as _n

    return _n(params)

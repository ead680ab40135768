"""Vision Transformer (reference: the reference trains torchvision/timm
ViTs through its Train library; e.g. release vision benchmarks.
Dosovitskiy et al. 2021).

TPU-first shape: patch embedding is a single strided Conv (one MXU
matmul per patch grid), the encoder reuses full-width bf16 matmuls with
f32 params, and the train step is one jittable function compatible with
`parallel.create_mesh` dp sharding."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class ViTConfig:
    image_size: int = 32
    patch_size: int = 4
    num_classes: int = 10
    d_model: int = 192
    n_layer: int = 6
    n_head: int = 3
    mlp_ratio: int = 4
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @staticmethod
    def tiny(**kw) -> "ViTConfig":
        return ViTConfig(d_model=64, n_layer=2, n_head=2, **kw)

    @property
    def n_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2


class _Block(nn.Module):
    cfg: ViTConfig

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.cfg
        h = nn.LayerNorm(dtype=cfg.dtype)(x)
        h = nn.MultiHeadDotProductAttention(
            num_heads=cfg.n_head,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
        )(h, h)
        x = x + h
        h = nn.LayerNorm(dtype=cfg.dtype)(x)
        h = nn.Dense(cfg.d_model * cfg.mlp_ratio, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype)(h)
        h = nn.gelu(h)
        h = nn.Dense(cfg.d_model, dtype=cfg.dtype, param_dtype=cfg.param_dtype)(h)
        return x + h


class ViT(nn.Module):
    cfg: ViTConfig

    @nn.compact
    def __call__(self, images, deterministic: bool = True):
        cfg = self.cfg
        B = images.shape[0]
        x = images.astype(cfg.dtype)
        # patchify: one strided conv == per-patch linear projection
        x = nn.Conv(
            cfg.d_model,
            (cfg.patch_size, cfg.patch_size),
            strides=(cfg.patch_size, cfg.patch_size),
            padding="VALID",
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="patch_embed",
        )(x)
        x = x.reshape(B, -1, cfg.d_model)  # [B, P, D]
        cls = self.param(
            "cls_token", nn.initializers.zeros, (1, 1, cfg.d_model), cfg.param_dtype
        )
        x = jnp.concatenate([jnp.broadcast_to(cls, (B, 1, cfg.d_model)).astype(cfg.dtype), x], axis=1)
        pos = self.param(
            "pos_embed",
            nn.initializers.normal(0.02),
            (1, cfg.n_patches + 1, cfg.d_model),
            cfg.param_dtype,
        )
        x = x + pos.astype(cfg.dtype)
        for i in range(cfg.n_layer):
            x = _Block(cfg, name=f"block_{i}")(x, deterministic)
        x = nn.LayerNorm(dtype=cfg.dtype)(x)
        return nn.Dense(
            cfg.num_classes, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="head"
        )(x[:, 0])  # classify from the CLS token


def init_params(cfg: ViTConfig, rng=None):
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    x = jnp.zeros((1, cfg.image_size, cfg.image_size, 3), jnp.float32)
    return ViT(cfg).init(rng, x)["params"]


def loss_fn(params, images, labels, cfg: ViTConfig):
    logits = ViT(cfg).apply({"params": params}, images)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    onehot = jax.nn.one_hot(labels, cfg.num_classes)
    return -(onehot * logp).sum(-1).mean()


def make_train_step(cfg: ViTConfig, optimizer):
    """(params, opt_state, images, labels) -> (params, opt_state, loss);
    jit at the call site (optionally over a dp mesh)."""

    def step(params, opt_state, images, labels):
        loss, grads = jax.value_and_grad(loss_fn)(params, images, labels, cfg)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(lambda p, u: p + u, params, updates)
        return params, opt_state, loss

    return step

"""What the latent-attention families share (``models/mistral4.py``,
``models/glm_moe_dsa.py``): a layer caches ONE row ``[c | k_r]`` a
position for all its heads, and reads it two ways.

- ``rope_interleaved``: the rotation of ``q_rope`` and of the shared
  key over pairs ``(2i, 2i + 1)``, at frequencies the family gives.
- ``expanded_attention``: a prompt chunk's queries over the sequence's
  rows, ``k_nope`` and ``v`` EXPANDED from the rows a block of keys at a
  time inside an online softmax; blocks wholly above the diagonal are
  not visited.  A family that attends a CHOICE of the positions hands it
  ``keep_of`` (``ops/dsa.py``): unchosen positions score ``-1e30``.
  Without a choice, on a TPU, the loop is one kernel
  (``ops/pallas_mla_chunk_attention.py``): a block is expanded and
  attended in VMEM.
- ``absorbed_queries``: a decode step's queries against the rows as they
  lie, ``q_nope W_uk[i]^T`` beside the rotated part, for the paged
  kernels of ``ops/attention.py``.

A config here is any object with ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``kv_lora_rank``, ``v_head_dim`` and
``latent_row`` (the columns of a stored row: whole lane tiles).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

K_BLOCK = 512  # keys a block of the prefill's online softmax
Q_BLOCK = 1024  # queries a block of it: scores are [H, Q_BLOCK, K_BLOCK] float32
NEG = -1e30


def rope_interleaved(x, pos, inv_freq, factor=1.0):
    """x [..., D] rotated at positions pos (broadcast against x's
    leading dims) over interleaved pairs (2i, 2i + 1); ``inv_freq`` the
    ``D / 2`` frequencies as Python floats, cos and sin times
    ``factor``."""
    ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def expanded_attention(q_nope, q_rope, ctx, wukv, start, n_valid, cfg, keep_of=None, q_block=Q_BLOCK):
    """The prefill path: queries [T, H, .] (scaled) of the positions
    ``start ..`` over the cached rows ``ctx [C, latent_row]`` (position
    p in row p; whole key blocks), keys and values expanded from the
    rows a block at a time inside an online softmax.  A block of keys
    past a query block's last position, or past the last real position,
    is not visited.  ``keep_of(first, n)``: [n, C] bool, the positions
    the chunk's queries ``first .. first + n - 1`` attend (None: every
    earlier one).  -> [T, H * v_head_dim].

    On a TPU, with no ``keep_of`` and where the widths fit its tiling,
    the Pallas kernel expands and attends a block in VMEM, in tiles of
    its own (ops.pallas_mla_chunk_attention).  Elsewhere the loop below."""
    T, H = q_nope.shape[:2]
    nope, rope, kv, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank, cfg.v_head_dim
    tq = min(T, q_block)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    if keep_of is None and jax.default_backend() == "tpu":  # as the paged kernels of ops/attention.py
        from ray_tpu.ops import pallas_mla_chunk_attention as kernel

        if kernel.kernel_takes(nope, rope, kv, dv, ctx.shape[1], q.dtype):
            return kernel.mla_chunk_attention_kernel(q, ctx, wukv, start, n_valid, nope=nope, rope=rope, kv=kv, dv=dv)
    outs = []
    for first in range(0, T, tq):
        qb = q[first:first + tq]
        q_pos = start + first + jnp.arange(tq)
        # keys up to this block's last query, and no further than the last real position
        seen = jnp.minimum(start + first + tq, start + n_valid)
        blocks = jnp.where(first < n_valid, -(-seen // K_BLOCK), 0)
        keep = None if keep_of is None else keep_of(first, tq)

        def body(j, carry, qb=qb, q_pos=q_pos, keep=keep):
            m, l, acc = carry
            rows = jax.lax.dynamic_slice_in_dim(ctx, j * K_BLOCK, K_BLOCK)
            with jax.named_scope("mla.expand"):
                knv = (rows[:, :kv] @ wukv).reshape(K_BLOCK, H, nope + dv)
                k_r = jnp.broadcast_to(rows[:, None, kv:kv + rope], (K_BLOCK, H, rope))
                k = jnp.concatenate([knv[..., :nope], k_r], axis=-1)
            with jax.named_scope("mla.attend"):
                s = jnp.einsum("thd,khd->htk", qb, k, preferred_element_type=jnp.float32)
                k_pos = j * K_BLOCK + jnp.arange(K_BLOCK)
                seen = k_pos[None, None, :] <= q_pos[None, :, None]
                if keep is not None:
                    seen = seen & jax.lax.dynamic_slice_in_dim(keep, j * K_BLOCK, K_BLOCK, axis=1)[None]
                s = jnp.where(seen, s, NEG)
                m_new = jnp.maximum(m, s.max(-1))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new[..., None])
                if keep is not None:
                    # a block none of whose positions a query keeps leaves m at NEG and p at 1
                    p = jnp.where(seen, p, 0.0)
                l = alpha * l + p.sum(-1)
                acc = alpha[..., None] * acc + jnp.einsum(
                    "htk,khd->htd", p.astype(qb.dtype), knv[..., nope:], preferred_element_type=jnp.float32)
            return m_new, l, acc

        init = (jnp.full((H, tq), NEG, jnp.float32), jnp.zeros((H, tq), jnp.float32),
                jnp.zeros((H, tq, dv), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, blocks, body, init)
        # a block of pads alone visited nothing: l is 0 there, and its rows are dropped
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        outs.append(o.transpose(1, 0, 2).reshape(tq, H * dv).astype(qb.dtype))
    return jnp.concatenate(outs) if len(outs) > 1 else outs[0]


def absorbed_queries(q_nope, q_rope, wukv, cfg):
    """``mla.absorb``: [B, H, latent_row] queries against latent rows:
    ``q_nope W_uk[i]^T`` (the latent's ``kv_lora_rank`` columns), the
    rotated part, zeros."""
    B, H = q_nope.shape[:2]
    nope, kv = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    w_uk = wukv.reshape(kv, H, nope + cfg.v_head_dim)[..., :nope]
    q_lat = jnp.einsum("bhd,chd->bhc", q_nope, w_uk)
    pad = jnp.zeros((B, H, cfg.latent_row - kv - cfg.qk_rope_head_dim), q_lat.dtype)
    return jnp.concatenate([q_lat, q_rope, pad], axis=-1)

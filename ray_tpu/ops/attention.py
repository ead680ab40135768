"""Causal attention dispatch.

One entry point for all models: picks the best implementation for the
placement —

- sequence sharded over an "sp" mesh axis → ring attention
  (ops.ring_attention, shard_map + ppermute over the ICI ring);
- single-device / GSPMD-sharded → Pallas flash kernel on TPU when shapes
  allow (ops.pallas_attention; under a mesh, per shard of batch and
  heads inside a shard_map), else the XLA einsum reference (which XLA
  fuses well on its own).

All paths: f32 accumulation, bf16 in/out, static shapes.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def reference_causal_attention(q, k, v):
    """[B, T, H, D] einsum attention with causal mask; f32 softmax."""
    B, T, H, D = q.shape
    scale = 1.0 / (D**0.5)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    qi = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    mask = (ki <= qi)[None, None, :, :]
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def causal_attention(q, k, v, *, mesh=None, sp_axis: Optional[str] = None):
    """Main entry: [B, T, H, D] → [B, T, H, D], causal.

    When `mesh` has a >1 `sp_axis`, T is assumed sharded over it and ring
    attention runs over that axis (other mesh axes stay under GSPMD).
    """
    if mesh is not None and sp_axis and mesh.shape.get(sp_axis, 1) > 1:
        from ray_tpu.ops.ring_attention import ring_causal_attention

        return ring_causal_attention(q, k, v, mesh=mesh, axis=sp_axis)
    if _use_pallas(q):
        return _flash_over_mesh(q, k, v)
    return reference_causal_attention(q, k, v)


# Mesh axes over which the two rule tables shard attention heads
# (train/sharding/rules.py "model", parallel/sharding.py "tp").  Every
# other axis of a mesh carries batch.
_HEAD_AXES = ("model", "tp")


def _flash_over_mesh(q, k, v):
    """The flash kernel under the mesh this step is traced in.

    A Mosaic kernel cannot be partitioned automatically: a multi-device
    jit that reaches it bare fails to lower.  Attention is independent
    across batch and heads, so under an ambient mesh
    (``jax.set_mesh``, which the sharded train steps enter) the kernel
    runs inside a shard_map, each device on its own shard of B and H
    with T and D whole.  The axis names only decide which dim a mesh
    axis splits; whatever layout the operands arrive in, the partitioner
    reshards to the specs given here, so a wrong guess costs a transfer,
    never a result.  Axes that do not divide their dim stay unsplit."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.ops.pallas_attention import flash_attention

    mesh = jax.sharding.get_abstract_mesh()
    free = [a for a in mesh.axis_names if a not in mesh.manual_axes and mesh.shape[a] > 1]
    if not free:
        return flash_attention(q, k, v, causal=True)
    B, T, H, D = q.shape

    def dividing(axes, n):
        kept, size = [], 1
        for a in axes:
            if n % (size * mesh.shape[a]) == 0:
                kept.append(a)
                size *= mesh.shape[a]
        return tuple(kept) or None

    spec = P(
        dividing([a for a in free if a not in _HEAD_AXES], B), None,
        dividing([a for a in free if a in _HEAD_AXES], H), None,
    )
    return jax.shard_map(
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False,
    )(q, k, v)


def _use_pallas(q) -> bool:
    import os

    if os.environ.get("RAY_TPU_DISABLE_PALLAS"):
        return False
    # The CPU tests take the einsum path; a backend that fails to
    # initialise raises here rather than quietly dropping the kernel.
    if jax.default_backend() != "tpu":
        return False
    B, T, H, D = q.shape
    # Tuned for the MXU: D a multiple of 64 (64/128 head dims), T a
    # multiple of the 256-wide q/k blocks.
    return T >= 256 and T % 256 == 0 and D % 64 == 0 and D <= 256

"""Causal attention dispatch.

One entry point for all models: picks the best implementation for the
placement —

- sequence sharded over a mesh axis (`sp_axis`) → ring attention
  (ops.ring_attention, shard_map + ppermute over the ICI ring);
- single-device / GSPMD-sharded → Pallas flash kernel on TPU when shapes
  allow (ops.pallas_attention; under a mesh, per shard of batch and
  heads inside a shard_map), else the XLA einsum reference (which XLA
  fuses well on its own).

Decode (one fed token a lane): over a contiguous context the einsum
reference.  Over a paged pool, on a TPU where a kernel takes the shapes,
one of four Pallas kernels that read the pages where they lie, else a
gather of the pages and the same reference: every head its own K and V
(ops.pallas_paged_attention); CHOSEN blocks of the pool with grouped
queries (ops.pallas_sparse_paged_attention); a pool of latent rows that
all heads share, keys and values both (ops.pallas_mla_paged_attention);
the whole of a lane's pages with grouped queries
(ops.pallas_gqa_paged_attention).  The four share the walk over the
pages (ops.paged_walk) and differ in the block's arithmetic.  Under a
learned token-level index (ops.dsa) two more on the same walk: the index
scores of a lane's queries against the index keys in its pages
(ops.pallas_dsa), and the latent kernel again with the scores masked by
a lane's CHOICE of positions (a pool's single row is not a copy Mosaic
takes, so every page a lane holds is still read).

A prompt chunk of the grouped-query families attends over a contiguous
context built from the pages (``chunk_attention``: an online softmax a
block of ``K_BLOCK`` keys at a time, plain XLA).

All paths: f32 accumulation, bf16 in/out, static shapes.
"""

from __future__ import annotations

import functools
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


def reference_decode_attention(q, k_self, v_self, k_ctx, v_ctx, ctx_mask):
    """One fed token a lane over a contiguous context and itself.

    q, k_self, v_self [B, H, Dh]; k_ctx, v_ctx [B, C, H, Dh]; ctx_mask
    [B, C] marks the cached positions.  Returns [B, H, Dh]; f32 softmax."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s_ctx = jnp.einsum("bhd,bchd->bhc", q, k_ctx).astype(jnp.float32) * scale
    s_ctx = jnp.where(ctx_mask[:, None, :], s_ctx, jnp.float32(-1e30))
    s_self = (q * k_self).sum(-1).astype(jnp.float32)[..., None] * scale  # [B, H, 1]
    probs = jax.nn.softmax(jnp.concatenate([s_ctx, s_self], axis=-1), axis=-1)
    probs = probs.astype(q.dtype)
    att = jnp.einsum("bhc,bchd->bhd", probs[..., :-1], v_ctx)
    return att + probs[..., -1:] * v_self


def paged_decode_attention(q, k_self, v_self, k_pages, v_pages, layer,
                           block_tables, lengths, *, block_size):
    """One fed token a lane over the pages it holds of a paged KV pool,
    layer ``layer``, and itself (its key and value are not in the pool
    yet).

    q, k_self, v_self [B, H, Dh]; k_pages, v_pages [L, num_blocks *
    block_size, H * Dh]; block_tables [B, pages] int32, the physical
    block of each logical page, scratch block 0 where a lane holds none;
    lengths [B] int32, the cached positions of a lane (0: it attends to
    itself alone).  Returns [B, H, Dh].

    On a TPU, where the shapes fit its tiling, the Pallas kernel reads
    the pages where they lie (ops.pallas_paged_attention).  Elsewhere
    the lane's pages are gathered to a contiguous context first."""
    B, H, Dh = q.shape
    if jax.default_backend() == "tpu":  # as _use_pallas: the CPU tests gather
        from ray_tpu.ops import pallas_paged_attention as kernel

        if kernel.kernel_takes(H, Dh, block_size, k_pages.dtype):
            return kernel.paged_decode_attention_kernel(
                q, k_self, v_self, k_pages, v_pages, layer, block_tables, lengths,
                block_size=block_size,
            )
    C = block_tables.shape[1] * block_size
    idx = (block_tables[:, :, None] * block_size + jnp.arange(block_size)).reshape(B, C)
    k_ctx = k_pages[layer][idx].reshape(B, C, H, Dh)
    v_ctx = v_pages[layer][idx].reshape(B, C, H, Dh)
    mask = jnp.arange(C)[None, :] < lengths[:, None]
    return reference_decode_attention(q, k_self, v_self, k_ctx, v_ctx, mask)


def sparse_paged_decode_attention(q, k_self, v_self, k_pages, v_pages, layer, chosen_pages,
                                  chosen_blocks, counts, lengths, *, block_size, sparse_block):
    """One fed token a lane over CHOSEN blocks of a paged KV pool, layer
    ``layer``, and itself; grouped queries.

    q [B, G, R, Dh]: R query heads to each of the G K/V heads; k_self,
    v_self [B, G, Dh]; k_pages, v_pages [L, num_blocks * block_size,
    G * Dh]; chosen_blocks [B, G, S] int32 the numbers of the blocks
    (``sparse_block`` positions each) a (lane, K/V head) pair reads, of
    which the first counts [B, G] are real; chosen_pages [B, G, S *
    sparse_block / block_size] the physical pages of those blocks, in
    order; lengths [B] the cached positions of a lane.  A chosen block
    is read up to the lane's length.  Returns [B, G, R, Dh].

    On a TPU, where the shapes fit its tiling, the Pallas kernel copies
    the chosen pages alone (ops.pallas_sparse_paged_attention).
    Elsewhere they are gathered first."""
    B, G, R, Dh = q.shape
    if jax.default_backend() == "tpu":  # as paged_decode_attention: the CPU tests gather
        from ray_tpu.ops import pallas_sparse_paged_attention as kernel

        if kernel.kernel_takes(R, Dh, block_size, sparse_block, k_pages.dtype):
            return kernel.sparse_paged_decode_attention_kernel(
                q, k_self, v_self, k_pages, v_pages, layer, chosen_pages, chosen_blocks, counts,
                lengths, block_size=block_size, sparse_block=sparse_block,
            )
    S = chosen_blocks.shape[-1]
    rows = (chosen_pages[..., None] * block_size + jnp.arange(block_size)).reshape(B, G, S * sparse_block)
    heads = jnp.arange(G)[None, :, None]

    def of_pair(pages):  # [B, G, C, G, Dh] -> a pair's own K/V head [B, G, C, Dh]
        x = pages[layer][rows].reshape(B, G, S * sparse_block, G, Dh)
        return jnp.take_along_axis(x, heads[..., None, None], axis=3)[:, :, :, 0]

    k_ctx, v_ctx = of_pair(k_pages), of_pair(v_pages)
    pos = (chosen_blocks[..., None] * sparse_block + jnp.arange(sparse_block)).reshape(B, G, -1)
    mask = (jnp.arange(S * sparse_block) < (counts * sparse_block)[..., None]) & (
        pos < lengths[:, None, None])
    scale = 1.0 / (Dh ** 0.5)
    s_ctx = jnp.einsum("bgrd,bgcd->bgrc", q, k_ctx).astype(jnp.float32) * scale
    s_ctx = jnp.where(mask[:, :, None, :], s_ctx, jnp.float32(-1e30))
    s_self = (q * k_self[:, :, None]).sum(-1).astype(jnp.float32)[..., None] * scale
    probs = jax.nn.softmax(jnp.concatenate([s_ctx, s_self], axis=-1), axis=-1).astype(q.dtype)
    att = jnp.einsum("bgrc,bgcd->bgrd", probs[..., :-1], v_ctx)
    return att + probs[..., -1:] * v_self[:, :, None]


def mla_paged_decode_attention(q, row_self, pages, layer, block_tables, lengths, *, block_size,
                               v_width):
    """One fed token a lane over the LATENT rows it holds of a paged
    pool, layer ``layer``, and its own row (not in the pool yet): every
    head attends the same rows, keys a row's W columns, values its first
    ``v_width`` (multi-query over a compressed cache; the caller absorbs
    the up-projections into q and out of the result).

    q [B, H, W], every scale already applied; row_self [B, W]; pages
    [L, num_blocks * block_size, W]; block_tables [B, pages] int32,
    scratch block 0 where a lane holds none; lengths [B] int32 the
    cached positions of a lane (0: it attends to itself alone).
    Returns [B, H, v_width].

    On a TPU, where the shapes fit its tiling, the Pallas kernel reads
    the pages where they lie (ops.pallas_mla_paged_attention).
    Elsewhere the lane's rows are gathered to a contiguous context."""
    B, H, W = q.shape
    if jax.default_backend() == "tpu":  # as paged_decode_attention: the CPU tests gather
        from ray_tpu.ops import pallas_mla_paged_attention as kernel

        if kernel.kernel_takes(H, W, v_width, block_size, pages.dtype):
            n = kernel.lanes_a_call(B, H, W, v_width, pages.dtype)  # B where the operands fit one call's VMEM

            def lanes(x, at):
                return x if n == B else x[at:at + n]

            out = [kernel.mla_paged_decode_attention_kernel(
                lanes(q, at), lanes(row_self, at), pages, layer, lanes(block_tables, at), lanes(lengths, at),
                block_size=block_size, v_width=v_width) for at in range(0, B, n)]
            return out[0] if n == B else jnp.concatenate(out)
    C = block_tables.shape[1] * block_size
    idx = (block_tables[:, :, None] * block_size + jnp.arange(block_size)).reshape(B, C)
    ctx = pages[layer][idx]  # [B, C, W]
    s_ctx = jnp.einsum("bhw,bcw->bhc", q, ctx).astype(jnp.float32)
    s_ctx = jnp.where((jnp.arange(C)[None, :] < lengths[:, None])[:, None, :], s_ctx, jnp.float32(-1e30))
    s_self = (q * row_self[:, None]).sum(-1).astype(jnp.float32)[..., None]
    probs = jax.nn.softmax(jnp.concatenate([s_ctx, s_self], axis=-1), axis=-1).astype(q.dtype)
    att = jnp.einsum("bhc,bcv->bhv", probs[..., :-1], ctx[..., :v_width])
    return att + probs[..., -1:] * row_self[:, None, :v_width]


def dsa_index_paged_scores(q_i, w, k_self, pages, layer, block_tables, lengths, *, block_size):
    """The index scores (``ops.dsa.index_scores``) of one fed token a
    lane against the index keys in the pages the lane holds of a paged
    pool, layer ``layer``, and against its own key (not in the pool yet).

    q_i [B, Hi, Di] the index queries, w [B, Hi] float32 the heads'
    weights, k_self [B, Di]; pages [L, num_blocks * block_size, Di];
    block_tables [B, pages] int32, scratch block 0 where a lane holds
    none; lengths [B] int32 the cached positions of a lane, under
    ``pages * block_size``.  Returns [B, pages * block_size] float32: a
    cached position's score in its column, the fed token's own in column
    ``lengths``, -1e30 past it.

    On a TPU, where the shapes fit its tiling, the Pallas kernel reads
    the keys where they lie (ops.pallas_dsa).  Elsewhere the lane's keys
    are gathered to a contiguous context."""
    from ray_tpu.ops import dsa

    B, Hi, Di = q_i.shape
    C = block_tables.shape[1] * block_size
    cached = None
    if jax.default_backend() == "tpu":  # as paged_decode_attention: the CPU tests gather
        from ray_tpu.ops import pallas_dsa as kernel

        if kernel.index_kernel_takes(B, Hi, Di, block_size, block_tables.shape[1], pages.dtype):
            cached = kernel.dsa_index_paged_scores_kernel(q_i, w, pages, layer, block_tables, lengths,
                                                          block_size=block_size)
    if cached is None:
        idx = (block_tables[:, :, None] * block_size + jnp.arange(block_size)).reshape(B, C)
        L, P, _ = pages.shape
        ctx = pages.reshape(L * P, Di)[layer * P + idx]  # [B, C, Di]
        cached = jax.vmap(lambda q, ws, keys: dsa.index_scores(q[None], ws[None], keys)[0])(q_i, w, ctx)
    own = jax.vmap(lambda q, ws, key: dsa.index_scores(q[None], ws[None], key[None])[0, 0])(q_i, w, k_self)
    pos = jnp.arange(C)[None, :]
    return jnp.where(pos < lengths[:, None], cached, jnp.where(pos == lengths[:, None], own[:, None], -1e30))


def mla_sparse_paged_decode_attention(q, row_self, pages, layer, block_tables, keep, lengths, *, block_size,
                                      v_width):
    """One fed token a lane over the CHOSEN latent rows of a paged pool,
    layer ``layer``: as ``mla_paged_decode_attention``, but a lane
    attends the positions ``keep [B, pages * block_size]`` bool marks
    alone (``ops.dsa.keep_mask`` over the cached positions and, in column
    ``lengths``, the fed token's own, whose row ``row_self`` is not in
    the pool yet; where the choice left it out it is not attended).
    Returns [B, H, v_width].

    On a TPU, where the shapes fit its tiling, the Pallas kernel walks
    the lane's pages where they lie and masks the scores by the choice
    (ops.pallas_mla_paged_attention: a pool's single row is not a copy
    Mosaic takes, so every page is read).  Elsewhere the lane's rows are
    gathered to a contiguous context."""
    B, H, W = q.shape
    C = block_tables.shape[1] * block_size
    pos = jnp.arange(C)[None, :]
    own_kept = (keep & (pos == lengths[:, None])).any(-1)
    cached = keep & (pos < lengths[:, None])
    if jax.default_backend() == "tpu":  # as paged_decode_attention: the CPU tests gather
        from ray_tpu.ops import pallas_mla_paged_attention as kernel

        if kernel.sparse_kernel_takes(B, H, W, v_width, block_size, block_tables.shape[1], pages.dtype):
            return kernel.mla_sparse_paged_decode_attention_kernel(
                q, row_self, cached, own_kept, pages, layer, block_tables, lengths, block_size=block_size,
                v_width=v_width)
    idx = (block_tables[:, :, None] * block_size + jnp.arange(block_size)).reshape(B, C)
    L, P, _ = pages.shape
    ctx = pages.reshape(L * P, W)[layer * P + idx]  # [B, C, W]
    s_ctx = jnp.einsum("bhw,bcw->bhc", q, ctx).astype(jnp.float32)
    s_ctx = jnp.where(cached[:, None, :], s_ctx, jnp.float32(-1e30))
    s_self = (q * row_self[:, None]).sum(-1).astype(jnp.float32)[..., None]
    s_self = jnp.where(own_kept[:, None, None], s_self, jnp.float32(-1e30))
    probs = jax.nn.softmax(jnp.concatenate([s_ctx, s_self], axis=-1), axis=-1).astype(q.dtype)
    att = jnp.einsum("bhc,bcv->bhv", probs[..., :-1], ctx[..., :v_width])
    return att + probs[..., -1:] * row_self[:, None, :v_width]

def gqa_decode_blocks(k_pages, lengths, block_size, calls=1):
    """What ``calls`` calls of ``gqa_paged_decode_attention`` over lanes
    of ``lengths`` cached positions walk: int32 [2], the kernel's
    compute blocks and those of them that hold all their pages
    (``paged_walk.blocks`` at the kernel's block for this pool), for a
    family's counters ``kv_blocks_walked`` and ``kv_blocks_whole``."""
    from ray_tpu.ops import paged_walk
    from ray_tpu.ops import pallas_gqa_paged_attention as kernel

    return jnp.stack(paged_walk.blocks(lengths, block_size, kernel.block_positions(k_pages))) * calls


def gqa_paged_decode_attention(q, k_self, v_self, k_pages, v_pages, layer, block_tables, lengths, *,
                               block_size, scale=None):
    """One fed token a lane over the pages it holds of a paged KV pool,
    layer ``layer``, and itself; grouped queries, every cached position
    attended.

    q [B, G, R, Dh]: R query heads to each of the G K/V heads; k_self,
    v_self [B, G, Dh]; k_pages, v_pages [L, num_blocks * block_size,
    G * Dh]; block_tables [B, pages] int32, scratch block 0 where a lane
    holds none; lengths [B] int32 the cached positions of a lane (0: it
    attends to itself alone); scale, static, multiplies the scores
    (None: ``Dh ** -0.5``).  Returns [B, G, R, Dh].

    On a TPU, where the shapes fit its tiling (whole lane tiles a head,
    whole sublane tiles a page; any R), the Pallas kernel reads the pages where they lie, a
    page once for its group's R heads (ops.pallas_gqa_paged_attention).
    Elsewhere the lane's pages are gathered to a contiguous context
    first."""
    B, G, R, Dh = q.shape
    if jax.default_backend() == "tpu":  # as paged_decode_attention: the CPU tests gather
        from ray_tpu.ops import pallas_gqa_paged_attention as kernel

        if kernel.kernel_takes(R, Dh, block_size, k_pages.dtype):
            return kernel.gqa_paged_decode_attention_kernel(
                q, k_self, v_self, k_pages, v_pages, layer, block_tables, lengths, block_size=block_size,
                scale=scale)
    if scale is not None:  # the reference scales by Dh ** -0.5
        q = q * (scale * Dh ** 0.5)
    C = block_tables.shape[1] * block_size
    idx = (block_tables[:, :, None] * block_size + jnp.arange(block_size)).reshape(B, C)
    # every K/V head repeated for its R query heads: the multi-head reference
    k_ctx = jnp.repeat(k_pages[layer][idx].reshape(B, C, G, Dh), R, axis=2)
    v_ctx = jnp.repeat(v_pages[layer][idx].reshape(B, C, G, Dh), R, axis=2)
    mask = jnp.arange(C)[None, :] < lengths[:, None]
    out = reference_decode_attention(q.reshape(B, G * R, Dh), jnp.repeat(k_self, R, axis=1),
                                     jnp.repeat(v_self, R, axis=1), k_ctx, v_ctx, mask)
    return out.reshape(B, G, R, Dh)


K_BLOCK = 512  # keys a block of ``chunk_attention``'s online softmax: its context is whole blocks of it
Q_BLOCK = 512  # queries a block of it: scores are [G, R, Q_BLOCK, K_BLOCK] float32
_NEG = -1e30


def chunk_attention(q, ctx_k, ctx_v, start, n_valid, scale=None):
    """The prefill twin of ``gqa_paged_decode_attention``: a chunk's
    queries [T, G, R, hd] of the positions ``start ..`` over the cached
    rows ``ctx_k``, ``ctx_v`` [C, G, hd] (position p in row p; whole key
    blocks), a block of keys at a time inside an online softmax.  A
    block of keys past a query block's last position, or past the last
    real position, is not visited.  `scale` multiplies the scores (None:
    ``hd ** -0.5``).  -> [T, G * R * hd]."""
    T, G, R, hd = q.shape
    tq = min(T, Q_BLOCK)
    scale = hd ** -0.5 if scale is None else scale
    outs = []
    for first in range(0, T, tq):
        qb = q[first:first + tq]
        q_pos = start + first + jnp.arange(tq)
        seen = jnp.minimum(start + first + tq, start + n_valid)
        blocks = jnp.where(first < n_valid, -(-seen // K_BLOCK), 0)

        def body(j, carry, qb=qb, q_pos=q_pos):
            m, l, acc = carry
            k = jax.lax.dynamic_slice_in_dim(ctx_k, j * K_BLOCK, K_BLOCK)
            v = jax.lax.dynamic_slice_in_dim(ctx_v, j * K_BLOCK, K_BLOCK)
            s = jnp.einsum("tgrd,kgd->grtk", qb, k, preferred_element_type=jnp.float32) * scale
            k_pos = j * K_BLOCK + jnp.arange(K_BLOCK)
            s = jnp.where(k_pos[None, None, None, :] <= q_pos[None, None, :, None], s, _NEG)
            m_new = jnp.maximum(m, s.max(-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l = alpha * l + p.sum(-1)
            acc = alpha[..., None] * acc + jnp.einsum(
                "grtk,kgd->grtd", p.astype(qb.dtype), v, preferred_element_type=jnp.float32)
            return m_new, l, acc

        init = (jnp.full((G, R, tq), _NEG, jnp.float32), jnp.zeros((G, R, tq), jnp.float32),
                jnp.zeros((G, R, tq, hd), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, blocks, body, init)
        # a block of pads alone visited nothing: l is 0 there, and its rows are dropped
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        outs.append(o.transpose(2, 0, 1, 3).reshape(tq, G * R * hd).astype(qb.dtype))
    return jnp.concatenate(outs) if len(outs) > 1 else outs[0]


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


# The mesh axis over which the partition rules shard attention heads
# (train/sharding/rules.py).  Every other axis of a mesh carries batch.
_HEAD_AXIS = "model"


def mesh_split(B, H, skip=()):
    """How the ambient mesh (``jax.set_mesh``, which the sharded train
    steps enter) splits a batch of B and H heads: (mesh, batch axes,
    head axes), each a tuple of axis names or None, or None outright
    where the mesh has no free axis wider than one.  ``_HEAD_AXIS``
    takes the heads and every other free axis the batch, but for those
    in ``skip``; an axis that does not divide its dim stays unsplit, one
    that a surrounding shard_map has made manual is not free.  One
    answer for every shard_map of the attention block, so that what one
    emits is what the next asks for."""
    mesh = jax.sharding.get_abstract_mesh()
    free = [a for a in mesh.axis_names
            if a not in mesh.manual_axes and a not in skip and mesh.shape[a] > 1]
    if not free:
        return None

    def dividing(axes, n):
        kept, size = [], 1
        for a in axes:
            if n % (size * mesh.shape[a]) == 0:
                kept.append(a)
                size *= mesh.shape[a]
        return tuple(kept) or None

    return (mesh, dividing([a for a in free if a != _HEAD_AXIS], B),
            dividing([a for a in free if a == _HEAD_AXIS], H))


def _flash_over_mesh(q, k, v):
    """The flash kernel under the mesh this step is traced in.

    A Mosaic kernel cannot be partitioned automatically: a multi-device
    jit that reaches it bare fails to lower.  Attention is independent
    across batch and heads, so under an ambient mesh the kernel runs
    inside a shard_map, each device on its own shard of B and H
    (``mesh_split``) with T and D whole.  The axis names only decide
    which dim a mesh axis splits; whatever layout the operands arrive
    in, the partitioner reshards to the specs given here, so a wrong
    guess costs a transfer, never a result."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.ops.pallas_attention import flash_attention

    B, T, H, D = q.shape
    split = mesh_split(B, H)
    if split is None:
        return flash_attention(q, k, v, causal=True)
    mesh, batch_axes, head_axes = split
    return _flash_shard_map(q, k, v, mesh=mesh, spec=P(batch_axes, None, head_axes, None))


@functools.partial(jax.jit, static_argnames=("mesh", "spec"))
def _flash_shard_map(q, k, v, *, mesh, spec):
    # a jit of its own, so that the layers of a model trace and lower
    # the kernels once and not once each (the step's set-up, not its
    # program: the calls are inlined when it is compiled)
    from ray_tpu.ops.pallas_attention import flash_attention

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
    # D a multiple of 64 (64/128 head dims: a lane tile or half of one);
    # T a multiple of 256, the chunk the kernels walk a block in
    # (pallas_attention.flash_tiles: any such T gets a block and a chunk).
    return T >= 256 and T % 256 == 0 and D % 64 == 0 and D <= 256

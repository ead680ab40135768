"""A learned token-level index over a latent cache (``model_type:
glm_moe_dsa``; ``models/glm_moe_dsa.py``), in plain ``jax.numpy``: a
small network of its own scores every earlier POSITION for a query, the
``index_topk`` largest are kept, and the layer's attention reads those
rows alone.

- ``index_scores``: ``I(t, s) = sum_j w_j(t) relu(q_I,j(t) . k_I(s))``
  of queries against index keys, float32.  ``chunk_index_scores`` takes
  a tile of a prompt chunk's queries over the sequence's keys a block at
  a time: the scores a head ``[n, 32, C]`` are never whole.
- the EXACT choice of the ``k`` largest a query, a tie at the k-th going
  to the lower position: ``kth_largest`` finds the k-th largest score by
  counting, four bits of the scores' bit pattern a pass (eight passes of
  fifteen comparisons an element; a sort a query is what PR 46 found
  fragile at 2k blocks).  ``keep_mask`` gives the choice as a mask a
  query, to a chunk and to a decode step alike.
- ``sparse_chunk_attention``: a prompt chunk over the sequence's latent
  rows under that choice: ``ops.mla.expanded_attention`` with the mask
  (every key block up to the diagonal is visited, an unchosen position
  scores -1e30: exact), the index scores and the choice a tile of
  ``Q_TILE`` queries at a time.

The decode step's two reads of the paged pools are
``ops.attention.dsa_index_paged_scores`` and
``ops.attention.mla_sparse_paged_decode_attention``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops import mla

Q_TILE = 512  # queries whose scores over every position are whole at once: [Q_TILE, C] float32
KEY_BLOCK = 2048  # index keys a block of a chunk's scores: [Q_TILE, heads, KEY_BLOCK] float32


def index_scores(q_i, w, k_i):
    """q_i [N, Hi, Di] the index queries, w [N, Hi] float32 their heads'
    weights, k_i [C, Di] index keys -> [N, C] float32."""
    s = jnp.einsum("nhd,cd->nhc", q_i, k_i, preferred_element_type=jnp.float32)
    return (jax.nn.relu(s) * w.astype(jnp.float32)[:, :, None]).sum(1)


def chunk_index_scores(q_i, w, k_ctx, blocks):
    """``index_scores`` of q_i [n, Hi, Di] over the first ``blocks``
    (traced) blocks of ``KEY_BLOCK`` keys of k_ctx [C, Di], C whole
    blocks; 0 beyond them.  -> [n, C] float32."""
    n, C = q_i.shape[0], k_ctx.shape[0]

    def body(j, out):
        keys = jax.lax.dynamic_slice_in_dim(k_ctx, j * KEY_BLOCK, KEY_BLOCK)
        return jax.lax.dynamic_update_slice_in_dim(out, index_scores(q_i, w, keys), j * KEY_BLOCK, axis=1)

    return jax.lax.fori_loop(0, blocks, body, jnp.zeros((n, C), jnp.float32))


def _sortable(scores, valid):
    """float32 scores as uint32 whose order is the scores' (-0.0 as 0.0);
    0, under every score's, where not ``valid``."""
    scores = jnp.where(scores == 0, 0.0, scores.astype(jnp.float32))
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    keys = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    return jnp.where(valid, keys, jnp.uint32(0))


def kth_largest(keys, k):
    """keys [N, C] uint32 -> [N] uint32: the k-th largest of a row (0
    where a row has fewer than k above 0).  Eight passes, four bits each:
    the largest prefix of which at least k keys are at or above."""
    prefix = jnp.zeros(keys.shape[0], jnp.uint32)
    digits = jnp.arange(1, 16, dtype=jnp.uint32)
    for shift in range(28, -1, -4):
        cand = prefix[:, None] | (digits << shift)[None, :]  # [N, 15], rising
        counts = (keys[:, None, :] >= cand[:, :, None]).sum(-1, dtype=jnp.int32)
        digit = (counts >= k).sum(-1).astype(jnp.uint32)  # the candidates at or under the k-th largest
        prefix = prefix | (digit << shift)
    return prefix


def keep_mask(scores, valid, k):
    """The choice as a mask: scores [N, C] float32, valid [N, C] bool a
    query's candidates, k static -> [N, C] bool, the k candidates of
    largest score (every candidate where there are at most k), a tie at
    the k-th going to the lower position."""
    with jax.named_scope("dsa.select"):
        keys = _sortable(scores, valid)
        kth = kth_largest(keys, k)[:, None]
        above = keys > kth
        ties = (keys == kth) & valid
        room = k - above.sum(-1, dtype=jnp.int32)
        return (above | (ties & (jnp.cumsum(ties, axis=-1, dtype=jnp.int32) <= room[:, None]))) & valid


def sparse_chunk_attention(q_nope, q_rope, q_i, w, ctx, k_ctx, wukv, start, n_valid, cfg, k):
    """A prompt chunk's attention under the index's choice.  q_nope,
    q_rope [T, H, .] (scaled) and q_i [T, Hi, Di], w [T, Hi] of the
    positions ``start ..``; ctx [C, latent_row] the sequence's latent
    rows and k_ctx [C, Di] its index keys (position p in row p, the
    chunk's own put in; C whole key blocks of both kinds).  -> ([T, H *
    v_head_dim], kept: the positions the real queries attended, int32;
    the choice as a mask [T, C], for the checks)."""
    T, C = q_nope.shape[0], ctx.shape[0]
    tile = min(T, Q_TILE)
    assert T % tile == 0 and C % KEY_BLOCK == 0 and C % mla.K_BLOCK == 0
    pos = jnp.arange(C)
    masks = {}

    def keep_of(first, n):
        # a query block of the online softmax is a tile of the index
        q_pos = start + first + jnp.arange(n)
        seen = jnp.minimum(start + first + n, start + n_valid)
        blocks = jnp.where(first < n_valid, -(-seen // KEY_BLOCK), 0)
        with jax.named_scope("dsa.index"):
            scores = chunk_index_scores(q_i[first:first + n], w[first:first + n], k_ctx, blocks)
        valid = (pos[None, :] <= q_pos[:, None]) & ((first + jnp.arange(n)) < n_valid)[:, None]
        masks[first] = keep_mask(scores, valid, k)
        return masks[first]

    out = mla.expanded_attention(q_nope, q_rope, ctx, wukv, start, n_valid, cfg, keep_of=keep_of, q_block=tile)
    mask = jnp.concatenate([masks[f] for f in sorted(masks)])
    return out, mask.sum(dtype=jnp.int32), mask

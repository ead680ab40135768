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
  query, to a chunk and to a decode step alike; told how many key
  ``blocks`` hold candidates (a chunk's tile knows its reach, a decode
  step's lanes differ), it counts over those alone, a block at a time,
  and not at all where they hold at most ``k`` positions.
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


def _running_count(ties):
    """ties [N, KEY_BLOCK] bool -> [N, KEY_BLOCK] int32, the ties up to
    and with each column: a product with a triangle of ones on the matrix
    unit, which is idle under the passes (0 and 1 are exact in bfloat16,
    their sums in float32).  Alone it costs a tile what ``jnp.cumsum``
    over a block's columns costs; inside the chunk program it took 11 ms
    out of 685 (PERF.md section 5, PR 58)."""
    at = jnp.arange(ties.shape[-1])
    upto = (at[:, None] <= at[None, :]).astype(jnp.bfloat16)
    return jnp.dot(ties.astype(jnp.bfloat16), upto, preferred_element_type=jnp.float32).astype(jnp.int32)


def _keep_within(scores, valid, k, blocks, running=_running_count):
    """``keep_mask`` over the first ``blocks`` (traced, at least one)
    blocks of ``KEY_BLOCK`` columns, a block at a time, in two loops a
    tile (a program holds 48 tiles, and every loop is code to trace, to
    compile and to load).  The first is ``kth_largest``'s eight passes
    block by block: a block's counts added to the tile's, and at a pass's
    last block the digit taken; it also keeps the count of the keys ABOVE
    the prefix so far (those at or above the next candidate over the
    digit taken, or where the digit is 15 the pass before's), which after
    the last pass is the count above the k-th largest.  The second writes
    the mask, the ties counted on from block to block (``running``: a
    block's ties up to each column).  False beyond the blocks."""
    N, C = scores.shape
    digits = jnp.arange(1, 16, dtype=jnp.uint32)

    def part(j):
        ok = jax.lax.dynamic_slice_in_dim(valid, j * KEY_BLOCK, KEY_BLOCK, axis=1)
        return _sortable(jax.lax.dynamic_slice_in_dim(scores, j * KEY_BLOCK, KEY_BLOCK, axis=1), ok), ok

    def count(i, carry):
        prefix, above, counts = carry
        shift = (28 - 4 * (i // blocks)).astype(jnp.uint32)
        cand = prefix[:, None] | (digits << shift)[None, :]
        counts = counts + (part(i % blocks)[0][:, None, :] >= cand[:, :, None]).sum(-1, dtype=jnp.int32)
        digit = (counts >= k).sum(-1)
        over = jnp.take_along_axis(counts, jnp.minimum(digit, 14)[:, None], axis=1)[:, 0]
        last = i % blocks == blocks - 1
        return (jnp.where(last, prefix | (digit.astype(jnp.uint32) << shift), prefix),
                jnp.where(last & (digit < 15), over, above), jnp.where(last, 0, counts))

    zeros = jnp.zeros(N, jnp.int32)
    kth, above, _ = jax.lax.fori_loop(0, 8 * blocks, count,
                                      (jnp.zeros(N, jnp.uint32), zeros, jnp.zeros((N, 15), jnp.int32)))
    kth, room = kth[:, None], (k - above)[:, None]

    def choose(j, carry):
        mask, before = carry
        keys, ok = part(j)
        ties = (keys == kth) & ok
        upto = before[:, None] + running(ties)
        keep = ((keys > kth) | (ties & (upto <= room))) & ok
        return jax.lax.dynamic_update_slice_in_dim(mask, keep, j * KEY_BLOCK, axis=1), upto[:, -1]

    return jax.lax.fori_loop(0, blocks, choose, (jnp.zeros((N, C), bool), zeros))[0]


def counts_over(blocks, k):
    """Whether the choice over ``blocks`` key blocks counts at all: where
    they hold at most ``k`` positions every candidate is kept."""
    return blocks * KEY_BLOCK > k


def keep_mask(scores, valid, k, blocks=None):
    """The choice as a mask: scores [N, C] float32, valid [N, C] bool a
    query's candidates, k static -> [N, C] bool, the k candidates of
    largest score (every candidate where there are at most k), a tie at
    the k-th going to the lower position.  ``blocks`` (traced or not; C
    whole key blocks then): the candidates lie in the first ``blocks``
    blocks of ``KEY_BLOCK`` columns, no other column is read and the
    mask is False beyond them: the same mask as without it, by work in
    proportion to ``blocks``."""
    with jax.named_scope("dsa.select"):
        if blocks is not None:
            blocks = jnp.asarray(blocks, jnp.int32)
            within = valid & (jnp.arange(scores.shape[1]) < blocks * KEY_BLOCK)[None, :]
            return jax.lax.cond(counts_over(blocks, k), lambda: _keep_within(scores, valid, k, blocks),
                                lambda: within)
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
    columns: the scores the choice's passes read, a tile's queries times
    the columns of its reach, 0 of a tile that counted nothing, int32;
    the choice as a mask [T, C], for the checks)."""
    T, C = q_nope.shape[0], ctx.shape[0]
    tile = min(T, Q_TILE)
    assert T % tile == 0 and C % KEY_BLOCK == 0 and C % mla.K_BLOCK == 0
    pos = jnp.arange(C)
    masks, columns = {}, {}

    def keep_of(first, n):
        # a query block of the online softmax is a tile of the index
        q_pos = start + first + jnp.arange(n)
        seen = jnp.minimum(start + first + n, start + n_valid)
        blocks = jnp.where(first < n_valid, -(-seen // KEY_BLOCK), 0)
        with jax.named_scope("dsa.index"):
            scores = chunk_index_scores(q_i[first:first + n], w[first:first + n], k_ctx, blocks)
        valid = (pos[None, :] <= q_pos[:, None]) & ((first + jnp.arange(n)) < n_valid)[:, None]
        masks[first] = keep_mask(scores, valid, k, blocks)
        columns[first] = jnp.where(counts_over(blocks, k), n * blocks * KEY_BLOCK, 0)
        return masks[first]

    out = mla.expanded_attention(q_nope, q_rope, ctx, wukv, start, n_valid, cfg, keep_of=keep_of, q_block=tile)
    mask = jnp.concatenate([masks[f] for f in sorted(masks)])
    return out, mask.sum(dtype=jnp.int32), sum(columns.values()), mask

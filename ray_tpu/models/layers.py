"""The layers more than one served family is built from, and the one
statement of how a prompt chunk's context is laid out.  No row of
``serve/llm/config.py:MODEL_FAMILIES``: a family imports from here and
from ``models/common.py``, never from another family
(``tests/test_models.py``).  Pure array math lives in ``ops/`` beside
its decode twin; here is what takes a layer's parameters ``lp``, a
family's config ``cfg`` and the engine's cache dict.

- A chunk's context (``chunk_slots``, ``chunk_context``): "the
  sequence's positions by page, then room to whole key blocks, the
  chunk's own rows laid in at ``start``".  The key block is the
  argument, of whoever owns the softmax it sizes
  (``ops.attention.K_BLOCK``, ``ops.mla.K_BLOCK``,
  ``ops.block_sparse.K_BLOCK``, ``ops.dsa.KEY_BLOCK``).
- Grouped-query attention over the paged cache (``attention_chunk``,
  ``attention_decode``; ``attend_chunk`` for a family that makes q, k
  and v its own way), no rotation, no bias.  cfg: ``n_head``,
  ``n_kv_head``, ``head_dim``; lp: ``wqkv [d, (H + 2 G) hd]``, ``wo
  [H hd, d]``.
- Mamba-2 over a lane's state (``mamba_chunk``, ``mamba_decode``; the
  equations as ``models/nemotron_h.py`` writes them; a lane's two
  arrays a layer are ``tail_name`` and ``state_name``).  cfg:
  ``mamba_num_heads``, ``mamba_head_dim``, ``ssm_state_size``,
  ``n_groups``, ``d_inner``, ``conv_dim``, ``chunk_size``,
  ``layer_norm_epsilon``; lp: as ``nemotron_h.init_params`` makes a
  Mamba layer.
- ``counters``: a family's ``COUNTERS``, in its order, as the one int32
  array its forwards return; ``numbered``: a layer's index among the
  layers of its kind.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.models.common import pool_rows
from ray_tpu.ops import mamba2
from ray_tpu.ops.attention import chunk_attention, gqa_paged_decode_attention

# What ``ops.moe.moe_experts`` counts of one expert layer behind the
# pairs its router made and those whose expert is held here, in the
# order an expert family's ``_experts`` returns them; last, where a
# family's router may choose no expert, the pairs that did.
EXPERT_LAYER_COUNTS = ("moe_pairs_routed", "moe_pairs_held", "moe_pairs", "moe_experts_hit", "moe_peak_rows",
                       "moe_pairs_skipped")


def numbered(kinds) -> list:
    """(kind, index among the layers of its kind) of every layer of a
    model whose layers are of the ``kinds`` given, in order: a layer's
    index into what its kind caches."""
    seen, out = {}, []
    for kind in kinds:
        out.append((kind, seen.setdefault(kind, 0)))
        seen[kind] += 1
    return out


# ----------------------------------------------------------------------
# a chunk's context
# ----------------------------------------------------------------------
def chunk_slots(table, block_size: int, T: int, key_block: int):
    """Where a sequence's cached positions lie and how much room a chunk
    of T tokens needs behind them: table [pages] the sequence's physical
    pages -> (``where`` [C] the slot of each of its ``C = pages *
    block_size`` positions, in the positions' order; ``room``, the rows
    that make ``C + room`` whole blocks of ``key_block`` keys and at
    least ``C + T``, so that the chunk fits wherever it starts)."""
    C = table.shape[0] * block_size
    where = (table[:, None] * block_size + jnp.arange(block_size)).reshape(C)
    room = -(-(C + T) // key_block) * key_block - C
    return where, room


def chunk_context(pool, layer, where, room, rows, start):
    """What a chunk attends over in paged layer ``layer``: the
    sequence's cached rows of pool [L, P, D] (``where``, ``room``:
    ``chunk_slots``) in their positions' order, ``room`` rows of zeros,
    and the chunk's own rows [T, ...] (a pool's row in the shape the
    attention takes it) laid in at ``start`` -> [C + room, ...]."""
    shape = rows.shape[1:]
    ctx = jnp.concatenate([pool_rows(pool, layer, where).reshape(-1, *shape), jnp.zeros((room, *shape), pool.dtype)])
    return jax.lax.dynamic_update_slice_in_dim(ctx, rows, start, axis=0)


# ----------------------------------------------------------------------
# grouped-query attention over the paged cache
# ----------------------------------------------------------------------
def _qkv(y, lp, cfg):
    """y [N, d] -> q [N, G, R, hd] and k, v [N, G, hd]."""
    H, G, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    q, k, v = jnp.split(y @ lp["wqkv"], [H * hd, (H + G) * hd], axis=-1)
    return q.reshape(-1, G, H // G, hd), k.reshape(-1, G, hd), v.reshape(-1, G, hd)


def attention_chunk(y, lp, cfg, cache, i, where, room, start, n_valid, scale=None):
    """Attention layer i on a chunk's normed tokens y [T, d] over the
    sequence's cached rows and the chunk's own (``where``, ``room``:
    ``chunk_slots`` at ``ops.attention.K_BLOCK``) -> (out [T, d], k, v
    [T, G, hd])."""
    with jax.named_scope("attn.gqa"):
        q, k, v = _qkv(y, lp, cfg)
        return attend_chunk(q, k, v, cache, i, where, room, start, n_valid, scale) @ lp["wo"], k, v


def attend_chunk(q, k, v, cache, i, where, room, start, n_valid, scale=None):
    """A chunk's queries q [T, G, R, hd] over paged layer i's cached
    rows of the sequence (``where``, ``room`` as ``attention_chunk``
    says) with the chunk's own k, v [T, G, hd] laid in at ``start`` ->
    [T, G * R * hd].  What a family that makes q, k and v its own way
    (``models/zaya.py``) shares with ``attention_chunk``."""
    return chunk_attention(q, chunk_context(cache["k_pages"], i, where, room, k, start),
                           chunk_context(cache["v_pages"], i, where, room, v, start), start, n_valid, scale)


def attention_decode(y, lp, cfg, cache, i, block_tables, lengths, block_size, scale=None):
    """Attention layer i on one normed token a lane y [B, d] over the
    lanes' pages where they lie -> (out [B, d], k, v [B, G, hd])."""
    with jax.named_scope("attn.gqa"):
        q, k, v = _qkv(y, lp, cfg)
        o = gqa_paged_decode_attention(q, k, v, cache["k_pages"], cache["v_pages"], i, block_tables, lengths,
                                       block_size=block_size, scale=scale)
        return o.reshape(y.shape[0], -1) @ lp["wo"], k, v


# ----------------------------------------------------------------------
# Mamba-2 over a lane's state
# ----------------------------------------------------------------------
def tail_name(i: int) -> str:
    return f"conv_tail_{i}"


def state_name(i: int) -> str:
    return f"ssm_state_{i}"


def _mamba_in(y, lp, cfg):
    """y [N, d] -> the gate z [N, d_inner], xBC [N, conv_dim] before its
    convolution, dt [N, heads] float32 after its softplus."""
    with jax.named_scope("mamba.in_proj"):
        z, xbc, dt = jnp.split(y @ lp["in_proj"], [cfg.d_inner, cfg.d_inner + cfg.conv_dim], axis=-1)
        return z, xbc, jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])


def _mamba_split(xbc, cfg):
    """xBC [N, conv_dim] after its convolution -> x [N, heads, head_dim], B, C [N, groups, state]."""
    N, G, S = xbc.shape[0], cfg.n_groups, cfg.ssm_state_size
    x, B, C = jnp.split(xbc, [cfg.d_inner, cfg.d_inner + G * S], axis=-1)
    return x.reshape(N, cfg.mamba_num_heads, cfg.mamba_head_dim), B.reshape(N, G, S), C.reshape(N, G, S)


def _mamba_out(o, z, lp, cfg):
    """The gate, the norm over each group's columns, the way out."""
    with jax.named_scope("mamba.gate_out"):
        return mamba2.gated_group_norm(o, z, lp["w_gn"], cfg.n_groups, cfg.layer_norm_epsilon) @ lp["out_proj"]


def mamba_chunk(y, lp, cfg, cache, i, lane, start, n_valid):
    """Mamba layer i on a chunk's normed tokens y [T, d], from lane
    ``lane``'s tail and state (zeros where ``start`` is 0) -> (out [T,
    d], {the tail's name, the state's name: as they stand after the last
    real position})."""
    z, xbc, dt = _mamba_in(y, lp, cfg)
    with jax.named_scope("mamba.conv"):
        tail = jnp.where(start == 0, 0, cache[tail_name(i)][lane])
        xbc, tail = mamba2.conv_tail(xbc, tail, lp["conv_w"], lp["conv_b"], n_valid)
    with jax.named_scope("mamba.scan"):
        xs, B, Cm = _mamba_split(xbc, cfg)
        held = jnp.where(start == 0, 0.0, cache[state_name(i)][lane])
        o, held = mamba2.ssd_chunk(xs, dt, -jnp.exp(lp["A_log"]), B, Cm, lp["D"], held, n_valid, cfg.chunk_size)
    return _mamba_out(o, z, lp, cfg), {tail_name(i): tail, state_name(i): held}


def mamba_decode(y, lp, cfg, cache, i, runs):
    """Mamba layer i on one normed token a lane y [B, d]: the running
    lanes' states updated where they lie, every tail shifted -> (out [B,
    d], {the tail's name, the state's name: the whole new arrays})."""
    z, xbc, dt = _mamba_in(y, lp, cfg)
    with jax.named_scope("mamba.conv"):
        xbc, tail = mamba2.conv_tail(xbc[:, None], cache[tail_name(i)], lp["conv_w"], lp["conv_b"])
    with jax.named_scope("mamba.step"):
        xs, Bm, Cm = _mamba_split(xbc[:, 0], cfg)
        o, state = mamba2.ssm_decode_step(
            xs, dt, -jnp.exp(lp["A_log"]), Bm, Cm, lp["D"], cache[state_name(i)], runs)
    return _mamba_out(o, z, lp, cfg), {tail_name(i): tail, state_name(i): state}


# ----------------------------------------------------------------------
# what a forward counted
# ----------------------------------------------------------------------
def counters(names, expert_layers=(), experts_held: int = 0, **named):
    """A family's COUNTERS (``names``, in its order) of one program as
    one int32 array.  ``expert_layers``: its expert layers' counts, an
    array a layer in EXPERT_LAYER_COUNTS' order (the first five, or all
    six), summed over the layers here; with them ``moe_layer_programs``
    (the layers there were) and ``moe_expert_slots`` (``experts_held``
    a layer).  ``named``: what else the program counted, by name; a
    value of n elements is that name's and the n - 1 names' after it
    (``kv_blocks_walked=ops.attention.gqa_decode_blocks(..)``: walked,
    whole).  A name nobody gives counts 0; the chunk programs that say
    ``kv_blocks_walked=(0, 0)`` state the pair because leaving it out is
    another program text than PR 58's (a [2] constant, sliced), and
    ``scripts/serve_program_hashes.py`` is how a PR shows that no cell's
    program moved."""
    if expert_layers:
        n = len(expert_layers)
        summed = jnp.stack(expert_layers).sum(0).astype(jnp.int32)
        named = {**dict(zip(EXPERT_LAYER_COUNTS, summed)), "moe_expert_slots": experts_held * n,
                 "moe_layer_programs": n, **named}
    unknown = set(named) - set(names)
    if unknown:
        raise KeyError(f"no counter of {names} is named {sorted(unknown)}")
    out = []
    while len(out) < len(names):
        value = jnp.asarray(named.get(names[len(out)], 0), jnp.int32)
        out += list(value) if value.ndim else [value]
    return jnp.stack(out)

"""MiniCPM-SALA (openbmb/MiniCPM-SALA) for the serving engine: a decoder
whose layers mix tokens in one of two ways, by the published
``mixer_types``:

- ``lightning-attn``: linear attention.  32 heads of 128; RMSNorm over
  each head of q and k, the rotation at the token's position, then for
  each head ``S_t = lambda_h S_{t-1} + k_t^T v_t`` (128 x 128, float32),
  ``o_t = q_t S_t / sqrt(128)``; RMSNorm over each head of o; times
  ``sigmoid(W_z y)``; ``W_o``.  It caches no keys: one state a sequence.
- ``minicpm4``: block-sparse attention (InfLLM-V2,
  ``ops/block_sparse.py``).  32 query heads of 128 over 2 K/V heads, 16
  to one; RMSNorm over each head of q and k, no rotation.  Under
  ``dense_len`` a query reads every earlier position; past it, block 0,
  the blocks of the last 2,048 positions and the best-scoring others,
  64 in all, each token and each K/V head its own.  It caches K and V
  of 256 values a position, and a compressed key for every 16.

The stream: ``x = scale_emb * E[tok]``; every layer ``x += r *
Mixer(rmsnorm(x))``, ``x += r * W_d(silu(W_g y) * W_u y)`` on ``y =
rmsnorm(x)``, with ``r = scale_depth / sqrt(32)`` (the PUBLISHED depth,
whatever is held here); ``logits = W_head rmsnorm(x) / (hidden_size /
dim_model_base)``.

The module is a *family* to ``serve/llm/engine.py`` that STATES a cache
other than K and V of every layer (``cache_spec``), so its two forwards
read that cache and return what to write into it
(``prefill_chunk``, ``decode_forward_cached``; docs/serving.md "Model
families"); a prompt goes in by chunks of ``prefill_chunk`` tokens.
``benchmark/reference_minicpm_sala.py`` is the plain float32 forward of
the same equations and reads the same tree: ``embed [V, d]``, ``layers``
(each ``w_in [d]``, ``wqkvz`` (q, k, v and the gate side by side),
``w_qn``, ``w_kn`` ``[head]``, ``wo``, ``w_post [d]``, ``wgu [d, 2f]``,
``wd [f, d]``, and ``w_on [head]`` in a lightning layer), ``norm [d]``,
``lm_head [d, V]``.  Weights are seeded random, made on the device a
layer at a time in the serving dtype.  There is no training path.

What the source's ``config.json`` does not carry is ASSUMED, here and in
``benchmark/configs/minicpm-sala.json``: the decay ``lambda_h =
exp(-2^(-8h/32))`` (Lightning Attention-2's fixed slopes), no activation
on q, k, v, gates of one value an output column, the sparse sizes of
MiniCPM4.1's ``sparse_config``, the state in float32.  A query is dense
or sparse by ITS position (``t < dense_len``), not by the length of the
prompt it came in: only so do a prompt's chunks, a decode step and a
recompute after preemption agree.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models.common import CacheSpec, pool_rows, rmsnorm, rope
from ray_tpu.models.layers import chunk_context, chunk_slots, numbered
from ray_tpu.ops import block_sparse
from ray_tpu.ops.lightning import lightning_chunk, lightning_slopes, lightning_step

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
# the published order of the 32 layers (config.json: mixer_types)
_L, _S = LIGHTNING, SPARSE
PUBLISHED_MIXERS = (_S, _L, _L, _L, _L, _L, _L, _L, _L, _S, _L, _L, _L, _L, _L, _L,
                    _S, _S, _L, _L, _L, _L, _S, _L, _L, _L, _L, _L, _L, _S, _S, _S)

# What a forward returns after what it writes, summed over its sparse
# layers: blocks read and blocks there were, over every (token or lane,
# K/V head); and of a decode step, the positions its kernel copied (the
# chosen blocks, whole) and those among them a lane holds; and of a chunk,
# the tiles of queries that scored blocks (none does under ``dense_len``)
# and the tiles there were.
COUNTERS = ("sparse_blocks_kept", "sparse_blocks_cached", "kv_positions_attended",
            "kv_positions_gathered", "sparse_tiles_selected", "sparse_tiles")


@dataclass(frozen=True)
class MiniCPMSalaConfig:
    """The source's ``config.json`` under the engine's names where it
    has one, the source's own elsewhere; then what is assumed."""

    vocab_size: int = 73472  # rows held: the source's 73,448 padded to 128
    mixer_types: tuple = PUBLISHED_MIXERS
    published_layers: int = 32  # num_hidden_layers of the source: the residual's scale
    n_head: int = 32  # num_attention_heads
    n_kv_head: int = 2  # num_key_value_heads
    head_dim: int = 128
    d_model: int = 4096  # hidden_size
    intermediate_size: int = 16384
    lightning_nh: int = 32  # = lightning_nkv
    lightning_head_dim: int = 128
    max_seq_len: int = 524288  # max_position_embeddings
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    # assumed: MiniCPM4.1's sparse_config
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64  # positions of a block of the SELECTION (not the engine's page)
    init_blocks: int = 1
    window_size: int = 2048
    topk: int = 64
    dense_len: int = 8192
    prefill_chunk: int = 4096  # most tokens of one prefill program
    dtype: Any = jnp.bfloat16  # parameters and matmuls; norms, softmax and the state are float32

    @property
    def n_layer(self) -> int:
        return len(self.mixer_types)

    @staticmethod
    def minicpm_sala(**kw) -> "MiniCPMSalaConfig":
        return MiniCPMSalaConfig(**kw)  # 9.48B parameters

    @staticmethod
    def minicpm_sala_16l(**kw) -> "MiniCPMSalaConfig":
        """Layers 8..23 of the 32: 4 sparse and 12 lightning, the
        published 1:3, 10.08 GB in bf16 with the head: what one 16 GB
        chip holds beside its cache (benchmark/configs/minicpm-sala.json)."""
        return MiniCPMSalaConfig(mixer_types=PUBLISHED_MIXERS[8:24], **kw)

    @staticmethod
    def minicpm_sala_tiny(**kw) -> "MiniCPMSalaConfig":
        """Every width small, and the sparse sizes with them: a prompt
        of a few hundred tokens is past ``dense_len`` and drops blocks,
        and takes several chunks."""
        return MiniCPMSalaConfig(
            vocab_size=256, mixer_types=(_L, _S, _L, _S), n_head=4, n_kv_head=2, head_dim=16,
            d_model=64, intermediate_size=128, lightning_nh=4, lightning_head_dim=16,
            max_seq_len=512, dim_model_base=16, kernel_size=8, kernel_stride=4, block_size=16,
            init_blocks=1, window_size=32, topk=4, dense_len=64, prefill_chunk=64, **kw)


def cache_spec(cfg: MiniCPMSalaConfig, block_size: int) -> CacheSpec:
    """The sparse layers page K and V of the K/V heads alone, and a
    compressed key a stride of positions beside them; the lightning
    layers hold one float32 state a lane."""
    if block_size % cfg.kernel_stride or cfg.block_size % block_size:
        raise ValueError(
            f"pages of {block_size} positions: a page must be whole strides of {cfg.kernel_stride} "
            f"and a selection block of {cfg.block_size} whole pages")
    row = cfg.n_kv_head * cfg.head_dim
    state = (cfg.lightning_nh, cfg.lightning_head_dim, cfg.lightning_head_dim)
    # a state a lightning LAYER: a decode step then reads and writes whole
    # arrays; one array of all layers cost a strided copy a layer each way
    # (5.3 ms of a 25.4 ms step on the chip, PR 30)
    return CacheSpec(
        paged_layers=cfg.mixer_types.count(SPARSE), row_width=row,
        page_extras=(("ck_pages", block_size // cfg.kernel_stride, row, cfg.dtype),),
        lane_state=tuple((_state_name(i), state, jnp.float32) for i in range(cfg.mixer_types.count(LIGHTNING))),
        prefill_chunk=cfg.prefill_chunk)


def _state_name(i: int) -> str:
    return f"lightning_state_{i}"


def init_params(cfg: MiniCPMSalaConfig, rng=None):
    """Seeded weights (normal, std 0.02; norm weights 1) in cfg.dtype,
    made on the device one layer at a time."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    d, f, V = cfg.d_model, cfg.intermediate_size, cfg.vocab_size

    def normal(key, *shape):
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(cfg.dtype)

    def ones(n):
        return jnp.ones((n,), cfg.dtype)

    def layer(kind):
        if kind == SPARSE:
            head, inner = cfg.head_dim, cfg.n_head * cfg.head_dim
            fused = 2 * inner + 2 * cfg.n_kv_head * cfg.head_dim
        else:
            head, inner = cfg.lightning_head_dim, cfg.lightning_nh * cfg.lightning_head_dim
            fused = 4 * inner

        @jax.jit
        def make(key):
            k = jax.random.split(key, 4)
            lp = {"w_in": ones(d), "wqkvz": normal(k[0], d, fused), "w_qn": ones(head),
                  "w_kn": ones(head), "wo": normal(k[1], inner, d), "w_post": ones(d),
                  "wgu": normal(k[2], d, 2 * f), "wd": normal(k[3], f, d)}
            if kind == LIGHTNING:
                lp["w_on"] = ones(head)
            return lp

        return make

    @jax.jit
    def ends(key):
        k = jax.random.split(key, 2)
        return {"embed": normal(k[0], V, d), "norm": ones(d), "lm_head": normal(k[1], d, V)}

    makers = {SPARSE: layer(SPARSE), LIGHTNING: layer(LIGHTNING)}
    keys = jax.random.split(rng, cfg.n_layer + 1)
    return {**ends(keys[0]),
            "layers": [makers[kind](key) for kind, key in zip(cfg.mixer_types, keys[1:])]}


def serving_params(params, cfg: MiniCPMSalaConfig):
    """The tree a server holds, which ``init_params`` already makes."""
    return params


def _residual(cfg):
    return cfg.scale_depth / (cfg.published_layers ** 0.5)


def _gated(o, z):
    return (o.astype(jnp.float32) * jax.nn.sigmoid(z.astype(jnp.float32))).astype(o.dtype)


def _sparse_qkvz(y, lp, cfg):
    """y [N, d] -> q [N, G, R, hd] and k, v [N, G, hd], q and k normed
    over each head (no rotation), and the gate z [N, H * hd]."""
    H, G, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    q, k, v, z = jnp.split(y @ lp["wqkvz"], [H * hd, (H + G) * hd, (H + 2 * G) * hd], axis=-1)
    q = rmsnorm(q.reshape(-1, G, H // G, hd), lp["w_qn"], cfg.rms_norm_eps)
    k = rmsnorm(k.reshape(-1, G, hd), lp["w_kn"], cfg.rms_norm_eps)
    return q, k, v.reshape(-1, G, hd), z


def _lightning_qkvz(y, lp, cfg, pos):
    """y [N, d] at positions pos [N] -> q, k, v [N, H, hd], q and k
    normed over each head and rotated, and the gate z."""
    H, hd = cfg.lightning_nh, cfg.lightning_head_dim
    q, k, v, z = (t.reshape(-1, H, hd) for t in jnp.split(y @ lp["wqkvz"], 4, axis=-1))
    q = rope(rmsnorm(q, lp["w_qn"], cfg.rms_norm_eps), pos, cfg.rope_theta)
    k = rope(rmsnorm(k, lp["w_kn"], cfg.rms_norm_eps), pos, cfg.rope_theta)
    return q, k, v, z.reshape(-1, H * hd)


def _lightning_out(o, z, lp, cfg):
    o = rmsnorm(o, lp["w_on"], cfg.rms_norm_eps)
    return _gated(o.reshape(o.shape[0], -1), z) @ lp["wo"]


def _mlp(x, lp, cfg):
    with jax.named_scope("sala.mlp"):
        gate, up = jnp.split(rmsnorm(x, lp["w_post"], cfg.rms_norm_eps) @ lp["wgu"], 2, axis=-1)
        return (jax.nn.silu(gate) * up) @ lp["wd"]


def _logits(x, params, cfg):
    x = rmsnorm(x, params["norm"], cfg.rms_norm_eps)
    return (x @ params["lm_head"]).astype(jnp.float32) / (cfg.d_model / cfg.dim_model_base)


def prefill_chunk(params, cfg: MiniCPMSalaConfig, cache, tokens, start, last_index, table, lane,
                  block_size: int):
    """One chunk of one prompt: tokens [1, T] at positions ``start ..``,
    of which ``last_index[0] + 1`` are real; table [pages] the
    sequence's physical pages; lane the lane whose state it holds.
    Reads the earlier positions' K and V through the table and, unless
    ``start`` is 0, the lane's state.  -> (logits [1, V] at
    ``last_index``, k, v [Lp, 1, T, G, hd] the chunk's rows, {"ck_pages":
    (rows [Lp, n, G * hd], where [n])} the compressed keys of the
    windows whose last key the chunk holds, {"lightning_state_<i>": [H,
    hd, hd]} the lane's state after the last real position, lightning
    layer by layer, COUNTERS)."""
    T = tokens.shape[1]
    n_valid = last_index[0] + 1
    G, hd, stride = cfg.n_kv_head, cfg.head_dim, cfg.kernel_stride
    x = (cfg.scale_emb * params["embed"][tokens[0]].astype(jnp.float32)).astype(cfg.dtype)
    pos = start + jnp.arange(T)
    where, room = chunk_slots(table, block_size, T, block_sparse.K_BLOCK)
    # the windows whose last key lies in this chunk, and their rows of the pool
    per_page = block_size // stride
    n_win = -(-T // stride)
    win = start // stride - (cfg.kernel_size // stride - 1) + jnp.arange(n_win)
    whole = (win >= 0) & (stride * win + cfg.kernel_size <= start + n_valid)
    win = jnp.maximum(win, 0)
    ck_where = jnp.where(whole, table[win // per_page] * per_page + win % per_page, 0)
    ks, vs, cks, states, counts = [], [], [], {}, []
    for lp, (kind, i) in zip(params["layers"], numbered(cfg.mixer_types)):
        y = rmsnorm(x, lp["w_in"], cfg.rms_norm_eps)
        if kind == SPARSE:
            q, k, v, z = _sparse_qkvz(y, lp, cfg)
            ctx_k = chunk_context(cache["k_pages"], i, where, room, k, start)
            ctx_v = chunk_context(cache["v_pages"], i, where, room, v, start)
            ck = block_sparse.compress_keys(ctx_k, cfg)
            o, counted = block_sparse.sparse_chunk_attention(q, ctx_k, ctx_v, ck, start, n_valid, cfg)
            out = _gated(o.reshape(T, -1), z) @ lp["wo"]
            ks.append(k)
            vs.append(v)
            cks.append(ck[win].reshape(n_win, G * hd).astype(cfg.dtype))
            counts.append(counted)
        else:
            with jax.named_scope("sala.lightning"):
                q, k, v, z = _lightning_qkvz(y, lp, cfg, pos)
                held = cache[_state_name(i)][lane]
                o, states[_state_name(i)] = lightning_chunk(
                    q, k, v, jnp.where(start == 0, 0.0, held), n_valid, lightning_slopes(cfg.lightning_nh))
                out = _lightning_out(o, z, lp, cfg)
        x = x + (_residual(cfg) * out).astype(x.dtype)
        x = x + (_residual(cfg) * _mlp(x, lp, cfg)).astype(x.dtype)
    blocks, tiles = jnp.split(jnp.stack(counts).sum(0), 2)
    counters = jnp.concatenate([blocks, jnp.zeros(2, jnp.int32), tiles])
    return (_logits(x[last_index], params, cfg), jnp.stack(ks)[:, None], jnp.stack(vs)[:, None],
            {"ck_pages": (jnp.stack(cks), ck_where)}, states, counters)


def _choose(q, k_new, cache, i, tables, t, cfg, block_size):
    """A decode step's selection in sparse layer i: q [B, G, R, hd] at
    positions t [B]; k_new [B, G, hd] the fed token's key.  -> (chosen
    pages, chosen blocks, counts: what the kernel walks; the compressed
    key of the window that k_new completes [B, G * hd] and its row of
    the pool, the scratch row where none does)."""
    B, G, R, hd = q.shape
    stride, size, sb = cfg.kernel_stride, cfg.kernel_size, cfg.block_size
    per_page = block_size // stride
    n_win = tables.shape[1] * per_page
    rows = (tables[:, :, None] * per_page + jnp.arange(per_page)).reshape(B, n_win)
    ck = pool_rows(cache["ck_pages"], i, rows)  # [B, n_win, G * hd]
    # the window whose last key is this token's: the mean of its size - 1 cached keys and k_new
    before = jnp.maximum(t[:, None] - (size - 1) + jnp.arange(size - 1), 0)
    before = jnp.take_along_axis(tables, before // block_size, axis=1) * block_size + before % block_size
    new = (pool_rows(cache["k_pages"], i, before).astype(jnp.float32).sum(1)
           + k_new.reshape(B, G * hd).astype(jnp.float32)) / size
    new = new.astype(ck.dtype)
    completes = ((t + 1) % stride == 0) & (t + 1 >= size)
    j = jnp.maximum(t + 1 - size, 0) // stride
    ck = jnp.where(((jnp.arange(n_win) == j[:, None]) & completes[:, None])[..., None], new[:, None], ck)
    new_where = jnp.where(completes, jnp.take_along_axis(tables, (j // per_page)[:, None], axis=1)[:, 0]
                          * per_page + j % per_page, 0)
    s = jnp.einsum("bgrd,bjgd->bgrj", q, ck.reshape(B, n_win, G, hd),
                   preferred_element_type=jnp.float32) / (hd ** 0.5)
    n_blocks = tables.shape[1] * block_size // sb
    score = block_sparse.block_scores(s, t, n_blocks, cfg)
    blocks, counts = block_sparse.block_choice(
        score, t, min(block_sparse.max_choice(cfg), n_blocks), cfg)
    counts = jnp.where((t > 0)[:, None], counts, 0)  # a lane nobody holds reads nothing
    ppb = sb // block_size
    pages = (blocks[..., None] * ppb + jnp.arange(ppb)).reshape(B, G, -1)
    pages = jnp.take_along_axis(jnp.broadcast_to(tables[:, None], (B, G, tables.shape[1])), pages, axis=2)
    return pages, blocks, counts, new, new_where


def decode_forward_cached(params, cfg: MiniCPMSalaConfig, cache, tok, block_tables, lengths,
                          block_size: int):
    """``decode_chosen`` less its last result: what the engine takes."""
    return decode_chosen(params, cfg, cache, tok, block_tables, lengths, block_size)[:-1]


def decode_chosen(params, cfg: MiniCPMSalaConfig, cache, tok, block_tables, lengths, block_size: int):
    """One decode step: tok [B] at positions lengths [B] (a lane's
    cached positions), block_tables [B, pages].  The sparse layers read
    the chosen pages where they lie; the lightning layers update the
    lanes' states.  -> (logits [B, V], k_new, v_new [Lp, B, G, hd],
    {"ck_pages": (rows [Lp, B, G * hd], where [B])}, {"lightning_state_<i>":
    the whole new array [B, H, hd, hd]}, COUNTERS, and for the checks what
    each sparse layer chose: (blocks [Lp, B, G, S], counts [Lp, B, G]))."""
    from ray_tpu.ops.attention import sparse_paged_decode_attention

    B = tok.shape[0]
    sb = cfg.block_size
    x = (cfg.scale_emb * params["embed"][tok].astype(jnp.float32)).astype(cfg.dtype)
    ks, vs, cks, states, counts, chose = [], [], [], {}, [], []
    ck_where = jnp.zeros(B, jnp.int32)
    for lp, (kind, i) in zip(params["layers"], numbered(cfg.mixer_types)):
        y = rmsnorm(x, lp["w_in"], cfg.rms_norm_eps)
        if kind == SPARSE:
            q, k, v, z = _sparse_qkvz(y, lp, cfg)
            with jax.named_scope("sala.select"):
                pages, blocks, n, ck_new, ck_where = _choose(q, k, cache, i, block_tables, lengths, cfg, block_size)
            with jax.named_scope("sala.sparse"):
                o = sparse_paged_decode_attention(
                    q, k, v, cache["k_pages"], cache["v_pages"], i, pages, blocks, n, lengths,
                    block_size=block_size, sparse_block=sb)
            out = _gated(o.reshape(B, -1), z) @ lp["wo"]
            ks.append(k)
            vs.append(v)
            cks.append(ck_new)
            chose.append((blocks, n))
            # of the chosen blocks' positions, those the lane holds
            chosen = jnp.arange(blocks.shape[-1]) < n[..., None]
            held = jnp.clip(lengths[:, None, None] - blocks * sb, 0, sb)
            counts.append(jnp.stack([n.sum(), (block_sparse.blocks_cached(lengths, cfg) * (lengths > 0)).sum()
                                     * cfg.n_kv_head, jnp.where(chosen, held, 0).sum(), n.sum() * sb, 0, 0]))
        else:
            with jax.named_scope("sala.lightning"):
                q, k, v, z = _lightning_qkvz(y, lp, cfg, lengths)
                o, states[_state_name(i)] = lightning_step(
                    q, k, v, cache[_state_name(i)], lightning_slopes(cfg.lightning_nh))
                out = _lightning_out(o, z, lp, cfg)
        x = x + (_residual(cfg) * out).astype(x.dtype)
        x = x + (_residual(cfg) * _mlp(x, lp, cfg)).astype(x.dtype)
    return (_logits(x, params, cfg), jnp.stack(ks), jnp.stack(vs),
            {"ck_pages": (jnp.stack(cks), ck_where)}, states,
            jnp.stack(counts).sum(0).astype(jnp.int32),
            (jnp.stack([b for b, _ in chose]), jnp.stack([n for _, n in chose])))

"""Mellum 2 (JetBrains/Mellum2-12B-A2.5B-Instruct, ``model_type:
mellum``) for the serving engine: a pre-norm decoder whose every layer
is grouped-query attention and then 64 SwiGLU experts routed top-8, and
whose attention is one of two kinds by the published ``layer_types``
(three ``sliding_attention`` to one ``full_attention``):

    x = E[tok]
    x = x + Attention_kind(rmsnorm(x, w1)) W_o
    x = x + sum_e p_e W_down,e (silu(y W_gate,e) * (y W_up,e)),  y = rmsnorm(x, w2)
    logits = rmsnorm(x, w_f) W_head                              [untied]

- attention: 32 query heads of 128 over 4 K/V heads, EIGHT queries a
  group, no bias; q and k are RMS-normed per head over their 128 columns
  (one weight of 128 for all heads) and then rotated (half-split), by
  the layer's kind (``rope_parameters``): ``sliding_attention`` the
  plain ``theta = 500,000``; ``full_attention`` YaRN (factor 16 over an
  original context of 8,192, beta 32 and 1: ``common.yarn_inv_freq``)
  with cos and sin times ``attention_factor`` 1.2772588722239782, so a
  full layer's scores carry its square.  Scores ``q . k / sqrt(128)``,
  causal; in a ``sliding_attention`` layer query t sees keys j with ``0
  <= t - j < sliding_window`` (1,024 keys, itself among them).
- the experts: ``g = softmax(y W_r)`` over all 64 in float32, the 8
  largest, their weights divided by their sum (``norm_topk_prob``); an
  expert is ``moe_intermediate_size`` 896 wide; no shared expert
  (``ops/moe.py``).

The module is a *family* to ``serve/llm/engine.py`` that STATES two
kinds of K/V (``cache_spec``, docs/serving.md "Model families"):

- the full layers' K and V in PAGES a sequence reserves by its length
  (``k_pages``/``v_pages`` of ``[full layers, slots, 512]``), read in
  place by the grouped-query kernel as the Nemotron-H and Granite
  families' attention layers are;
- the window layers' K and V in a RING a lane owns whatever its
  sequence's length (``win_k``/``win_v`` of ``[lanes, window layers,
  sliding_window, 512]``).  A query's window is itself and the
  ``sliding_window - 1`` positions before it, and the fed token's own
  key comes to the kernel beside the cache, so the ring holds the
  ``sliding_window - 1`` positions before the fed token and no more:
  position p lies at row ``p mod (sliding_window - 1)``, and a row is
  overwritten exactly when its position leaves every later window.  The
  ring's last row is never read (lengths stop at ``sliding_window -
  1``): it takes the writes of lanes that do not run and of a chunk's
  pads, as scratch block 0 does for the pages.  A decode step reads the
  rings THROUGH THE SAME KERNEL, addressed as a pool of one layer
  (``[1, lanes * layers * sliding_window, 512]``, a lane's pages of
  layer l at ``(lane * layers + l) * pages + p``, lengths ``min(len,
  sliding_window - 1)``): keys are cached rotated and a softmax does not
  care in which order a ring's rows come.  It writes one row a lane a
  window layer where the array lies.  A prompt chunk reads the ring's
  rows in their positions' order in front of its own, attends under the
  window mask a block of keys at a time (blocks wholly outside a query
  block's window are not visited), and writes its last ``sliding_window
  - 1`` real rows.  Stale rows of a lane's predecessor lie behind the
  length mask; a preempted sequence recomputed from position 0 rebuilds
  ring and pages alike.

``benchmark/reference_mellum2.py`` is the plain float32 forward of the
same equations and reads the same tree: ``embed [V, d]``, ``norm [d]``,
``lm_head [d, V]``, ``layers``, each ``norm1 [d]``, ``wqkv [d, 4096 + 2
* 512]``, ``w_qn``, ``w_kn`` ``[128]``, ``wo [4096, d]``, ``norm2 [d]``,
``router [d, 64]``, ``wgu [64, d, 2 * 896]`` (gate | up side by side),
``wd [64, 896, d]``.  Weights are seeded random, made on the device a
layer at a time in the serving dtype.  There is no training path.

ASSUMED, because the catalog's row of the source does not settle it
(``benchmark/configs/mellum2-12b-a2.5b.json`` lists the same): the QK
norm per head (the Qwen3-MoE convention whose key names the row uses);
bf16 parameters; the router's softmax before the top-k; the window's
edge (``t - j < sliding_window``, the ``transformers`` sliding mask); no
multi-token-prediction head (the row's ``config`` has no key that sizes
one).  ``intermediate_size`` 7168, ``max_window_layers`` and
``use_sliding_window`` are read by nothing: every layer is sparse and
``layer_types`` decides.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models.common import CacheSpec, rmsnorm, rope, yarn_inv_freq
from ray_tpu.models.layers import chunk_context, chunk_slots, numbered
from ray_tpu.ops.attention import K_BLOCK, chunk_attention

SLIDING, FULL = "sliding_attention", "full_attention"
# the published kind of each of the 28 layers (config.json: layer_types): full at 3, 7, 11, ...
PUBLISHED_LAYER_TYPES = tuple(FULL if i % 4 == 3 else SLIDING for i in range(28))
RING_K, RING_V = "win_k", "win_v"
_NEG = -1e30

# What a forward returns after what it writes, summed over its layers:
# the expert families' counters (``models/olmoe.py``), then what a
# decode step's attention read: cached positions attended and whole
# pages copied (both kinds of layer), the window layers' and the full
# layers' attended positions apart, and what the layers would attend
# were all of them full.
COUNTERS = ("moe_pairs", "moe_experts_hit", "moe_peak_rows", "moe_expert_slots", "moe_layer_programs",
            "kv_positions_attended", "kv_positions_gathered",
            "attn_positions_window", "attn_positions_full", "attn_positions_unwindowed",
            "kv_blocks_walked", "kv_blocks_whole")


@dataclass(frozen=True)
class MellumConfig:
    """The source's ``config.json`` under the engine's names where it has
    one (the source's key in the comment)."""

    vocab_size: int = 98304
    layer_types: tuple = PUBLISHED_LAYER_TYPES  # a layer's attention
    d_model: int = 2304  # hidden_size
    n_head: int = 32  # num_attention_heads
    n_kv_head: int = 4  # num_key_value_heads
    head_dim: int = 128
    sliding_window: int = 1024  # keys a window layer's query sees, itself among them
    moe_intermediate_size: int = 896  # the width of ONE expert
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rope_theta: float = 500000.0  # rope_parameters: both kinds
    yarn_factor: float = 16.0  # rope_parameters.full_attention: factor
    original_max_position_embeddings: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.2772588722239782  # 0.1 ln 16 + 1
    max_seq_len: int = 131072  # max_position_embeddings
    layer_norm_epsilon: float = 1e-6  # rms_norm_eps
    prefill_chunk: int = 2048  # most tokens of one prefill program
    dtype: Any = jnp.bfloat16  # parameters and matmuls; norms, softmax and the router are float32

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def chunk_block(self) -> int:
        """Queries, and keys, a block of a window layer's chunk
        attention: at most half a window, so that a query block's window
        is three or four key blocks."""
        return min(512, self.sliding_window // 2)

    @property
    def ring_rows(self) -> int:
        """Positions a window layer's ring holds: the window less the
        fed token itself."""
        return self.sliding_window - 1

    @staticmethod
    def mellum2_12b_a2_5b(**kw) -> "MellumConfig":
        return MellumConfig(**kw)  # 12.15B parameters, 2.44B active a token: no one chip holds it

    @staticmethod
    def mellum2_12b_a2_5b_12l(**kw) -> "MellumConfig":
        """Layers 0-11 of the 28, three whole periods (full attention at
        3, 7, 11), every expert and the whole vocabulary: the first of
        three pipeline stages (12, 8, 8) and, so that it yields tokens,
        the head.  10.93 GB in bf16
        (benchmark/configs/mellum2-12b-a2.5b.json)."""
        return MellumConfig(**{**dict(layer_types=PUBLISHED_LAYER_TYPES[:12]), **kw})

    @staticmethod
    def mellum2_tiny(**kw) -> "MellumConfig":
        """Every width small, two periods, four queries a group; a
        window of 16 that a prompt of a few dozen tokens wraps several
        times, in chunks that need not divide it; YaRN's ramp inside the
        8 pairs of a head."""
        fields = dict(
            vocab_size=256, layer_types=PUBLISHED_LAYER_TYPES[:8], d_model=64, n_head=8, n_kv_head=2, head_dim=16,
            sliding_window=16, moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
            original_max_position_embeddings=32, max_seq_len=512, prefill_chunk=8)
        return MellumConfig(**{**fields, **kw})


# ----------------------------------------------------------------------
# the statement, the weights
# ----------------------------------------------------------------------
def cache_spec(cfg: MellumConfig, block_size: int) -> CacheSpec:
    """The full layers page K and V of the K/V heads; the window layers
    hold two rings a lane, K's and V's, of ``sliding_window`` rows a
    layer (whole pages of the engine's, so that the paged kernel reads
    them as a pool): a later family with a window states the same, two
    ``lane_state`` arrays of ``[window layers, window, row]`` beside
    ``paged_layers`` counting its other layers alone."""
    if cfg.sliding_window % block_size:
        raise ValueError(f"a window of {cfg.sliding_window} is not whole pages of {block_size}")
    row = cfg.n_kv_head * cfg.head_dim
    ring = (cfg.layer_types.count(SLIDING), cfg.sliding_window, row)
    return CacheSpec(paged_layers=cfg.layer_types.count(FULL), row_width=row,
                     lane_state=((RING_K, ring, cfg.dtype), (RING_V, ring, cfg.dtype)),
                     prefill_chunk=cfg.prefill_chunk)


def init_params(cfg: MellumConfig, rng=None):
    """Seeded weights (normal, std 0.02; norm weights 1) in cfg.dtype,
    made on the device one layer at a time, the experts one at a time
    within it."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    d, V, f, E, hd = cfg.d_model, cfg.vocab_size, cfg.moe_intermediate_size, cfg.num_experts, cfg.head_dim
    q_cols, kv_cols = cfg.n_head * hd, cfg.n_kv_head * hd

    def normal(key, *shape):
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(cfg.dtype)

    def ones(n):
        return jnp.ones((n,), cfg.dtype)

    @jax.jit
    def layer(key):
        k = jax.random.split(key, 5)
        return {
            "norm1": ones(d), "wqkv": normal(k[0], d, q_cols + 2 * kv_cols), "w_qn": ones(hd), "w_kn": ones(hd),
            "wo": normal(k[1], q_cols, d), "norm2": ones(d), "router": normal(k[2], d, E),
            "wgu": jax.lax.map(lambda e: normal(e, d, 2 * f), jax.random.split(k[3], E)),
            "wd": jax.lax.map(lambda e: normal(e, f, d), jax.random.split(k[4], E)),
        }

    @jax.jit
    def ends(key):
        k = jax.random.split(key, 2)
        return {"embed": normal(k[0], V, d), "norm": ones(d), "lm_head": normal(k[1], d, V)}

    keys = jax.random.split(rng, cfg.n_layer + 1)
    return {**ends(keys[0]), "layers": [layer(key) for key in keys[1:]]}


def serving_params(params, cfg: MellumConfig):
    """The tree a server holds, which ``init_params`` already makes."""
    return params


# ----------------------------------------------------------------------
# the layers' parts
# ----------------------------------------------------------------------
def _rotate(x, pos, cfg, kind):
    """x [N, heads, hd] at positions pos [N], by the layer's kind."""
    if kind == SLIDING:
        return rope(x, pos, cfg.rope_theta)
    table = yarn_inv_freq(cfg.rope_theta, cfg.head_dim, cfg.yarn_factor, cfg.original_max_position_embeddings,
                          cfg.beta_fast, cfg.beta_slow)
    return rope(x, pos, cfg.rope_theta, inv_freq=table, factor=cfg.attention_factor)


def _qkv(y, lp, cfg, pos, kind):
    """y [N, d] at positions pos [N] -> q [N, G, R, hd] and k, v [N, G,
    hd]: q and k normed per head, then rotated by the layer's kind."""
    H, G, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    q, k, v = jnp.split(y @ lp["wqkv"], [H * hd, (H + G) * hd], axis=-1)
    q = rmsnorm(q.reshape(-1, H, hd), lp["w_qn"], cfg.layer_norm_epsilon)
    k = rmsnorm(k.reshape(-1, G, hd), lp["w_kn"], cfg.layer_norm_epsilon)
    q, k = _rotate(q, pos, cfg, kind), _rotate(k, pos, cfg, kind)
    return q.reshape(-1, G, H // G, hd), k, v.reshape(-1, G, hd)


def window_chunk_attention(q, ctx_k, ctx_v, start, n_valid, cfg):
    """A window layer's prefill path: queries [T, G, R, hd] of the
    positions ``start ..`` over ``ctx_k``, ``ctx_v`` [C, G, hd], whose
    row c holds position ``start - ring_rows + c`` (the ring's rows in
    their positions' order, then the chunk's own; whole key blocks), a
    block of keys at a time inside an online softmax.  Query t is row
    ``ring_rows + t``: it sees the rows c with ``t <= c <= ring_rows +
    t`` whose position is not negative.  A block of keys wholly before a
    query block's first window, before position 0, past its last
    position or past the last real position is not visited.  -> [T, G *
    R * hd]."""
    T, G, R, hd = q.shape
    tq, kb, ring = min(T, cfg.chunk_block), cfg.chunk_block, cfg.ring_rows
    scale = hd ** -0.5
    first_row = jnp.maximum(ring - start, 0)  # the row of position 0, where the ring is not full yet
    outs = []
    for first in range(0, T, tq):
        qb = q[first:first + tq]
        q_row = ring + first + jnp.arange(tq)
        lo = jnp.maximum(first, first_row) // kb
        seen = ring + jnp.minimum(first + tq, n_valid)  # rows up to the block's last real query's own
        hi = jnp.where(first < n_valid, -(-seen // kb), lo)

        def body(j, carry, qb=qb, q_row=q_row):
            m, l, acc = carry
            k = jax.lax.dynamic_slice_in_dim(ctx_k, j * kb, kb)
            v = jax.lax.dynamic_slice_in_dim(ctx_v, j * kb, kb)
            s = jnp.einsum("tgrd,kgd->grtk", qb, k, preferred_element_type=jnp.float32) * scale
            row = (j * kb + jnp.arange(kb))[None, :]
            ok = (row <= q_row[:, None]) & (row > q_row[:, None] - cfg.sliding_window) & (row >= first_row)
            s = jnp.where(ok[None, None], s, _NEG)
            m_new = jnp.maximum(m, s.max(-1))
            alpha = jnp.exp(m - m_new)
            # a visited block may hold no key of some query's window: its
            # scores are all _NEG, m stays _NEG and exp(0) = 1 would count
            # them, so they are selected out, not left to the exponent
            p = jnp.where(ok[None, None], jnp.exp(s - m_new[..., None]), 0.0)
            l = alpha * l + p.sum(-1)
            acc = alpha[..., None] * acc + jnp.einsum(
                "grtk,kgd->grtd", p.astype(qb.dtype), v, preferred_element_type=jnp.float32)
            return m_new, l, acc

        init = (jnp.full((G, R, tq), _NEG, jnp.float32), jnp.zeros((G, R, tq), jnp.float32),
                jnp.zeros((G, R, tq, hd), jnp.float32))
        _, l, acc = jax.lax.fori_loop(lo, hi, body, init)
        # a block of pads alone visited nothing: l is 0 there, and its rows are dropped
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        outs.append(o.transpose(2, 0, 1, 3).reshape(tq, G * R * hd).astype(qb.dtype))
    return jnp.concatenate(outs) if len(outs) > 1 else outs[0]


def _experts(y, lp, cfg):
    """The expert part on normed tokens y [T, d]: what to add to the
    stream, ``moe_experts``' counters [pairs, hit, peak], and the experts
    the router chose [T, k]."""
    from ray_tpu.ops.moe import moe_experts

    with jax.named_scope("moe.route"):
        logits = jnp.dot(y, lp["router"], preferred_element_type=jnp.float32)
        top_p, top_e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.num_experts_per_tok)
        if cfg.norm_topk_prob:
            top_p = top_p / top_p.sum(-1, keepdims=True)
    out, c = moe_experts(y, top_p, top_e, lp["wgu"], lp["wd"])
    return out, c, top_e


def _counters(cfg, per_layer, window=0, full=0, unwindowed=0, gathered=0, blocks=(0, 0)):
    """COUNTERS of one program from its layers' [pairs, hit, peak] and
    what its attention read (``blocks``: the full layers',
    ``ops.attention.gqa_decode_blocks``)."""
    window, full = jnp.asarray(window, jnp.int32), jnp.asarray(full, jnp.int32)
    return jnp.concatenate([
        jnp.stack(per_layer).sum(0).astype(jnp.int32),
        jnp.stack([jnp.int32(cfg.num_experts * cfg.n_layer), jnp.int32(cfg.n_layer), window + full,
                   jnp.asarray(gathered, jnp.int32), window, full, jnp.asarray(unwindowed, jnp.int32),
                   *jnp.asarray(blocks, jnp.int32)])])


def _logits(x, params, cfg):
    return (rmsnorm(x, params["norm"], cfg.layer_norm_epsilon) @ params["lm_head"]).astype(jnp.float32)


# ----------------------------------------------------------------------
# the two forwards
# ----------------------------------------------------------------------
def prefill_chunk(params, cfg: MellumConfig, cache, tokens, start, last_index, table, lane, block_size: int):
    """``prefill_chosen`` less its last result: what the engine takes."""
    return prefill_chosen(params, cfg, cache, tokens, start, last_index, table, lane, block_size)[:-1]


def prefill_chosen(params, cfg: MellumConfig, cache, tokens, start, last_index, table, lane, block_size: int):
    """One chunk of one prompt: tokens [1, T] at positions ``start ..``,
    of which ``last_index[0] + 1`` are real; table [pages] the
    sequence's physical pages; lane the lane whose rings it holds.  The
    full layers read the earlier positions' K and V through the table,
    the window layers the lane's rings (whatever lies there of another
    sequence is behind the mask of positions before 0).  -> (logits [1,
    V] at ``last_index``, k, v [Lf, 1, T, G, hd] the chunk's rows of the
    full layers, {}, {"win_k", "win_v": the lane's rings [Lw, window,
    G * hd] with the chunk's last real rows written}, COUNTERS, and for
    the checks the experts each layer's router chose [L, T, k])."""
    T = tokens.shape[1]
    G, hd, ring = cfg.n_kv_head, cfg.head_dim, cfg.ring_rows
    n_valid = last_index[0] + 1
    pos = start + jnp.arange(T)
    x = params["embed"][tokens[0]]
    where, room = chunk_slots(table, block_size, T, K_BLOCK)  # the full layers' context
    # the window layers: the ring's rows in their positions' order (row c: position start - ring + c)
    ring_k, ring_v = cache[RING_K][lane], cache[RING_V][lane]  # [Lw, window, G * hd]
    order = (start + jnp.arange(ring)) % ring
    pad = -(ring + T) % cfg.chunk_block

    def window_context(held, i, rows):
        return jnp.concatenate([held[i][order].reshape(-1, G, hd), rows, jnp.zeros((pad, G, hd), rows.dtype)])

    ks, vs, win_k, win_v, counts, chose = [], [], [], [], [], []
    for lp, (kind, i) in zip(params["layers"], numbered(cfg.layer_types)):
        with jax.named_scope("attn.gqa"):
            q, k, v = _qkv(rmsnorm(x, lp["norm1"], cfg.layer_norm_epsilon), lp, cfg, pos, kind)
            if kind == FULL:
                att = chunk_attention(q, chunk_context(cache["k_pages"], i, where, room, k, start),
                                      chunk_context(cache["v_pages"], i, where, room, v, start), start, n_valid)
                ks.append(k)
                vs.append(v)
            else:
                att = window_chunk_attention(q, window_context(ring_k, i, k), window_context(ring_v, i, v),
                                             start, n_valid, cfg)
                win_k.append(k.reshape(T, G * hd))
                win_v.append(v.reshape(T, G * hd))
            x = x + att @ lp["wo"]
        out, c, top_e = _experts(rmsnorm(x, lp["norm2"], cfg.layer_norm_epsilon), lp, cfg)
        counts.append(c)
        chose.append(top_e)
        x = x + out
    # the chunk's last ring_rows real rows to their places; the others (pads, rows a later one of this
    # chunk overwrites) to the ring's last row, which nothing reads
    t = jnp.arange(T)
    slot = jnp.where((t < n_valid) & (t >= n_valid - ring), pos % ring, ring)
    state = {RING_K: ring_k.at[:, slot].set(jnp.stack(win_k)), RING_V: ring_v.at[:, slot].set(jnp.stack(win_v))}
    return (_logits(x[last_index], params, cfg), jnp.stack(ks)[:, None], jnp.stack(vs)[:, None], {}, state,
            _counters(cfg, counts), jnp.stack(chose))


def decode_forward_cached(params, cfg: MellumConfig, cache, tok, block_tables, lengths, block_size: int):
    """``decode_chosen`` less its last result: what the engine takes."""
    return decode_chosen(params, cfg, cache, tok, block_tables, lengths, block_size)[:-1]


def decode_chosen(params, cfg: MellumConfig, cache, tok, block_tables, lengths, block_size: int):
    """One decode step: tok [B] at positions lengths [B] (a lane's
    cached positions; 0: the lane does not run), block_tables [B,
    pages].  The full layers read the lanes' pages where they lie; the
    window layers read the lanes' rings where they lie, through the same
    kernel, and the step's rows go into the rings in place.  -> (logits
    [B, V], k_new, v_new [Lf, B, G, hd], {}, {"win_k", "win_v": the
    whole rings}, COUNTERS, and for the checks the experts each layer's
    router chose [L, B, k])."""
    from ray_tpu.ops.attention import gqa_decode_blocks, gqa_paged_decode_attention

    B = tok.shape[0]
    window, ring = cfg.sliding_window, cfg.ring_rows
    n_w, n_f = cfg.layer_types.count(SLIDING), cfg.layer_types.count(FULL)
    runs = lengths > 0
    x = params["embed"][tok]
    # the rings as a pool of one layer: lane b's layer i is pages (b * n_w + i) * pages_w ..
    pages_w = window // block_size
    pool_k = cache[RING_K].reshape(1, B * n_w * window, -1)
    pool_v = cache[RING_V].reshape(1, B * n_w * window, -1)
    held = jnp.minimum(lengths, ring)  # the cached positions inside the fed token's window
    ks, vs, win_k, win_v, counts, chose = [], [], [], [], [], []
    for lp, (kind, i) in zip(params["layers"], numbered(cfg.layer_types)):
        with jax.named_scope("attn.gqa"):
            q, k, v = _qkv(rmsnorm(x, lp["norm1"], cfg.layer_norm_epsilon), lp, cfg, lengths, kind)
            if kind == FULL:
                o = gqa_paged_decode_attention(q, k, v, cache["k_pages"], cache["v_pages"], i, block_tables, lengths,
                                               block_size=block_size)
                ks.append(k)
                vs.append(v)
            else:
                tables = ((jnp.arange(B) * n_w + i) * pages_w)[:, None] + jnp.arange(pages_w)
                o = gqa_paged_decode_attention(q, k, v, pool_k, pool_v, 0, tables, held, block_size=block_size)
                win_k.append(k.reshape(B, -1))
                win_v.append(v.reshape(B, -1))
            x = x + o.reshape(B, -1) @ lp["wo"]
        out, c, top_e = _experts(rmsnorm(x, lp["norm2"], cfg.layer_norm_epsilon), lp, cfg)
        counts.append(c)
        chose.append(top_e)
        x = x + out
    # one row a lane a window layer, where the rings lie (a lane that does not run: the unread last row)
    slot = jnp.where(runs, lengths % ring, ring)
    at = ((jnp.arange(B)[:, None] * n_w + jnp.arange(n_w)) * window + slot[:, None]).reshape(-1)

    def written(pool, rows):
        return pool[0].at[at].set(jnp.stack(rows, axis=1).reshape(B * n_w, -1)).reshape(B, n_w, window, -1)

    def copied(n):
        """Positions of the whole pages the kernel copies for n cached."""
        return (-(-n // block_size) * block_size).sum()

    state = {RING_K: written(pool_k, win_k), RING_V: written(pool_v, win_v)}
    return (_logits(x, params, cfg), jnp.stack(ks), jnp.stack(vs), {}, state,
            _counters(cfg, counts, window=held.sum() * n_w, full=lengths.sum() * n_f,
                      unwindowed=lengths.sum() * (n_w + n_f),
                      gathered=copied(held) * n_w + copied(lengths) * n_f,
                      blocks=gqa_decode_blocks(cache["k_pages"], lengths, block_size, n_f)), jnp.stack(chose))

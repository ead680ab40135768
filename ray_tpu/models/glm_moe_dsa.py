"""GLM-5 (zai-org/GLM-5, ``model_type: glm_moe_dsa``) for the serving
engine: a decoder of RMSNorm blocks whose attention caches ONE latent
row a position for all its heads and attends a CHOICE of the earlier
positions, made by a small index network with a cache of its own; and
whose feed-forward is a dense SwiGLU in the leading layers and, after
them, a shared SwiGLU expert beside routed ones of which a chip may
hold a share.

The layer, on tokens ``x [T, 6144]`` (RMSNorm eps 1e-5 with a weight,
no biases; norms, softmax, sigmoid, index scores and their ordering in
float32, matmuls in the serving dtype with float32 accumulation):

1. ``h = rmsnorm(x)``; ``c_q = rmsnorm(h W_dq)`` (2048); ``q = c_q
   W_uq``: 64 heads of ``[q_nope 192 | q_rope 64]``; ``[c | k_r] = h
   W_dkv`` (512 | 64); ``c = rmsnorm(c)``.  ``q_rope`` and ``k_r`` (one
   for all heads) are rotated at the token's position over interleaved
   pairs ``(2i, 2i + 1)``, plain frequencies ``1e6^(-2i/64)``.
2. The indexer.  ``q_I = c_q W_qI``: 32 heads of 128; ``k_I =
   layernorm(h W_kI)`` (128, one for all heads, weight and bias); the
   first 64 values of every ``q_I`` head and of ``k_I`` rotated like
   step 1; ``w = (h W_w) 32^-0.5 128^-0.5`` (32).  ``I(t, s) = sum_j
   w_j(t) relu(q_I,j(t) . k_I(s))`` for ``s <= t``; ``S(t)`` the
   ``index_topk`` = 2,048 positions of largest ``I(t, .)``, the token's
   own among the candidates, a tie at the last going to the lower
   position; every ``s <= t`` while ``t < 2048`` (``ops/dsa.py``).
3. ``[k_nope 192 | v 256]`` of head ``i`` is ``c W_ukv[i]``.  Query ``t``
   scores ``(q_nope . k_nope + q_rope . k_r) 256^-0.5`` on ``s in
   S(t)`` ONLY; softmax over ``S(t)``, times ``v``, heads side by side,
   ``W_o``.
4. The first ``first_k_dense_replace`` layers: ``h = rmsnorm(x)``, SwiGLU
   of width 12,288.  The others: ``s = sigmoid(h W_r)`` over ALL 256
   experts; the 8 largest of ``s + b`` (the bias chooses and does not
   weigh); ``g_e = 2.5 s_e / sum of the chosen s``; ``y =
   SwiGLU_shared(h) + sum g_e SwiGLU_e(h)`` over those of the token's 8
   that are HELD here (``experts_first``, ``experts_held``).  What the
   absent experts would add is left out: on the chips of a deployment
   that share a layer the partial sums add up (``ops/moe.py``).
5. After the last layer RMSNorm and the untied head over the rows of
   the vocabulary held.

The cache is two paged pools a layer, addressed by one block table
(``cache_spec``): ``k_pages``, the row ``[c | k_r]`` (576 values, a
position's keys all 576 and its values the first 512) laid out as
``latent_row`` = 640 columns (whole lane tiles, as ``mistral4.py``
says), with no V pool; and ``index_k``, the 128 values of ``k_I``, a row
a position, which EVERY later query of the sequence reads.  Two paths
read them:

- *prefill* (``prefill_chunk``): a chunk of a prompt at ``start``.  The
  sequence's rows of both pools are gathered through the block table,
  the chunk's own put in at ``start``; index scores, the choice and the
  attention go through ``ops.dsa.sparse_chunk_attention`` (``k_nope`` and
  ``v`` EXPANDED a block of keys at a time inside an online softmax,
  unchosen positions masked).
- *decode* (``decode_forward_cached``): the index scores of a lane's
  cached positions by ``ops.attention.dsa_index_paged_scores`` over the
  pages where they lie, the choice by ``ops.dsa.keep_mask`` over the
  table's whole width (twenty lanes of different lengths have no reach in
  common worth a loop: a pass over them is 40 us), and the
  ABSORBED attention (``ops.mla.absorbed_queries``) over the chosen rows
  by ``ops.attention.mla_sparse_paged_decode_attention``.

The tree, which ``benchmark/reference_glm_5.py`` reads too: ``embed [V,
d]``, ``layers`` (each ``w_in [d]``, ``wdq [d, 2048]``, ``w_qn [2048]``,
``wuq [2048, 64 * 256]``, ``wdkv [d, 576]``, ``w_kvn [512]``, ``wukv
[512, 64 * 448]`` (a head's ``k_nope | v`` side by side), ``wo [64 *
256, d]``, ``wq_idx [2048, 32 * 128]``, ``wk_idx [d, 128]``, ``k_idx_w``
and ``k_idx_b [128]`` (the LayerNorm), ``w_idx [d, 32]``, ``w_post
[d]``; then a dense layer's ``wgu_dense [d, 2 * 12288]``, ``wd_dense
[12288, d]``, or an expert layer's ``router [d, 256]``, ``router_bias
[256]`` float32, ``wgu_shared [d, 2f]``, ``wd_shared [f, d]``, ``wgu
[held, d, 2f]``, ``wd [held, f, d]``), ``norm [d]``, ``lm_head [d, V]``.
Weights are seeded random, made on the device a layer at a time in the
serving dtype.  There is no training path, and the multi-token
prediction layer is not served.

ASSUMED, because the source's ``config.json`` does not carry it (the
file ``benchmark/configs/glm-5.json`` lists the same): bf16 parameters;
``router_bias`` seeded normal, std 0.02; the indexer rotates the FIRST
64 values of a head, its LayerNorm's eps is 1e-6, the scale of ``w``;
the family's Hadamard rotation and FP8 storage of ``q_I`` / ``k_I`` are
left out; a tie at the 2,048th score goes to the lower position.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models.common import CacheSpec, rmsnorm
from ray_tpu.models.layers import chunk_context, chunk_slots, counters
from ray_tpu.ops import dsa
from ray_tpu.ops.mla import absorbed_queries, rope_interleaved

# What a forward returns after what it writes, summed over its layers.
# Under mistral4.COUNTERS' names and meanings, over the EXPERT layers (a
# dense layer counts nowhere): token-expert pairs the router made; those
# whose expert is held here; pairs computed; held experts that received a
# row; held experts there were; rows of the largest group; layers.  Of a
# decode step the chosen cached positions its attention attended and the
# positions of the whole pages its walk copied (every page a lane holds:
# the choice masks scores, it does not spare copies).  Then
# the index's own, prefill and decode apart: a query's candidates (the
# positions up to its own) and the positions it attended, summed over
# real queries and layers; and the cached positions a decode step's
# index kernel scored.  Last the scores the choice's passes read: of a
# chunk a tile's queries times the columns of the key blocks it can reach
# (0 of a tile that reaches at most ``index_topk`` positions and counts
# nothing), of a decode step every column of the table for each lane that
# runs; over layers.
COUNTERS = ("moe_pairs_routed", "moe_pairs_held", "moe_pairs", "moe_experts_hit",
            "moe_expert_slots", "moe_peak_rows", "moe_layer_programs",
            "kv_positions_attended", "kv_positions_gathered",
            "dsa_positions_cached_prefill", "dsa_positions_kept_prefill",
            "dsa_positions_cached", "dsa_positions_kept", "dsa_index_positions_scored",
            "dsa_select_columns_prefill", "dsa_select_columns")

_LANE = 128  # columns of a lane tile: a cached row is whole tiles


@dataclass(frozen=True)
class GlmMoeDsaConfig:
    """The source's ``config.json`` under the engine's names where it
    has one, the source's own elsewhere; then the share held here."""

    vocab_size: int = 154880  # rows of the vocabulary HELD (the engine's name); ids are below it
    published_vocab_size: int = 154880
    vocab_first: int = 0  # the first published row held
    n_layer: int = 78  # num_hidden_layers, the leading dense ones among them
    first_k_dense_replace: int = 3
    n_head: int = 64  # num_attention_heads = num_key_value_heads
    d_model: int = 6144  # hidden_size
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    intermediate_size: int = 12288  # the width of a leading dense layer
    moe_intermediate_size: int = 2048  # the width of ONE expert, routed or shared
    n_routed_experts: int = 256  # the router's outputs, whatever is held here
    experts_first: int = 0  # the first routed expert held
    experts_held: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    max_seq_len: int = 202752  # max_position_embeddings
    rms_norm_eps: float = 1e-5
    index_norm_eps: float = 1e-6  # the indexer's LayerNorm (assumed)
    rope_theta: float = 1000000.0  # rope_parameters: rope_type default
    prefill_chunk: int = 4096  # most tokens of one prefill program
    dtype: Any = jnp.bfloat16  # parameters and matmuls; norms, softmaxes, the router and the index are float32

    @property
    def latent_row(self) -> int:
        """Columns of a cached row: ``[c | k_r]`` and zeros up to whole
        lane tiles (576 -> 640)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // _LANE) * _LANE

    @property
    def index_rope_dim(self) -> int:
        """The values of an index head that are rotated: its first
        ``qk_rope_head_dim``."""
        return self.qk_rope_head_dim

    @staticmethod
    def glm5(**kw) -> "GlmMoeDsaConfig":
        return GlmMoeDsaConfig(**kw)  # 743.9B parameters: no one chip builds it

    @staticmethod
    def glm5_6l_ep16(**kw) -> "GlmMoeDsaConfig":
        """One chip's share of sixteen that share each layer: ONE leading
        dense layer and five expert layers of the 78, routed experts 0-15
        of 256, rows 0-19,359 of the vocabulary; attention, the indexer
        and the shared expert whole.  9.45 GB in bf16
        (benchmark/configs/glm-5.json)."""
        return GlmMoeDsaConfig(**{**dict(n_layer=6, first_k_dense_replace=1, experts_held=16,
                                         vocab_size=19360), **kw})

    @staticmethod
    def glm5_tiny(**kw) -> "GlmMoeDsaConfig":
        """Every width small, one dense layer before two expert layers;
        4 of 16 experts' shares are what the tests cut it into.
        ``index_topk`` 16, so a prompt of a few dozen tokens passes it
        and the choice chooses."""
        fields = dict(
            vocab_size=256, published_vocab_size=256, n_layer=3, first_k_dense_replace=1, n_head=8,
            d_model=64, q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, index_n_heads=8, index_head_dim=16, index_topk=16, intermediate_size=96,
            moe_intermediate_size=32, n_routed_experts=16, experts_held=16, num_experts_per_tok=4,
            max_seq_len=512, prefill_chunk=64)
        return GlmMoeDsaConfig(**{**fields, **kw})


# ----------------------------------------------------------------------
# positions, scales
# ----------------------------------------------------------------------
def inv_freq(cfg: GlmMoeDsaConfig) -> list:
    """The plain rotary frequency of each of the ``qk_rope_head_dim / 2``
    pairs, as Python floats (``rope_type: default``)."""
    d = cfg.qk_rope_head_dim
    return [cfg.rope_theta ** (-2.0 * i / d) for i in range(d // 2)]


def softmax_scale(cfg: GlmMoeDsaConfig) -> float:
    """``(nope + rope)^-0.5``: 0.0625 at the published sizes."""
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def index_weight_scale(cfg: GlmMoeDsaConfig) -> float:
    """What ``h W_w`` is multiplied by: ``heads^-0.5 head_dim^-0.5``."""
    return cfg.index_n_heads ** -0.5 * cfg.index_head_dim ** -0.5


def _rope(x, pos, cfg):
    return rope_interleaved(x, pos, inv_freq(cfg))


def _rope_first(x, n, pos, cfg):
    """The first n values of x's last dim rotated, the others as they are."""
    return x if n == 0 else jnp.concatenate([_rope(x[..., :n], pos, cfg), x[..., n:]], axis=-1)


def _is_dense(cfg, i) -> bool:
    return i < cfg.first_k_dense_replace


# ----------------------------------------------------------------------
# the statement, the weights
# ----------------------------------------------------------------------
def cache_spec(cfg: GlmMoeDsaConfig, block_size: int) -> CacheSpec:
    """A pool of latent rows with no V pool (a position's values are its
    row's first ``kv_lora_rank`` columns) and, beside it under the same
    block table, a pool of index keys, a row a position."""
    return CacheSpec(paged_layers=cfg.n_layer, row_width=cfg.latent_row, prefill_chunk=cfg.prefill_chunk,
                     v_pool=False, page_extras=(("index_k", block_size, cfg.index_head_dim, cfg.dtype),))


def init_params(cfg: GlmMoeDsaConfig, rng=None):
    """Seeded weights (normal, std 0.02; norm weights 1, the LayerNorm's
    bias 0, the router's bias normal std 0.02 in float32) in cfg.dtype,
    made on the device one layer at a time, the held experts one at a
    time within it."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    d, f, V, H = cfg.d_model, cfg.moe_intermediate_size, cfg.vocab_size, cfg.n_head
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    kv, Hi, Di = cfg.kv_lora_rank, cfg.index_n_heads, cfg.index_head_dim

    def normal(key, *shape):
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(cfg.dtype)

    def ones(n):
        return jnp.ones((n,), cfg.dtype)

    def attention(k):
        return {
            "w_in": ones(d), "wdq": normal(k[0], d, cfg.q_lora_rank), "w_qn": ones(cfg.q_lora_rank),
            "wuq": normal(k[1], cfg.q_lora_rank, H * qk),
            "wdkv": normal(k[2], d, kv + cfg.qk_rope_head_dim), "w_kvn": ones(kv),
            "wukv": normal(k[3], kv, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "wo": normal(k[4], H * cfg.v_head_dim, d),
            "wq_idx": normal(k[5], cfg.q_lora_rank, Hi * Di), "wk_idx": normal(k[6], d, Di),
            "k_idx_w": ones(Di), "k_idx_b": jnp.zeros((Di,), cfg.dtype), "w_idx": normal(k[7], d, Hi),
            "w_post": ones(d),
        }

    @jax.jit
    def dense_layer(key):
        k = jax.random.split(key, 10)
        return {**attention(k), "wgu_dense": normal(k[8], d, 2 * cfg.intermediate_size),
                "wd_dense": normal(k[9], cfg.intermediate_size, d)}

    @jax.jit
    def expert_layer(key):
        k = jax.random.split(key, 14)
        held = cfg.experts_held
        return {
            **attention(k),
            "router": normal(k[8], d, cfg.n_routed_experts),
            "router_bias": 0.02 * jax.random.normal(k[9], (cfg.n_routed_experts,), jnp.float32),
            "wgu_shared": normal(k[10], d, 2 * f * cfg.n_shared_experts),
            "wd_shared": normal(k[11], f * cfg.n_shared_experts, d),
            "wgu": jax.lax.map(lambda e: normal(e, d, 2 * f), jax.random.split(k[12], held)),
            "wd": jax.lax.map(lambda e: normal(e, f, d), jax.random.split(k[13], held)),
        }

    @jax.jit
    def ends(key):
        k = jax.random.split(key, 2)
        return {"embed": normal(k[0], V, d), "norm": ones(d), "lm_head": normal(k[1], d, V)}

    keys = jax.random.split(rng, cfg.n_layer + 1)
    layers = [(dense_layer if _is_dense(cfg, i) else expert_layer)(key) for i, key in enumerate(keys[1:])]
    return {**ends(keys[0]), "layers": layers}


def serving_params(params, cfg: GlmMoeDsaConfig):
    """The tree a server holds, which ``init_params`` already makes."""
    return params


# ----------------------------------------------------------------------
# the layer's halves
# ----------------------------------------------------------------------
def _project(h, lp, cfg, pos):
    """h [N, d] at positions pos [N] -> q_nope, q_rope [N, H, .] with the
    softmax scale in them (applied in float32); the row to cache [N,
    latent_row] (the normed latent, the rotated shared key, zeros); the
    index queries [N, Hi, Di], their heads' weights [N, Hi] float32, and
    the index key to cache [N, Di]."""
    N, H, Hi, Di = h.shape[0], cfg.n_head, cfg.index_n_heads, cfg.index_head_dim
    nope, rope, kv, ir = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank, cfg.index_rope_dim
    with jax.named_scope("mla.project"):
        c_q = rmsnorm(h @ lp["wdq"], lp["w_qn"], cfg.rms_norm_eps)
        q = (c_q @ lp["wuq"]).reshape(N, H, nope + rope)
        q = (q.astype(jnp.float32) * softmax_scale(cfg)).astype(q.dtype)
        ckr = h @ lp["wdkv"]
        c = rmsnorm(ckr[:, :kv], lp["w_kvn"], cfg.rms_norm_eps)
        k_r = _rope(ckr[:, kv:], pos, cfg)
        row = jnp.concatenate([c, k_r, jnp.zeros((N, cfg.latent_row - kv - rope), c.dtype)], axis=-1)
    with jax.named_scope("dsa.project"):
        q_i = (c_q @ lp["wq_idx"]).reshape(N, Hi, Di)
        q_i = _rope_first(q_i, ir, pos[:, None], cfg)
        k = (h @ lp["wk_idx"]).astype(jnp.float32)
        mean = k.mean(-1, keepdims=True)
        k = (k - mean) * jax.lax.rsqrt(jnp.square(k - mean).mean(-1, keepdims=True) + cfg.index_norm_eps)
        k = (k * lp["k_idx_w"].astype(jnp.float32) + lp["k_idx_b"].astype(jnp.float32)).astype(h.dtype)
        k_i = _rope_first(k, ir, pos, cfg)
        w = jnp.dot(h, lp["w_idx"], preferred_element_type=jnp.float32) * index_weight_scale(cfg)
    return q[..., :nope], _rope(q[..., nope:], pos[:, None], cfg), row, q_i, w, k_i


def route(h, lp, cfg):
    """The router on normed tokens h [T, d]: a token's weights [T, k]
    float32 and experts [T, k].  ``sigmoid`` over all the experts; the k
    largest of score + bias; the weights the chosen SCORES over their
    sum, times ``routed_scaling_factor``."""
    scores = jax.nn.sigmoid(jnp.dot(h, lp["router"], preferred_element_type=jnp.float32))
    _, top_e = jax.lax.top_k(scores + lp["router_bias"], cfg.num_experts_per_tok)
    top_p = jnp.take_along_axis(scores, top_e, axis=-1)
    if cfg.norm_topk_prob:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    return top_p * cfg.routed_scaling_factor, top_e


def _feed_forward(x, lp, cfg, dense):
    """The second half of a block on tokens x [T, d]: what to add to x;
    an expert layer's counters [routed, held, computed, hit, peak] (None
    of a dense layer); and the experts the router chose [T, k] (-1 of a
    dense layer)."""
    from ray_tpu.ops.moe import moe_experts

    h = rmsnorm(x, lp["w_post"], cfg.rms_norm_eps)
    if dense:
        with jax.named_scope("mlp.dense"):
            gate, up = jnp.split(h @ lp["wgu_dense"], 2, axis=-1)
            y = (jax.nn.silu(gate) * up) @ lp["wd_dense"]
        return y, None, jnp.full((x.shape[0], cfg.num_experts_per_tok), -1, jnp.int32)
    with jax.named_scope("moe.route"):
        top_p, top_e = route(h, lp, cfg)
        here = (top_e >= cfg.experts_first) & (top_e < cfg.experts_first + cfg.experts_held)
    with jax.named_scope("moe.shared"):
        gate, up = jnp.split(h @ lp["wgu_shared"], 2, axis=-1)
        shared = (jax.nn.silu(gate) * up) @ lp["wd_shared"]
    held = None if cfg.experts_held == cfg.n_routed_experts else (cfg.experts_first, cfg.experts_held)
    y, c = moe_experts(h, top_p, top_e, lp["wgu"], lp["wd"], held=held)
    routed = jnp.int32(top_e.size)
    return shared + y, jnp.concatenate([jnp.stack([routed, here.sum(dtype=jnp.int32)]), c]), top_e


def _logits(x, params, cfg):
    return (rmsnorm(x, params["norm"], cfg.rms_norm_eps) @ params["lm_head"]).astype(jnp.float32)


def prefill_chunk(params, cfg: GlmMoeDsaConfig, cache, tokens, start, last_index, table, lane,
                  block_size: int):
    """``prefill_chosen`` less its last two results: what the engine takes."""
    return prefill_chosen(params, cfg, cache, tokens, start, last_index, table, lane, block_size)[:-2]


def prefill_chosen(params, cfg: GlmMoeDsaConfig, cache, tokens, start, last_index, table, lane,
                   block_size: int):
    """One chunk of one prompt: tokens [1, T] at positions ``start ..``,
    of which ``last_index[0] + 1`` are real; table [pages] the
    sequence's physical pages.  Reads the earlier positions' latent rows
    and index keys through the table.  -> (logits [1, V] at
    ``last_index``, the chunk's rows [L, 1, T, latent_row], None (no V
    pool), {"index_k": (the chunk's index keys [L, T, Di], their slots
    [T])}, {}, COUNTERS, and for the checks the experts each layer's
    router chose [L, T, k] (-1 in a dense layer) and the positions each
    layer's index chose, as a mask [L, T, C])."""
    T = tokens.shape[1]
    n_valid = last_index[0] + 1
    x = params["embed"][tokens[0]]
    pos = start + jnp.arange(T)
    where, room = chunk_slots(table, block_size, T, dsa.KEY_BLOCK)
    C = where.shape[0]
    # where the chunk's index keys go: a real token's own slot, the scratch slot at pads
    slots = jnp.where(jnp.arange(T) < n_valid, where[jnp.minimum(pos, C - 1)], 0)
    rows_out, keys_out, counts, chose, masks, kept, columns = [], [], [], [], [], [], []
    for i, lp in enumerate(params["layers"]):
        h = rmsnorm(x, lp["w_in"], cfg.rms_norm_eps)
        q_nope, q_rope, row, q_i, w, k_i = _project(h, lp, cfg, pos)
        ctx = chunk_context(cache["k_pages"], i, where, room, row, start)
        k_ctx = chunk_context(cache["index_k"], i, where, room, k_i, start)
        att, n_kept, n_columns, mask = dsa.sparse_chunk_attention(
            q_nope, q_rope, q_i, w, ctx, k_ctx, lp["wukv"], start, n_valid, cfg, cfg.index_topk)
        x = x + att @ lp["wo"]
        y, c, top_e = _feed_forward(x, lp, cfg, _is_dense(cfg, i))
        # the layer's counters with its output: left to the scheduler, the count of the pairs
        # computed is taken at the program's end and every layer's [T * k, d] rows live until then
        y, c = jax.lax.optimization_barrier((y, c))
        x = x + y
        rows_out.append(row)
        keys_out.append(k_i)
        chose.append(top_e)
        masks.append(mask)
        kept.append(n_kept)
        columns.append(n_columns)
        if c is not None:
            counts.append(c)
    candidates = jnp.where(jnp.arange(T) < n_valid, pos + 1, 0).sum() * cfg.n_layer
    return (_logits(x[last_index], params, cfg), jnp.stack(rows_out)[:, None], None,
            {"index_k": (jnp.stack(keys_out), slots)}, {},
            counters(COUNTERS, counts, cfg.experts_held, dsa_positions_cached_prefill=candidates,
                     dsa_positions_kept_prefill=jnp.stack(kept).sum(),
                     dsa_select_columns_prefill=jnp.stack(columns).sum()),
            jnp.stack(chose), jnp.stack(masks))


def decode_forward_cached(params, cfg: GlmMoeDsaConfig, cache, tok, block_tables, lengths,
                          block_size: int):
    """``decode_chosen`` less its last two results: what the engine takes."""
    return decode_chosen(params, cfg, cache, tok, block_tables, lengths, block_size)[:-2]


def decode_chosen(params, cfg: GlmMoeDsaConfig, cache, tok, block_tables, lengths, block_size: int):
    """One decode step: tok [B] at positions lengths [B] (a lane's
    cached positions), block_tables [B, pages].  Every layer scores the
    lanes' index keys where they lie, chooses, and attends the chosen
    latent rows, absorbed.  -> (logits [B, V], the fed tokens' rows [L, B,
    latent_row], None, {"index_k": (their index keys [L, B, Di], their
    slots [B])}, {}, COUNTERS, and for the checks the experts each
    layer's router chose [L, B, k] and the positions each layer's index
    chose, as a mask [L, B, pages * block_size] (column ``lengths`` the
    fed token's own))."""
    from ray_tpu.ops.attention import dsa_index_paged_scores, mla_sparse_paged_decode_attention

    B, H = tok.shape[0], cfg.n_head
    nope, kv, dv = cfg.qk_nope_head_dim, cfg.kv_lora_rank, cfg.v_head_dim
    C = block_tables.shape[1] * block_size
    valid = jnp.arange(C)[None, :] <= lengths[:, None]
    x = params["embed"][tok]
    rows_out, keys_out, counts, chose, picked = [], [], [], [], []
    for i, lp in enumerate(params["layers"]):
        h = rmsnorm(x, lp["w_in"], cfg.rms_norm_eps)
        q_nope, q_rope, row, q_i, w, k_i = _project(h, lp, cfg, lengths)
        with jax.named_scope("dsa.index"):
            scores = dsa_index_paged_scores(q_i, w, k_i, cache["index_k"], i, block_tables, lengths,
                                            block_size=block_size)
        keep = dsa.keep_mask(scores, valid, cfg.index_topk)
        with jax.named_scope("mla.absorb"):
            q = absorbed_queries(q_nope, q_rope, lp["wukv"], cfg)
        with jax.named_scope("dsa.attend"):
            o_lat = mla_sparse_paged_decode_attention(
                q, row, cache["k_pages"], i, block_tables, keep, lengths, block_size=block_size, v_width=kv)
        with jax.named_scope("mla.absorb"):
            w_uv = lp["wukv"].reshape(kv, H, nope + dv)[..., nope:]
            att = jnp.einsum("bhc,chd->bhd", o_lat, w_uv).reshape(B, H * dv)
        x = x + att @ lp["wo"]
        y, c, top_e = _feed_forward(x, lp, cfg, _is_dense(cfg, i))
        x = x + y
        rows_out.append(row)
        keys_out.append(k_i)
        chose.append(top_e)
        picked.append(keep)
        if c is not None:
            counts.append(c)
    page = jnp.take_along_axis(block_tables, (lengths // block_size)[:, None], axis=1)[:, 0]
    slots = page * block_size + lengths % block_size
    # of the chosen positions, the cached ones (a lane that does not run has length 0 and keeps its own alone)
    masks = jnp.stack(picked)
    n_kept = masks.sum(dtype=jnp.int32)
    n_cached = n_kept - jnp.take_along_axis(masks, lengths[None, :, None], axis=2).sum(dtype=jnp.int32)
    running = lengths > 0
    pages = -(-lengths // block_size) * block_size  # the whole pages the attention's walk copies
    return (_logits(x, params, cfg), jnp.stack(rows_out), None, {"index_k": (jnp.stack(keys_out), slots)}, {},
            counters(COUNTERS, counts, cfg.experts_held, kv_positions_attended=n_cached,
                     kv_positions_gathered=pages.sum() * cfg.n_layer,
                     dsa_positions_cached=jnp.where(running, lengths + 1, 0).sum() * cfg.n_layer,
                     dsa_positions_kept=n_kept - (~running).sum() * cfg.n_layer,
                     dsa_index_positions_scored=lengths.sum() * cfg.n_layer,
                     dsa_select_columns=running.sum() * C * cfg.n_layer),
            jnp.stack(chose), masks)

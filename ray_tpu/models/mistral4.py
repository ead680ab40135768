"""Mistral-Small-4 (mistralai/Mistral-Small-4-119B-2603, ``model_type:
mistral4``) for the serving engine: a decoder of RMSNorm blocks whose
attention caches ONE latent row a position for all its heads, and whose
feed-forward is a shared SwiGLU expert beside routed ones of which a
chip may hold a share.

The layer, on tokens ``x [T, 4096]`` (RMSNorm eps 1e-6 with a weight,
no biases; norms, softmaxes and the router in float32, matmuls in the
serving dtype with float32 accumulation):

1. ``h = rmsnorm(x)``; ``c_q = rmsnorm(h W_dq)`` (1024); ``q = c_q
   W_uq``: 32 heads of ``[q_nope 64 | q_rope 64]``; ``[c | k_r] = h
   W_dkv`` (256 | 64); ``c = rmsnorm(c)``.
2. ``q_rope`` and ``k_r`` (one for all heads) are rotated at the token's
   position over interleaved pairs ``(2i, 2i + 1)``, frequencies YaRN
   (``yarn_inv_freq``).
3. ``[k_nope 64 | v 128]`` of head ``i`` is ``c W_ukv[i]``.  Query ``t``
   on position ``s <= t`` scores ``a(t) (q_nope . k_nope + q_rope . k_r)
   128^-0.5 m^2`` (``softmax_scale``, ``query_scale``); softmax, times
   ``v``, heads side by side, ``W_o``.
4. ``h = rmsnorm(x)``; ``p = softmax(h W_r)`` over ALL 128 experts; the
   top 4 divided by their sum; ``y = SwiGLU_shared(h) + sum p_e
   SwiGLU_e(h)`` over those of the token's 4 experts that are HELD here
   (``experts_first``, ``experts_held``).  What the absent experts would
   add is left out: on the chips of a deployment that share a layer the
   partial sums add up (``ops/moe.py``).
5. After the last layer RMSNorm and the untied head over the rows of
   the vocabulary held.

The cache is the row ``[c | k_r]`` (320 values, of which a position's
keys are all 320 and its values the first 256), laid out as
``latent_row`` = 384 columns: a row of bf16 that is not whole lane tiles
of 128 is either padded so by the compiler or laid out positions-minor,
and the engine's write then re-lays the whole pool out every program.
There is no V pool (``cache_spec``).  Two attention paths read it:

- *prefill* (``prefill_chunk``): a chunk of a prompt at ``start``.  The
  sequence's rows are gathered through the block table, the chunk's own
  put in at ``start``, and ``k_nope`` and ``v`` EXPANDED from them a
  block of keys at a time inside an online softmax; blocks wholly above
  the diagonal are not visited (``ops.mla.expanded_attention``, which
  ``models/glm_moe_dsa.py`` shares; with no mask, on a TPU, one kernel:
  ``ops/pallas_mla_chunk_attention.py``).
- *decode* (``decode_forward_cached``): ABSORBED
  (``ops.mla.absorbed_queries``).  ``q_lat = q_nope
  W_uk[i]^T`` (32 x 256), scores ``q_lat . c + q_rope . k_r`` against
  the latent pages read in place by
  ``ops.attention.mla_paged_decode_attention``, ``o_lat = softmax . c``,
  then ``o = o_lat W_uv[i]``.  The cache is never expanded.

The tree, which ``benchmark/reference_mistral_small_4.py`` reads too:
``embed [V, d]``, ``layers`` (each ``w_in [d]``, ``wdq [d, 1024]``,
``w_qn [1024]``, ``wuq [1024, 32 * 128]``, ``wdkv [d, 320]``, ``w_kvn
[256]``, ``wukv [256, 32 * 192]`` (a head's ``k_nope | v`` side by
side), ``wo [32 * 128, d]``, ``w_post [d]``, ``router [d, 128]``,
``wgu_shared [d, 2f]``, ``wd_shared [f, d]``, ``wgu [held, d, 2f]``,
``wd [held, f, d]``), ``norm [d]``, ``lm_head [d, V]``.  Weights are
seeded random, made on the device a layer at a time in the serving
dtype.  There is no training path, and no vision tower.

ASSUMED, because the source's ``config.json`` does not carry it (the
file ``benchmark/configs/mistral-small-4.json`` lists the same): the
router scores by softmax; the query scale ``a(t) = 1 + beta ln(1 +
floor(t / 8192))`` (``llama_4_scaling_beta``); YaRN's ramp as
DeepSeek-V3's published code computes it; bf16 parameters.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models.common import CacheSpec, rmsnorm, yarn_inv_freq as common_yarn_inv_freq
from ray_tpu.models.layers import chunk_context, chunk_slots, counters
from ray_tpu.ops.mla import K_BLOCK, absorbed_queries, expanded_attention, rope_interleaved

# What a forward returns after what it writes, summed over its layers:
# token-expert pairs the router made (tokens x 4); of those, the pairs
# whose expert is held here (counted from the router's choice); pairs
# computed (``ops.moe.moe_experts``: rows of the second grouped matmul
# that are not all zero); held experts that received a row; held experts
# there were; rows of the largest group; layers; and of a decode step the
# cached positions its kernel calls attended and the positions of the
# whole pages they copied.
COUNTERS = ("moe_pairs_routed", "moe_pairs_held", "moe_pairs", "moe_experts_hit",
            "moe_expert_slots", "moe_peak_rows", "moe_layer_programs",
            "kv_positions_attended", "kv_positions_gathered")

_LANE = 128  # columns of a lane tile: a cached row is whole tiles


@dataclass(frozen=True)
class Mistral4Config:
    """The source's ``config.json`` under the engine's names where it
    has one, the source's own elsewhere; then the share held here."""

    vocab_size: int = 131072  # rows of the vocabulary HELD (the engine's name); ids are below it
    published_vocab_size: int = 131072
    vocab_first: int = 0  # the first published row held
    n_layer: int = 36  # num_hidden_layers
    n_head: int = 32  # num_attention_heads = num_key_value_heads
    d_model: int = 4096  # hidden_size
    q_lora_rank: int = 1024
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    moe_intermediate_size: int = 2048  # the width of ONE expert, routed or shared
    n_routed_experts: int = 128  # the router's outputs, whatever is held here
    experts_first: int = 0  # the first routed expert held
    experts_held: int = 128
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    max_seq_len: int = 1048576  # max_position_embeddings
    rms_norm_eps: float = 1e-6
    # rope_parameters
    rope_theta: float = 10000.0
    rope_factor: float = 128.0
    original_max_position_embeddings: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0
    llama_4_scaling_beta: float = 0.1
    prefill_chunk: int = 4096  # most tokens of one prefill program
    dtype: Any = jnp.bfloat16  # parameters and matmuls; norms, softmaxes and the router are float32

    @property
    def latent_row(self) -> int:
        """Columns of a cached row: ``[c | k_r]`` and zeros up to whole
        lane tiles (320 -> 384)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // _LANE) * _LANE

    @staticmethod
    def mistral_small_4(**kw) -> "Mistral4Config":
        return Mistral4Config(**kw)  # 118.97B parameters: no one chip builds it

    @staticmethod
    def mistral_small_4_6l_ep4(**kw) -> "Mistral4Config":
        """One chip's share of four that share each layer: 6 of the 36
        layers, routed experts 0-31 of 128, rows 0-32,767 of the
        vocabulary; attention and the shared expert whole.  10.85 GB in
        bf16 (benchmark/configs/mistral-small-4.json)."""
        return Mistral4Config(**{**dict(n_layer=6, experts_held=32, vocab_size=32768), **kw})

    @staticmethod
    def mistral_small_4_tiny(**kw) -> "Mistral4Config":
        """Every width small; 8 of 32 experts' shares are what the
        tests cut it into.  ``original_max_position_embeddings`` 32, so
        a prompt of a few dozen tokens passes it and ``a(t)`` moves."""
        fields = dict(
            vocab_size=256, published_vocab_size=256, n_layer=2, n_head=4, d_model=64, q_lora_rank=32,
            kv_lora_rank=32, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
            moe_intermediate_size=32, n_routed_experts=32, experts_held=32, num_experts_per_tok=4,
            max_seq_len=512, rope_factor=16.0, original_max_position_embeddings=32, prefill_chunk=64)
        return Mistral4Config(**{**fields, **kw})


# ----------------------------------------------------------------------
# positions: YaRN frequencies, the softmax scale, the query scale
# ----------------------------------------------------------------------
def yarn_inv_freq(cfg: Mistral4Config) -> list:
    """The rotary frequency of each of the ``qk_rope_head_dim / 2``
    pairs, as Python floats: ``common.yarn_inv_freq`` (DeepSeek-V3's
    published ``yarn_find_correction_range`` / ``linear_ramp``) at this
    configuration's numbers."""
    return common_yarn_inv_freq(cfg.rope_theta, cfg.qk_rope_head_dim, cfg.rope_factor,
                                cfg.original_max_position_embeddings, cfg.beta_fast, cfg.beta_slow)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(cfg: Mistral4Config) -> float:
    """``(nope + rope)^-0.5 m^2``, ``m = 0.1 mscale_all_dim ln(factor)
    + 1``: 0.19497 at the published sizes."""
    m = _yarn_mscale(cfg.rope_factor, cfg.mscale_all_dim)
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def query_scale(pos, cfg: Mistral4Config):
    """``a(t) = 1 + beta ln(1 + floor(t / original_max))``: 1 under the
    original context, 1.161 at 32,768 positions."""
    steps = (pos // cfg.original_max_position_embeddings).astype(jnp.float32)
    return 1.0 + cfg.llama_4_scaling_beta * jnp.log1p(steps)


def _rope(x, pos, cfg):
    """x [..., D] rotated at positions pos over interleaved pairs
    (``ops.mla.rope_interleaved``) at YaRN's frequencies; cos and sin
    scaled by ``mscale / mscale_all_dim`` as YaRN has it (1 here)."""
    attn = _yarn_mscale(cfg.rope_factor, cfg.mscale) / _yarn_mscale(cfg.rope_factor, cfg.mscale_all_dim)
    return rope_interleaved(x, pos, yarn_inv_freq(cfg), attn)


# ----------------------------------------------------------------------
# the statement, the weights
# ----------------------------------------------------------------------
def cache_spec(cfg: Mistral4Config, block_size: int) -> CacheSpec:
    """One pool of latent rows, every layer alike, and no V pool: a
    position's values are its row's first ``kv_lora_rank`` columns."""
    return CacheSpec(paged_layers=cfg.n_layer, row_width=cfg.latent_row,
                     prefill_chunk=cfg.prefill_chunk, v_pool=False)


def init_params(cfg: Mistral4Config, rng=None):
    """Seeded weights (normal, std 0.02; norm weights 1) in cfg.dtype,
    made on the device one layer at a time, the held experts one at a
    time within it (an expert tensor's random bits alone are 2 GB)."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    d, f, V, H = cfg.d_model, cfg.moe_intermediate_size, cfg.vocab_size, cfg.n_head
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    kv = cfg.kv_lora_rank

    def normal(key, *shape):
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(cfg.dtype)

    def ones(n):
        return jnp.ones((n,), cfg.dtype)

    @jax.jit
    def layer(key):
        k = jax.random.split(key, 10)
        held = cfg.experts_held
        return {
            "w_in": ones(d), "wdq": normal(k[0], d, cfg.q_lora_rank), "w_qn": ones(cfg.q_lora_rank),
            "wuq": normal(k[1], cfg.q_lora_rank, H * qk),
            "wdkv": normal(k[2], d, kv + cfg.qk_rope_head_dim), "w_kvn": ones(kv),
            "wukv": normal(k[3], kv, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "wo": normal(k[4], H * cfg.v_head_dim, d), "w_post": ones(d),
            "router": normal(k[5], d, cfg.n_routed_experts),
            "wgu_shared": normal(k[6], d, 2 * f * cfg.n_shared_experts),
            "wd_shared": normal(k[7], f * cfg.n_shared_experts, d),
            "wgu": jax.lax.map(lambda e: normal(e, d, 2 * f), jax.random.split(k[8], held)),
            "wd": jax.lax.map(lambda e: normal(e, f, d), jax.random.split(k[9], held)),
        }

    @jax.jit
    def ends(key):
        k = jax.random.split(key, 2)
        return {"embed": normal(k[0], V, d), "norm": ones(d), "lm_head": normal(k[1], d, V)}

    keys = jax.random.split(rng, cfg.n_layer + 1)
    return {**ends(keys[0]), "layers": [layer(key) for key in keys[1:]]}


def serving_params(params, cfg: Mistral4Config):
    """The tree a server holds, which ``init_params`` already makes."""
    return params


# ----------------------------------------------------------------------
# the layer's halves
# ----------------------------------------------------------------------
def _project(h, lp, cfg, pos):
    """h [N, d] at positions pos [N] -> q_nope, q_rope [N, H, .], the
    softmax scale and the position's ``a(t)`` already in them (applied
    in float32), and the row to cache [N, latent_row]: the normed
    latent, the rotated shared key, zeros."""
    N, H = h.shape[0], cfg.n_head
    nope, rope, kv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    with jax.named_scope("mla.project"):
        c_q = rmsnorm(h @ lp["wdq"], lp["w_qn"], cfg.rms_norm_eps)
        q = (c_q @ lp["wuq"]).reshape(N, H, nope + rope)
        scale = softmax_scale(cfg) * query_scale(pos, cfg)
        q = (q.astype(jnp.float32) * scale[:, None, None]).astype(q.dtype)
        ckr = h @ lp["wdkv"]
        c = rmsnorm(ckr[:, :kv], lp["w_kvn"], cfg.rms_norm_eps)
        k_r = _rope(ckr[:, kv:], pos, cfg)
        row = jnp.concatenate([c, k_r, jnp.zeros((N, cfg.latent_row - kv - rope), c.dtype)], axis=-1)
        return q[..., :nope], _rope(q[..., nope:], pos[:, None], cfg), row


def _experts(x, lp, cfg):
    """The expert half of a block on tokens x [T, d]: what to add to x
    (the shared expert and the held routed experts' part), the layer's
    counters [routed, held, computed, hit, peak], and the experts the
    router chose [T, k]."""
    from ray_tpu.ops.moe import moe_experts

    h = rmsnorm(x, lp["w_post"], cfg.rms_norm_eps)
    with jax.named_scope("moe.route"):
        logits = jnp.dot(h, lp["router"], preferred_element_type=jnp.float32)
        top_p, top_e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.num_experts_per_tok)
        if cfg.norm_topk_prob:
            top_p = top_p / top_p.sum(-1, keepdims=True)
        top_p = top_p * cfg.routed_scaling_factor
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


def prefill_chunk(params, cfg: Mistral4Config, cache, tokens, start, last_index, table, lane,
                  block_size: int):
    """``prefill_chosen`` less its last result: what the engine takes."""
    return prefill_chosen(params, cfg, cache, tokens, start, last_index, table, lane, block_size)[:-1]


def prefill_chosen(params, cfg: Mistral4Config, cache, tokens, start, last_index, table, lane,
                   block_size: int):
    """One chunk of one prompt: tokens [1, T] at positions ``start ..``,
    of which ``last_index[0] + 1`` are real; table [pages] the
    sequence's physical pages.  Reads the earlier positions' latent rows
    through the table.  -> (logits [1, V] at ``last_index``, the chunk's
    rows [L, 1, T, latent_row], None (no V pool), {}, {}, COUNTERS, and
    for the checks the experts each layer's router chose [L, T, k])."""
    T = tokens.shape[1]
    n_valid = last_index[0] + 1
    x = params["embed"][tokens[0]]
    pos = start + jnp.arange(T)
    where, room = chunk_slots(table, block_size, T, K_BLOCK)
    rows_out, counts, chose = [], [], []
    for i, lp in enumerate(params["layers"]):
        h = rmsnorm(x, lp["w_in"], cfg.rms_norm_eps)
        q_nope, q_rope, row = _project(h, lp, cfg, pos)
        ctx = chunk_context(cache["k_pages"], i, where, room, row, start)
        att = expanded_attention(q_nope, q_rope, ctx, lp["wukv"], start, n_valid, cfg)
        x = x + att @ lp["wo"]
        y, c, top_e = _experts(x, lp, cfg)
        x = x + y
        rows_out.append(row)
        counts.append(c)
        chose.append(top_e)
    return (_logits(x[last_index], params, cfg), jnp.stack(rows_out)[:, None], None, {}, {},
            counters(COUNTERS, counts, cfg.experts_held), jnp.stack(chose))


def decode_forward_cached(params, cfg: Mistral4Config, cache, tok, block_tables, lengths,
                          block_size: int):
    """``decode_chosen`` less its last result: what the engine takes."""
    return decode_chosen(params, cfg, cache, tok, block_tables, lengths, block_size)[:-1]


def decode_chosen(params, cfg: Mistral4Config, cache, tok, block_tables, lengths, block_size: int):
    """One decode step: tok [B] at positions lengths [B] (a lane's
    cached positions), block_tables [B, pages].  Every layer reads the
    lanes' latent pages where they lie, absorbed.  -> (logits [B, V],
    the fed tokens' rows [L, B, latent_row], None, {}, {}, COUNTERS, and
    for the checks the experts each layer's router chose [L, B, k])."""
    from ray_tpu.ops.attention import mla_paged_decode_attention

    B, H = tok.shape[0], cfg.n_head
    nope, kv, dv = cfg.qk_nope_head_dim, cfg.kv_lora_rank, cfg.v_head_dim
    x = params["embed"][tok]
    rows_out, counts, chose = [], [], []
    for i, lp in enumerate(params["layers"]):
        h = rmsnorm(x, lp["w_in"], cfg.rms_norm_eps)
        q_nope, q_rope, row = _project(h, lp, cfg, lengths)
        with jax.named_scope("mla.absorb"):
            q = absorbed_queries(q_nope, q_rope, lp["wukv"], cfg)
        with jax.named_scope("mla.attend"):
            o_lat = mla_paged_decode_attention(q, row, cache["k_pages"], i, block_tables, lengths,
                                               block_size=block_size, v_width=kv)
        with jax.named_scope("mla.absorb"):
            w_uv = lp["wukv"].reshape(kv, H, nope + dv)[..., nope:]
            att = jnp.einsum("bhc,chd->bhd", o_lat, w_uv).reshape(B, H * dv)
        x = x + att @ lp["wo"]
        y, c, top_e = _experts(x, lp, cfg)
        x = x + y
        rows_out.append(row)
        counts.append(c)
        chose.append(top_e)
    pages = -(-lengths // block_size) * block_size
    return (_logits(x, params, cfg), jnp.stack(rows_out), None, {}, {},
            counters(COUNTERS, counts, cfg.experts_held, kv_positions_attended=lengths.sum() * cfg.n_layer,
                     kv_positions_gathered=pages.sum() * cfg.n_layer), jnp.stack(chose))

"""Granite 4.0-H (ibm-granite/granite-4.0-h-small, ``model_type:
granitemoehybrid``) for the serving engine: a decoder whose layers are
TWO parts each, a mixer by the published ``layer_types`` and then the
experts, each part behind its own norm and its own scaled residual:

    x = 12 * E[tok]                                      [embedding_multiplier]
    x = x + 0.22 * Mixer(rmsnorm(x, w1))                 [residual_multiplier]
    x = x + 0.22 * (Experts(y) + Shared(y)),  y = rmsnorm(x, w2)
    logits = rmsnorm(x, w_f) E_held^T / 16               [logits_scaling, tie_word_embeddings]

- ``mamba``, Mamba-2 (128 heads of 64, state 128, ONE group, convolution
  4, scan blocks of 256): ``[z | xBC | dt] = y W_in`` (8192 | 8448 |
  128); the convolution, the scan and the gated norm as
  ``models/nemotron_h.py`` writes them (``models/layers.py`` has them),
  the norm over all 8,192 columns.  It caches the last 3 rows of ``xBC``
  and the state ``[128, 64, 128]`` float32, a sequence.
- ``attention``: 32 query heads of 128 over 8 K/V heads, FOUR queries a
  group, no bias, no positional rotation (``position_embedding_type:
  nope``), softmax scale ``attention_multiplier`` 0.0078125 (not
  ``head_dim^-0.5``), causal.  It caches K and V of 1,024 values each a
  position.
- the experts: ``g = y W_r`` in float32 (72 logits); the 10 largest;
  ``p = softmax`` over those 10 alone; ``sum_e p_e W_down,e (silu(a_e) *
  b_e)``, ``[a_e | b_e] = y W_in,e`` (``4096 -> 2 x 768``), over those of
  the 10 that are HELD here (``experts_first``, ``experts_held``); beside
  them the shared expert, ``W_down,s (silu(a) * b)`` of width 1,536,
  whole.  What the absent experts would add is left out: on the chips of
  a deployment that share a layer the partial sums add up
  (``ops/moe.py``).

The module is a *family* to ``serve/llm/engine.py`` that STATES its cache
(``cache_spec``): K and V pages for the attention layers alone, and two
arrays a lane for every Mamba layer (``conv_tail_<i>`` ``[3 * 8448]`` in
the serving dtype, ``ssm_state_<i>`` ``[128, 64, 128]`` float32).  Its
two forwards read that cache and return what to write into it, as the
Nemotron-H family's do.  ``benchmark/reference_granite_4_0_h_small.py``
is the plain float32 forward of the same equations and reads the same
tree: ``embed [V, d]`` (the head too: no ``lm_head`` leaf), ``norm
[d]``, ``layers``, each ``norm1 [d]``, by its kind ``in_proj [d,
16768]``, ``conv_w [8448, 4]``, ``conv_b``, ``A_log``, ``D``, ``dt_bias``
``[128]`` float32, ``w_gn [8192]``, ``out_proj [8192, d]``; or ``wqkv [d,
4096 + 2 * 1024]``, ``wo [4096, d]``; then ``norm2 [d]``, ``router [d,
72]``, ``w_in [held, d, 2 * 768]`` (a | b side by side), ``w_down [held,
768, d]``, ``w_in_shared [d, 2 * 1536]``, ``w_down_shared [1536, d]``.
Weights are seeded random, made on the device a layer at a time in the
serving dtype.  There is no training path.

ASSUMED, because the catalog's row of the source does not settle it (the
file ``benchmark/configs/granite-4.0-h-small.json`` lists the same):
``intermediate_size`` 768 is ONE routed expert's width (the published
code sizes the experts by it); ``head_dim`` is ``hidden_size /
num_attention_heads``; ``d_inner = mamba_expand * hidden_size`` (=
``mamba_n_heads * mamba_d_head``); the gate applied BEFORE the norm;
the scan state float32 and the tail in the serving dtype; the seeded
weights (``init_params``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models.common import CacheSpec, rmsnorm
from ray_tpu.models.layers import (
    attention_chunk, attention_decode, chunk_slots, counters, mamba_chunk, mamba_decode, numbered, state_name,
    tail_name,
)
from ray_tpu.ops.attention import K_BLOCK

MAMBA, ATTENTION = "mamba", "attention"
# the published kind of each of the 40 layers (config.json: layer_types): attention at 5, 15, 25, 35
PUBLISHED_LAYER_TYPES = tuple(ATTENTION if i % 10 == 5 else MAMBA for i in range(40))

# What a forward returns after what it writes, summed over its layers:
# ``models/nemotron_h.py``'s, name for name (every layer here has an
# expert part: pairs are tokens x 10 a layer).
COUNTERS = ("moe_pairs_routed", "moe_pairs_held", "moe_pairs", "moe_experts_hit",
            "moe_expert_slots", "moe_peak_rows", "moe_layer_programs",
            "kv_positions_attended", "kv_positions_gathered", "ssm_lane_steps", "ssm_chunk_tokens",
            "kv_blocks_walked", "kv_blocks_whole")


@dataclass(frozen=True)
class GraniteHybridConfig:
    """The source's ``config.json`` under the engine's names where it
    has one and under ``models/layers.py``'s for the two mixers it
    shares with the Nemotron-H family (the source's key in the
    comment); then the share held here."""

    vocab_size: int = 100352  # rows of the vocabulary HELD (the engine's name); ids are below it
    published_vocab_size: int = 100352
    vocab_first: int = 0  # the first published row held
    layer_types: tuple = PUBLISHED_LAYER_TYPES  # a layer's mixer
    d_model: int = 4096  # hidden_size
    n_head: int = 32  # num_attention_heads
    n_kv_head: int = 8  # num_key_value_heads
    mamba_num_heads: int = 128  # mamba_n_heads
    mamba_head_dim: int = 64  # mamba_d_head
    ssm_state_size: int = 128  # mamba_d_state
    n_groups: int = 1  # mamba_n_groups
    conv_kernel: int = 4  # mamba_d_conv
    chunk_size: int = 256  # mamba_chunk_size: positions a block of the chunked scan
    intermediate_size: int = 768  # the width of ONE routed expert
    shared_intermediate_size: int = 1536
    num_local_experts: int = 72  # the router's outputs, whatever is held here
    experts_first: int = 0  # the first routed expert held
    experts_held: int = 72
    num_experts_per_tok: int = 10
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    max_seq_len: int = 131072  # max_position_embeddings
    layer_norm_epsilon: float = 1e-5  # rms_norm_eps
    time_step_min: float = 0.001  # the published mixer's initialisation (init_params)
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    prefill_chunk: int = 2048  # most tokens of one prefill program: whole blocks of the scan
    dtype: Any = jnp.bfloat16  # parameters and matmuls; norms, softmax, the router and the scan are float32

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """Columns the convolution runs over: x, then B and C of every group."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @staticmethod
    def granite_4_0_h_small(**kw) -> "GraniteHybridConfig":
        return GraniteHybridConfig(**kw)  # 32.2B parameters: no one chip builds it

    @staticmethod
    def granite_4_0_h_small_10l_ep2(**kw) -> "GraniteHybridConfig":
        """One chip's share of two that share each layer, of the first
        of four such pairs: layers 0-9 of the 40 (9 Mamba-2, attention
        at 5: one whole period), routed experts 0-35 of 72, rows
        0-50,175 of the vocabulary; mixers and the shared expert whole.
        9.51 GB in bf16 (benchmark/configs/granite-4.0-h-small.json)."""
        return GraniteHybridConfig(**{**dict(layer_types=PUBLISHED_LAYER_TYPES[:10], experts_held=36,
                                             vocab_size=50176), **kw})

    @staticmethod
    def granite_4_0_h_small_tiny(**kw) -> "GraniteHybridConfig":
        """Every width small, every kind of layer twice, four queries a
        group as published; 8 of 16 experts' shares are what the tests
        cut it into.  A prompt of a few dozen tokens takes several
        chunks, and a chunk several blocks of the scan."""
        fields = dict(
            vocab_size=256, published_vocab_size=256, layer_types=(MAMBA, ATTENTION, MAMBA, ATTENTION),
            d_model=64, n_head=8, n_kv_head=2, mamba_num_heads=8, mamba_head_dim=16, ssm_state_size=16,
            chunk_size=8, intermediate_size=32, shared_intermediate_size=48, num_local_experts=16,
            experts_held=16, num_experts_per_tok=4, attention_multiplier=0.25, max_seq_len=512, prefill_chunk=32)
        return GraniteHybridConfig(**{**fields, **kw})


# ----------------------------------------------------------------------
# the statement, the weights
# ----------------------------------------------------------------------
def cache_spec(cfg: GraniteHybridConfig, block_size: int) -> CacheSpec:
    """The attention layers page K and V of the K/V heads alone; every
    Mamba layer holds two arrays a lane, the convolution's tail and the
    scan's state (``nemotron_h.cache_spec`` says why an array a layer
    and why the tail lies flat)."""
    if cfg.prefill_chunk % cfg.chunk_size:
        raise ValueError(f"a prompt chunk of {cfg.prefill_chunk} is not whole scan blocks of {cfg.chunk_size}")
    tail = ((cfg.conv_kernel - 1) * cfg.conv_dim,)
    state = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size)
    lane_state = []
    for i in range(cfg.layer_types.count(MAMBA)):
        lane_state += [(tail_name(i), tail, cfg.dtype), (state_name(i), state, jnp.float32)]
    return CacheSpec(paged_layers=cfg.layer_types.count(ATTENTION), row_width=cfg.n_kv_head * cfg.head_dim,
                     lane_state=tuple(lane_state), prefill_chunk=cfg.prefill_chunk)


def init_params(cfg: GraniteHybridConfig, rng=None):
    """Seeded weights in cfg.dtype, made on the device one layer at a
    time, the held experts one at a time within it: matrices normal with
    std 0.02, norm weights 1; what is not a matrix by the published
    initialisation of a Mamba-2 mixer, as ``nemotron_h.init_params``
    says and for its reason: the convolution's weights and bias uniform
    in ``+-conv_kernel^-0.5``, ``A_log = log U(1, 16)``, ``D = 1`` and
    ``dt_bias`` the inverse softplus of a log-uniform draw in
    ``[time_step_min, time_step_max]`` floored at ``time_step_floor``,
    the last three float32.  Every matrix is a PLAIN draw: what the down
    projections read here (``silu(a) * b`` with a and b independent,
    ``o * silu(z)``) has zero mean, so none adds a vector common to every
    token's stream, and the zero-sum draw that family needs behind
    relu^2 is not taken (PERF.md section 6, PR 41, has what the routers'
    load reads)."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    d, V, f, fs = cfg.d_model, cfg.vocab_size, cfg.intermediate_size, cfg.shared_intermediate_size
    Hm, inner = cfg.mamba_num_heads, cfg.d_inner
    q_cols, kv_cols = cfg.n_head * cfg.head_dim, cfg.n_kv_head * cfg.head_dim

    def normal(key, *shape):
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(cfg.dtype)

    def ones(n):
        return jnp.ones((n,), cfg.dtype)

    def conv_uniform(key, *shape):
        bound = cfg.conv_kernel ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound).astype(cfg.dtype)

    def mamba_mixer(key):
        k = jax.random.split(key, 6)
        dt = jnp.exp(jax.random.uniform(k[3], (Hm,), jnp.float32, math.log(cfg.time_step_min),
                                        math.log(cfg.time_step_max)))
        dt = jnp.maximum(dt, cfg.time_step_floor)
        return {
            "in_proj": normal(k[0], d, inner + cfg.conv_dim + Hm),
            "conv_w": conv_uniform(k[1], cfg.conv_dim, cfg.conv_kernel), "conv_b": conv_uniform(k[5], cfg.conv_dim),
            "A_log": jnp.log(jax.random.uniform(k[2], (Hm,), jnp.float32, 1.0, 16.0)),
            "D": jnp.ones((Hm,), jnp.float32), "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "w_gn": ones(inner), "out_proj": normal(k[4], inner, d),
        }

    def attention_mixer(key):
        k = jax.random.split(key, 2)
        return {"wqkv": normal(k[0], d, q_cols + 2 * kv_cols), "wo": normal(k[1], q_cols, d)}

    def experts_part(key):
        k = jax.random.split(key, 5)
        return {
            "norm1": ones(d), "norm2": ones(d), "router": normal(k[0], d, cfg.num_local_experts),
            "w_in_shared": normal(k[1], d, 2 * fs), "w_down_shared": normal(k[2], fs, d),
            "w_in": jax.lax.map(lambda e: normal(e, d, 2 * f), jax.random.split(k[3], cfg.experts_held)),
            "w_down": jax.lax.map(lambda e: normal(e, f, d), jax.random.split(k[4], cfg.experts_held)),
        }

    mixers = {MAMBA: mamba_mixer, ATTENTION: attention_mixer}

    @jax.jit
    def ends(key):
        return {"embed": normal(key, V, d), "norm": ones(d)}

    def layer(kind):
        def make(key):
            k = jax.random.split(key, 2)
            return {**mixers[kind](k[0]), **experts_part(k[1])}

        return jax.jit(make)

    makers = {kind: layer(kind) for kind in mixers}
    keys = jax.random.split(rng, cfg.n_layer + 1)
    return {**ends(keys[0]), "layers": [makers[kind](key) for kind, key in zip(cfg.layer_types, keys[1:])]}


def serving_params(params, cfg: GraniteHybridConfig):
    """The tree a server holds, which ``init_params`` already makes."""
    return params


# ----------------------------------------------------------------------
# the layers' parts
# ----------------------------------------------------------------------
def _experts(y, lp, cfg):
    """The expert part on normed tokens y [T, d]: what to add to the
    stream before its multiplier (the shared expert and the held routed
    experts' part), the layer's counters [routed, held, computed, hit,
    peak], and the experts the router chose [T, k]."""
    from ray_tpu.ops.moe import moe_experts

    with jax.named_scope("moe.route"):
        logits = jnp.dot(y, lp["router"], preferred_element_type=jnp.float32)
        top_l, top_e = jax.lax.top_k(logits, cfg.num_experts_per_tok)
        top_p = jax.nn.softmax(top_l, axis=-1)  # over the chosen alone
        here = (top_e >= cfg.experts_first) & (top_e < cfg.experts_first + cfg.experts_held)
    with jax.named_scope("moe.shared"):
        a, b = jnp.split(y @ lp["w_in_shared"], 2, axis=-1)
        shared = (jax.nn.silu(a) * b) @ lp["w_down_shared"]
    held = None if cfg.experts_held == cfg.num_local_experts else (cfg.experts_first, cfg.experts_held)
    out, c = moe_experts(y, top_p, top_e, lp["w_in"], lp["w_down"], held=held, gated=True)
    routed = jnp.int32(top_e.size)
    return shared + out, jnp.concatenate([jnp.stack([routed, here.sum(dtype=jnp.int32)]), c]), top_e


def _logits(x, params, cfg):
    """The tied head: the embedding's held rows, transposed."""
    y = rmsnorm(x, params["norm"], cfg.layer_norm_epsilon)
    logits = jax.lax.dot_general(y, params["embed"], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    return logits / cfg.logits_scaling


def _embed(tokens, params, cfg):
    return params["embed"][tokens] * cfg.embedding_multiplier


# ----------------------------------------------------------------------
# the two forwards
# ----------------------------------------------------------------------
def prefill_chunk(params, cfg: GraniteHybridConfig, cache, tokens, start, last_index, table, lane,
                  block_size: int):
    """``prefill_chosen`` less its last result: what the engine takes."""
    return prefill_chosen(params, cfg, cache, tokens, start, last_index, table, lane, block_size)[:-1]


def prefill_chosen(params, cfg: GraniteHybridConfig, cache, tokens, start, last_index, table, lane,
                   block_size: int):
    """One chunk of one prompt: tokens [1, T] at positions ``start ..``,
    of which ``last_index[0] + 1`` are real; table [pages] the
    sequence's physical pages; lane the lane whose state it holds.
    Reads the earlier positions' K and V through the table and, unless
    ``start`` is 0 (then they read as zeros), the lane's tails and
    states.  -> (logits [1, V] at ``last_index``, k, v [La, 1, T, G, hd]
    the chunk's rows, {}, {"conv_tail_<i>": [3 * 8448], "ssm_state_<i>":
    [128, 64, 128]} the lane's tail and state after the last real
    position, Mamba layer by layer, COUNTERS, and for the checks the
    experts each layer's router chose [L, T, k])."""
    T = tokens.shape[1]
    n_valid = last_index[0] + 1
    res = cfg.residual_multiplier
    x = _embed(tokens[0], params, cfg)
    where, room = chunk_slots(table, block_size, T, K_BLOCK)
    ks, vs, state, counts, chose = [], [], {}, [], []
    for lp, (kind, i) in zip(params["layers"], numbered(cfg.layer_types)):
        y = rmsnorm(x, lp["norm1"], cfg.layer_norm_epsilon)
        if kind == MAMBA:
            out, after = mamba_chunk(y, lp, cfg, cache, i, lane, start, n_valid)
            state.update(after)
        else:
            out, k, v = attention_chunk(y, lp, cfg, cache, i, where, room, start, n_valid,
                                         scale=cfg.attention_multiplier)
            ks.append(k)
            vs.append(v)
        x = x + res * out
        out, c, top_e = _experts(rmsnorm(x, lp["norm2"], cfg.layer_norm_epsilon), lp, cfg)
        counts.append(c)
        chose.append(top_e)
        x = x + res * out
    return (_logits(x[last_index], params, cfg), jnp.stack(ks)[:, None], jnp.stack(vs)[:, None], {}, state,
            counters(COUNTERS, counts, cfg.experts_held, ssm_chunk_tokens=n_valid * cfg.layer_types.count(MAMBA),
                     kv_blocks_walked=(0, 0)),  # stated: layers.counters says why
            jnp.stack(chose))


def decode_forward_cached(params, cfg: GraniteHybridConfig, cache, tok, block_tables, lengths,
                          block_size: int):
    """``decode_chosen`` less its last result: what the engine takes."""
    return decode_chosen(params, cfg, cache, tok, block_tables, lengths, block_size)[:-1]


def decode_chosen(params, cfg: GraniteHybridConfig, cache, tok, block_tables, lengths, block_size: int):
    """One decode step: tok [B] at positions lengths [B] (a lane's
    cached positions; 0: the lane does not run), block_tables [B,
    pages].  The Mamba layers update the running lanes' states where they
    lie and shift their tails; the attention layers read the lanes'
    pages where they lie.  -> (logits [B, V], k_new, v_new [La, B, G,
    hd], {}, {"conv_tail_<i>", "ssm_state_<i>": the whole new arrays},
    COUNTERS, and for the checks the experts each layer's router chose
    [L, B, k])."""
    from ray_tpu.ops.attention import gqa_decode_blocks

    runs = lengths > 0
    res = cfg.residual_multiplier
    x = _embed(tok, params, cfg)
    ks, vs, state, counts, chose = [], [], {}, [], []
    for lp, (kind, i) in zip(params["layers"], numbered(cfg.layer_types)):
        y = rmsnorm(x, lp["norm1"], cfg.layer_norm_epsilon)
        if kind == MAMBA:
            out, after = mamba_decode(y, lp, cfg, cache, i, runs)
            state.update(after)
        else:
            out, k, v = attention_decode(y, lp, cfg, cache, i, block_tables, lengths, block_size,
                                          scale=cfg.attention_multiplier)
            ks.append(k)
            vs.append(v)
        x = x + res * out
        out, c, top_e = _experts(rmsnorm(x, lp["norm2"], cfg.layer_norm_epsilon), lp, cfg)
        counts.append(c)
        chose.append(top_e)
        x = x + res * out
    pages = -(-lengths // block_size) * block_size
    n_a, n_m = cfg.layer_types.count(ATTENTION), cfg.layer_types.count(MAMBA)
    return (_logits(x, params, cfg), jnp.stack(ks), jnp.stack(vs), {}, state,
            counters(COUNTERS, counts, cfg.experts_held, kv_positions_attended=lengths.sum() * n_a,
                     kv_positions_gathered=pages.sum() * n_a, ssm_lane_steps=runs.sum() * n_m,
                     kv_blocks_walked=gqa_decode_blocks(cache["k_pages"], lengths, block_size, n_a)),
            jnp.stack(chose))

"""Nemotron-H (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, ``model_type:
nemotron_h``) for the serving engine: a decoder whose layers are ONE
part each, by the letters of the published ``hybrid_override_pattern``:

- ``M``, Mamba-2 (64 heads of 64, state 128, 8 groups, convolution 4):
  ``[z | xBC | dt] = y W_in`` (4096 | 6144 | 64); ``xBC_t = silu(b_c +
  sum_j w_c[:, j] xBC_{t-3+j})``, depthwise and causal, zeros before
  position 0; ``xBC -> x [64, 64] | B [8, 128] | C [8, 128]``; ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; for head h of group g
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` (``[64, 128]``,
  float32), ``o_t = S_t C_t + D x_t``; ``u = o * silu(z)``, RMSNorm over
  each group of 512 columns, times ``w_n``; ``W_out``.  It caches no
  keys: the last 3 rows of ``xBC`` it saw and the state, a sequence.
- ``*``, attention: 32 query heads of 128 over 2 K/V heads, no bias, no
  positional rotation, softmax scale ``128^-0.5``, causal.  It caches K
  and V of 256 values a position.
- ``E``, experts: ``s = sigmoid(y W_r)`` in float32; the 6 experts of
  largest ``s + b_sel``; weights ``2.5 s_e / sum s`` over the chosen;
  ``sum_e w_e W_down,e relu(y W_up,e)^2`` over those of the 6 that are
  HELD here (``experts_first``, ``experts_held``) plus the shared expert
  ``W_down,s relu(y W_up,s)^2`` (width 3,712).  What the absent experts
  would add is left out: on the chips of a deployment that share a layer
  the partial sums add up (``ops/moe.py``).

The stream, in the serving dtype: ``x = E[tok]``; every layer ``x = x +
Part(rmsnorm(x, w, 1e-5))``; ``logits = W_head rmsnorm(x)`` over the rows
of the vocabulary held (untied head, no logit scale).

The module is a *family* to ``serve/llm/engine.py`` that STATES its cache
(``cache_spec``): K and V pages for the attention layers alone, and two
arrays a lane for every Mamba layer (``conv_tail_<i>`` ``[3 * 6144]`` in
the serving dtype, ``ssm_state_<i>`` ``[64, 64, 128]`` float32).  Its two
forwards read that cache and return what to write into it
(``prefill_chunk``: the chunked scan from the lane's state and tail,
attention over the paged context; ``decode_forward_cached``:
``ops.pallas_mamba2.mamba2_decode_step`` updating the lanes' states in
place, ``ops.attention.gqa_paged_decode_attention`` over the pages where
they lie).  ``benchmark/reference_nemotron_3_nano.py`` is the plain
float32 forward of the same equations and reads the same tree: ``embed
[V, d]``, ``layers`` (each ``norm [d]`` and, by its letter, ``in_proj [d,
10304]``, ``conv_w [6144, 4]``, ``conv_b``, ``A_log``, ``D``, ``dt_bias``
``[64]`` float32, ``w_gn [4096]``, ``out_proj [4096, d]``; or ``wqkv [d,
4096 + 2 * 256]``, ``wo [4096, d]``; or ``router [d, 128]``, ``b_sel
[128]`` float32, ``w_up [held, f, d]`` (TRANSPOSED, as ``ops/moe.py`` says), ``w_down [held, f, d]``,
``w_up_shared [d, 3712]``, ``w_down_shared [3712, d]``), ``norm [d]``,
``lm_head [d, V]``.  Weights are seeded random, made on the device a
layer at a time in the serving dtype.  There is no training path.

The Mamba-2 mixer and the grouped-query attention are not this
module's: ``models/layers.py`` has them (``mamba_chunk``,
``mamba_decode``, ``attention_chunk``, ``attention_decode``), for this
family as for ``models/granite_hybrid.py``.

ASSUMED, because the source's ``config.json`` does not settle it (the
file ``benchmark/configs/nemotron-3-nano.json`` lists the same): no
rotation in attention (``rope_theta`` and ``partial_rotary_factor`` are
in the config and the published modelling code applies none); sigmoid
scoring with a selection bias (the config has DeepSeek-V3's router keys
and no ``scoring_func``); ``d_inner = mamba_num_heads * mamba_head_dim``
(not ``expand * hidden_size``); the gate applied BEFORE the grouped
norm; the scan state float32 and the tail in the serving dtype; the
seeded weights (``init_params``).
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

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
# the published order of the 52 layers (config.json: hybrid_override_pattern)
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

# What a forward returns after what it writes, summed over its layers:
# the expert layers' counters as ``models/mistral4.py`` has them (pairs
# the router made, tokens x 6 an expert layer; those whose expert is
# held; pairs computed; held experts that received a row; held experts
# there were; rows of the largest group; expert layers); of a decode
# step the cached positions its attention kernel calls attended and the
# positions of the whole pages they copied; the (lane, Mamba layer)
# states a decode step updated, idle lanes not counted; and the real
# tokens x Mamba layers a chunk's scan took.
COUNTERS = ("moe_pairs_routed", "moe_pairs_held", "moe_pairs", "moe_experts_hit",
            "moe_expert_slots", "moe_peak_rows", "moe_layer_programs",
            "kv_positions_attended", "kv_positions_gathered", "ssm_lane_steps", "ssm_chunk_tokens",
            "kv_blocks_walked", "kv_blocks_whole")


@dataclass(frozen=True)
class NemotronHConfig:
    """The source's ``config.json`` under the engine's names where it
    has one, the source's own elsewhere; then the share held here."""

    vocab_size: int = 131072  # rows of the vocabulary HELD (the engine's name); ids are below it
    published_vocab_size: int = 131072
    vocab_first: int = 0  # the first published row held
    pattern: str = PUBLISHED_PATTERN  # hybrid_override_pattern: a letter a layer
    d_model: int = 2688  # hidden_size
    n_head: int = 32  # num_attention_heads
    n_kv_head: int = 2  # num_key_value_heads
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128  # positions a block of the chunked scan
    moe_intermediate_size: int = 1856  # the width of ONE routed expert
    moe_shared_expert_intermediate_size: int = 3712
    n_routed_experts: int = 128  # the router's outputs, whatever is held here
    experts_first: int = 0  # the first routed expert held
    experts_held: int = 128
    num_experts_per_tok: int = 6
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    max_seq_len: int = 262144  # max_position_embeddings
    layer_norm_epsilon: float = 1e-5
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    prefill_chunk: int = 2048  # most tokens of one prefill program: whole blocks of the scan
    dtype: Any = jnp.bfloat16  # parameters and matmuls; norms, softmax, the router and the scan are float32

    @property
    def n_layer(self) -> int:
        return len(self.pattern)

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """Columns the convolution runs over: x, then B and C of every group."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @staticmethod
    def nemotron_3_nano(**kw) -> "NemotronHConfig":
        return NemotronHConfig(**kw)  # 31.58B parameters: no one chip builds it

    @staticmethod
    def nemotron_3_nano_26l_ep4(**kw) -> "NemotronHConfig":
        """One chip's share of four that share each layer, of the first
        of two such groups: layers 0-25 of the 52 (12 Mamba-2, 11
        expert, 3 attention), routed experts 0-31 of 128, rows 0-32,767
        of the vocabulary; Mamba-2, attention and the shared expert
        whole.  8.89 GB in bf16 (benchmark/configs/nemotron-3-nano.json)."""
        return NemotronHConfig(**{**dict(pattern=PUBLISHED_PATTERN[:26], experts_held=32, vocab_size=32768), **kw})

    @staticmethod
    def nemotron_3_nano_tiny(**kw) -> "NemotronHConfig":
        """Every width small, every kind of layer twice; 8 of 32 experts'
        shares are what the tests cut it into.  A prompt of a few dozen
        tokens takes several chunks, and a chunk several blocks of the
        scan."""
        fields = dict(
            vocab_size=256, published_vocab_size=256, pattern="MEM*EM*E", d_model=64, n_head=4, n_kv_head=2,
            head_dim=16, mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16, n_groups=2, chunk_size=8,
            moe_intermediate_size=32, moe_shared_expert_intermediate_size=48, n_routed_experts=32,
            experts_held=32, num_experts_per_tok=6, max_seq_len=512, prefill_chunk=32)
        return NemotronHConfig(**{**fields, **kw})


# ----------------------------------------------------------------------
# the statement, the weights
# ----------------------------------------------------------------------
def cache_spec(cfg: NemotronHConfig, block_size: int) -> CacheSpec:
    """The attention layers page K and V of the K/V heads alone; every
    Mamba layer holds two arrays a lane, the convolution's tail and the
    scan's state (an array a layer, as ``minicpm_sala.cache_spec`` says:
    a decode step then reads and writes whole arrays)."""
    if cfg.prefill_chunk % cfg.chunk_size:
        raise ValueError(f"a prompt chunk of {cfg.prefill_chunk} is not whole scan blocks of {cfg.chunk_size}")
    tail = ((cfg.conv_kernel - 1) * cfg.conv_dim,)  # the rows side by side (ops.mamba2.conv_tail)
    state = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size)
    lane_state = []
    for i in range(cfg.pattern.count(MAMBA)):
        lane_state += [(tail_name(i), tail, cfg.dtype), (state_name(i), state, jnp.float32)]
    return CacheSpec(paged_layers=cfg.pattern.count(ATTENTION), row_width=cfg.n_kv_head * cfg.head_dim,
                     lane_state=tuple(lane_state), prefill_chunk=cfg.prefill_chunk)


def init_params(cfg: NemotronHConfig, rng=None):
    """Seeded weights in cfg.dtype, made on the device one layer at a
    time, the held experts one at a time within it: matrices normal with
    std 0.02, norm weights 1; the convolution's weights and bias uniform
    in ``+-conv_kernel^-0.5``, ``A_log = log U(1, 16)``, ``D = 1`` and
    ``dt_bias`` the inverse softplus of a log-uniform draw in
    ``[time_step_min, time_step_max]`` floored at ``time_step_floor``
    (the published initialisation of a Mamba-2 mixer: with a convolution
    of std 0.02 x, B and C come out near 0.02 and the state adds a
    ten-thousandth of what ``D x`` does, so no check would see the scan),
    the last three float32; ``b_sel`` normal with std 0.02 (float32:
    small, so that the term is exercised and the scores still decide).
    The matrices that read an activation with a positive mean
    (``out_proj`` behind the silu gate, ``w_down`` and ``w_down_shared``
    behind relu^2) have columns that sum to ZERO over the hidden axis:
    under plain normal draws each adds the SAME vector to every token's
    stream (``W^T 1`` times that mean), the vectors pile up with depth,
    and every router then reads them as a bias an expert of the seed's
    own, where a trained router's selection bias spreads the load: of 32
    held experts a decode step of 128 lanes hit 28.3 +- 0.5 by the seed,
    and the step's time went with it (PERF.md section 6, PR 38)."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    d, V, f = cfg.d_model, cfg.vocab_size, cfg.moe_intermediate_size
    Hm, inner = cfg.mamba_num_heads, cfg.d_inner
    q_cols, kv_cols = cfg.n_head * cfg.head_dim, cfg.n_kv_head * cfg.head_dim

    def normal(key, *shape):
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(cfg.dtype)

    def zero_sum(key, *shape):
        w = 0.02 * jax.random.normal(key, shape, jnp.float32)
        return (w - w.mean(-2, keepdims=True)).astype(cfg.dtype)

    def ones(n):
        return jnp.ones((n,), cfg.dtype)

    def conv_uniform(key, *shape):
        bound = cfg.conv_kernel ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound).astype(cfg.dtype)

    @jax.jit
    def mamba_layer(key):
        k = jax.random.split(key, 6)
        dt = jnp.exp(jax.random.uniform(k[3], (Hm,), jnp.float32, math.log(cfg.time_step_min),
                                        math.log(cfg.time_step_max)))
        dt = jnp.maximum(dt, cfg.time_step_floor)
        return {
            "norm": ones(d), "in_proj": normal(k[0], d, inner + cfg.conv_dim + Hm),
            "conv_w": conv_uniform(k[1], cfg.conv_dim, cfg.conv_kernel), "conv_b": conv_uniform(k[5], cfg.conv_dim),
            "A_log": jnp.log(jax.random.uniform(k[2], (Hm,), jnp.float32, 1.0, 16.0)),
            "D": jnp.ones((Hm,), jnp.float32), "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "w_gn": ones(inner), "out_proj": zero_sum(k[4], inner, d),
        }

    @jax.jit
    def attention_layer(key):
        k = jax.random.split(key, 2)
        return {"norm": ones(d), "wqkv": normal(k[0], d, q_cols + 2 * kv_cols), "wo": normal(k[1], q_cols, d)}

    @jax.jit
    def expert_layer(key):
        k = jax.random.split(key, 6)
        fs = cfg.moe_shared_expert_intermediate_size * cfg.n_shared_experts
        return {
            "norm": ones(d), "router": normal(k[0], d, cfg.n_routed_experts),
            "b_sel": 0.02 * jax.random.normal(k[1], (cfg.n_routed_experts,), jnp.float32),
            "w_up_shared": normal(k[2], d, fs), "w_down_shared": zero_sum(k[3], fs, d),
            "w_up": jax.lax.map(lambda e: normal(e, f, d), jax.random.split(k[4], cfg.experts_held)),
            "w_down": jax.lax.map(lambda e: zero_sum(e, f, d), jax.random.split(k[5], cfg.experts_held)),
        }

    @jax.jit
    def ends(key):
        k = jax.random.split(key, 2)
        return {"embed": normal(k[0], V, d), "norm": ones(d), "lm_head": normal(k[1], d, V)}

    makers = {MAMBA: mamba_layer, ATTENTION: attention_layer, EXPERTS: expert_layer}
    keys = jax.random.split(rng, cfg.n_layer + 1)
    return {**ends(keys[0]), "layers": [makers[kind](key) for kind, key in zip(cfg.pattern, keys[1:])]}


def serving_params(params, cfg: NemotronHConfig):
    """The tree a server holds, which ``init_params`` already makes."""
    return params


# ----------------------------------------------------------------------
# the layers' parts
# ----------------------------------------------------------------------
def _experts(y, lp, cfg):
    """The expert part on normed tokens y [T, d]: what to add to the
    stream (the shared expert and the held routed experts' part), the
    layer's counters [routed, held, computed, hit, peak], and the experts
    the router chose [T, k]."""
    from ray_tpu.ops.moe import moe_experts

    with jax.named_scope("moe.route"):
        s = jax.nn.sigmoid(jnp.dot(y, lp["router"], preferred_element_type=jnp.float32))
        _, top_e = jax.lax.top_k(s + lp["b_sel"], cfg.num_experts_per_tok)
        top_s = jnp.take_along_axis(s, top_e, axis=-1)
        if cfg.norm_topk_prob:
            top_s = top_s / (top_s.sum(-1, keepdims=True) + 1e-20)
        top_s = top_s * cfg.routed_scaling_factor
        here = (top_e >= cfg.experts_first) & (top_e < cfg.experts_first + cfg.experts_held)
    with jax.named_scope("moe.shared"):
        shared = jnp.square(jax.nn.relu(y @ lp["w_up_shared"])) @ lp["w_down_shared"]
    held = None if cfg.experts_held == cfg.n_routed_experts else (cfg.experts_first, cfg.experts_held)
    out, c = moe_experts(y, top_s, top_e, lp["w_up"], lp["w_down"], held=held, gated=False)
    routed = jnp.int32(top_e.size)
    return shared + out, jnp.concatenate([jnp.stack([routed, here.sum(dtype=jnp.int32)]), c]), top_e


def _logits(x, params, cfg):
    return (rmsnorm(x, params["norm"], cfg.layer_norm_epsilon) @ params["lm_head"]).astype(jnp.float32)


# ----------------------------------------------------------------------
# the two forwards
# ----------------------------------------------------------------------
def prefill_chunk(params, cfg: NemotronHConfig, cache, tokens, start, last_index, table, lane,
                  block_size: int):
    """``prefill_chosen`` less its last result: what the engine takes."""
    return prefill_chosen(params, cfg, cache, tokens, start, last_index, table, lane, block_size)[:-1]


def prefill_chosen(params, cfg: NemotronHConfig, cache, tokens, start, last_index, table, lane,
                   block_size: int):
    """One chunk of one prompt: tokens [1, T] at positions ``start ..``,
    of which ``last_index[0] + 1`` are real; table [pages] the
    sequence's physical pages; lane the lane whose state it holds.
    Reads the earlier positions' K and V through the table and, unless
    ``start`` is 0 (then they read as zeros), the lane's tails and
    states.  -> (logits [1, V] at ``last_index``, k, v [La, 1, T, G, hd]
    the chunk's rows, {}, {"conv_tail_<i>": [3 * 6144], "ssm_state_<i>":
    [64, 64, 128]} the lane's tail and state after the last real
    position, Mamba layer by layer, COUNTERS, and for the checks the
    experts each expert layer's router chose [Le, T, k])."""
    T = tokens.shape[1]
    n_valid = last_index[0] + 1
    x = params["embed"][tokens[0]]
    where, room = chunk_slots(table, block_size, T, K_BLOCK)
    ks, vs, state, counts, chose = [], [], {}, [], []
    for lp, (kind, i) in zip(params["layers"], numbered(cfg.pattern)):
        y = rmsnorm(x, lp["norm"], cfg.layer_norm_epsilon)
        if kind == MAMBA:
            out, after = mamba_chunk(y, lp, cfg, cache, i, lane, start, n_valid)
            state.update(after)
        elif kind == ATTENTION:
            out, k, v = attention_chunk(y, lp, cfg, cache, i, where, room, start, n_valid)
            ks.append(k)
            vs.append(v)
        else:
            out, c, top_e = _experts(y, lp, cfg)
            counts.append(c)
            chose.append(top_e)
        x = x + out
    return (_logits(x[last_index], params, cfg), jnp.stack(ks)[:, None], jnp.stack(vs)[:, None], {}, state,
            counters(COUNTERS, counts, cfg.experts_held, ssm_chunk_tokens=n_valid * cfg.pattern.count(MAMBA),
                     kv_blocks_walked=(0, 0)),  # stated: layers.counters says why
            jnp.stack(chose))


def decode_forward_cached(params, cfg: NemotronHConfig, cache, tok, block_tables, lengths,
                          block_size: int):
    """``decode_chosen`` less its last result: what the engine takes."""
    return decode_chosen(params, cfg, cache, tok, block_tables, lengths, block_size)[:-1]


def decode_chosen(params, cfg: NemotronHConfig, cache, tok, block_tables, lengths, block_size: int):
    """One decode step: tok [B] at positions lengths [B] (a lane's
    cached positions; 0: the lane does not run), block_tables [B,
    pages].  The Mamba layers update the running lanes' states where they
    lie and shift their tails; the attention layers read the lanes'
    pages where they lie.  -> (logits [B, V], k_new, v_new [La, B, G,
    hd], {}, {"conv_tail_<i>", "ssm_state_<i>": the whole new arrays},
    COUNTERS, and for the checks the experts each expert layer's router
    chose [Le, B, k])."""
    from ray_tpu.ops.attention import gqa_decode_blocks

    runs = lengths > 0
    x = params["embed"][tok]
    ks, vs, state, counts, chose = [], [], {}, [], []
    for lp, (kind, i) in zip(params["layers"], numbered(cfg.pattern)):
        y = rmsnorm(x, lp["norm"], cfg.layer_norm_epsilon)
        if kind == MAMBA:
            out, after = mamba_decode(y, lp, cfg, cache, i, runs)
            state.update(after)
        elif kind == ATTENTION:
            out, k, v = attention_decode(y, lp, cfg, cache, i, block_tables, lengths, block_size)
            ks.append(k)
            vs.append(v)
        else:
            out, c, top_e = _experts(y, lp, cfg)
            counts.append(c)
            chose.append(top_e)
        x = x + out
    pages = -(-lengths // block_size) * block_size
    n_a, n_m = cfg.pattern.count(ATTENTION), cfg.pattern.count(MAMBA)
    return (_logits(x, params, cfg), jnp.stack(ks), jnp.stack(vs), {}, state,
            counters(COUNTERS, counts, cfg.experts_held, kv_positions_attended=lengths.sum() * n_a,
                     kv_positions_gathered=pages.sum() * n_a, ssm_lane_steps=runs.sum() * n_m,
                     kv_blocks_walked=gqa_decode_blocks(cache["k_pages"], lengths, block_size, n_a)),
            jnp.stack(chose))

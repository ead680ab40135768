"""Jamba (ai21labs/AI21-Jamba2-3B, ``model_type: jamba``) for the serving
engine: a decoder whose layers are TWO parts each, a mixer by the
layer's place and then a dense SwiGLU, each behind its own RMSNorm (eps
1e-6) and a plain residual:

    x = E[tok]
    x = x + Mixer_i(rmsnorm(x, w_in))
    x = x + W_down (silu(y W_gate) * (y W_up)),  y = rmsnorm(x, w_ff)      [intermediate_size 8,192]
    logits = rmsnorm(x, w_f) E^T                                            [tie_word_embeddings]

- layer i is ``attention`` where ``i % attn_layer_period ==
  attn_layer_offset`` (7 and 21 of 28) and ``mamba`` elsewhere (the
  published ``layers_block_type``).  ``num_experts`` is 1: no layer has
  a router, every second half is the plain MLP.
- ``mamba``, Mamba-1 (``d_inner = mamba_expand x 2560 = 5120``, state N
  = 16, convolution K = 4, ``dt`` rank R = 160): ``[x | z] = y W_in``
  (2560 x 10240, no bias); ``x_t = silu(b_c + sum_j w_c[:, j]
  x_{t-3+j})``, depthwise and causal over the 5,120 columns of ``x``
  ALONE; ``[d | B | C] = x W_x`` (5120 x (160 + 16 + 16)), each through
  an RMSNorm of its own; ``dt = softplus(d W_dt + b_dt)`` (float32); ``A
  = -exp(A_log)`` (``[5120, 16]``: a decay for EVERY value of the
  state); ``h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) B_t^T`` (float32),
  ``o_t = h_t C_t + D x_t``; ``o_t * silu(z_t)``; ``W_out``.  No heads,
  no groups, no gated norm.  It caches no keys: the last 3 rows of ``x``
  it saw and the state, a sequence.
- ``attention``: 20 query heads of 128 over ONE K/V head, no bias, NO
  rotation and no position embedding of any kind (the Mamba layers carry
  the order), scale ``128^-0.5``, causal, every cached position.  It
  caches K and V of 128 values each a position.

The module is a *family* to ``serve/llm/engine.py`` that STATES its cache
(``cache_spec``): K and V pages for the two attention layers alone, and
two arrays a lane for every Mamba layer (``conv_tail_<i>`` ``[3 * 5120]``
in the serving dtype, ``ssm_state_<i>`` ``[16, 5120]`` float32: N on the
sublanes, the channels along the lanes, ``ops/mamba1.py`` says why).  Its
two forwards read that cache and return what to write into it
(``prefill_chunk``: ``ops.pallas_mamba1.mamba1_chunk_scan`` from the
lane's state and tail, attention over the paged context;
``decode_forward_cached``: ``mamba1_decode_step`` updating the lanes'
states in place, ``ops.attention.gqa_paged_decode_attention`` over the
pages where they lie).  The chunked attention, the lane state's names
and the convolution's tail are the Nemotron-H family's and
``ops/mamba2.py``'s, called and not copied.
``benchmark/reference_jamba2.py`` is the plain float32 forward of the
same equations and reads the same tree: ``embed [V, d]`` (the head too),
``norm [d]``, ``layers``, each ``norm1 [d]``, by its kind ``in_proj [d,
2 * 5120]``, ``conv_w [5120, 4]``, ``conv_b [5120]``, ``x_proj [5120,
192]``, ``dt_norm [160]``, ``b_norm [16]``, ``c_norm [16]``, ``dt_proj
[160, 5120]``, ``dt_bias [5120]``, ``A_log [5120, 16]``, ``D [5120]``
(the last three float32), ``out_proj [5120, d]``; or ``wqkv [d, 2560 + 2
* 128]``, ``wo [2560, d]``; then ``norm2 [d]``, ``w_gate_up [d, 2 *
8192]`` (gate | up side by side), ``w_down [8192, d]``.  Weights are
seeded random, made on the device a layer at a time in the serving
dtype.  There is no training path.

ASSUMED, because the catalog's row of the source does not settle it
(``benchmark/configs/jamba2-3b.json`` lists the same): the order of the
layer kinds (above); ``head_dim = hidden_size / num_attention_heads``;
bf16 parameters; the scan state float32 and the tail in the serving
dtype; the seeded weights (``init_params``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models.common import CacheSpec, rmsnorm
from ray_tpu.models.layers import (
    attention_chunk, attention_decode, chunk_slots, counters, numbered, state_name, tail_name,
)
from ray_tpu.ops import mamba1
from ray_tpu.ops.attention import K_BLOCK
from ray_tpu.ops.mamba2 import conv_tail

MAMBA, ATTENTION = "mamba", "attention"

# What a forward returns after what it writes, summed over its layers,
# as ``models/nemotron_h.py`` names them (there are no experts to
# count): of a decode step the cached positions its attention kernel
# calls attended and the positions of the whole pages they copied; the
# (lane, Mamba layer) states a decode step updated, idle lanes not
# counted; and the real tokens x Mamba layers a chunk's scan took.
COUNTERS = ("kv_positions_attended", "kv_positions_gathered", "ssm_lane_steps", "ssm_chunk_tokens",
            "kv_blocks_walked", "kv_blocks_whole")


@dataclass(frozen=True)
class JambaConfig:
    """The source's ``config.json`` under the engine's names where it
    has one, the source's own elsewhere (the source's key in the
    comment)."""

    vocab_size: int = 65536
    n_layer: int = 28  # num_hidden_layers
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    d_model: int = 2560  # hidden_size
    n_head: int = 20  # num_attention_heads
    n_kv_head: int = 1  # num_key_value_heads
    intermediate_size: int = 8192
    mamba_expand: int = 2
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 160
    max_seq_len: int = 262144  # max_position_embeddings
    layer_norm_epsilon: float = 1e-6  # rms_norm_eps
    time_step_min: float = 0.001  # the Mamba-1 convention's initialisation (init_params)
    time_step_max: float = 0.1
    prefill_chunk: int = 2048  # most tokens of one prefill program
    dtype: Any = jnp.bfloat16  # parameters and matmuls; norms, softmax, dt and the scan are float32

    @property
    def layer_types(self) -> tuple:
        """A layer's mixer: the published ``layers_block_type``."""
        return tuple(ATTENTION if i % self.attn_layer_period == self.attn_layer_offset else MAMBA
                     for i in range(self.n_layer))

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    @staticmethod
    def jamba2_3b(**kw) -> "JambaConfig":
        """The published model whole: 3.03B parameters, 6.06 GB in bf16
        (benchmark/configs/jamba2-3b.json)."""
        return JambaConfig(**kw)

    @staticmethod
    def jamba2_tiny(**kw) -> "JambaConfig":
        """Every width small, two periods of four layers with the
        attention layer at offset 2, five queries on the one K/V head.
        A prompt of a few dozen tokens takes several chunks."""
        fields = dict(vocab_size=256, n_layer=8, attn_layer_period=4, attn_layer_offset=2, d_model=40, n_head=5,
                      intermediate_size=64, mamba_d_state=4, mamba_dt_rank=4, max_seq_len=256, prefill_chunk=8)
        return JambaConfig(**{**fields, **kw})


# ----------------------------------------------------------------------
# the statement, the weights
# ----------------------------------------------------------------------
def cache_spec(cfg: JambaConfig, block_size: int) -> CacheSpec:
    """The attention layers page K and V of the one K/V head; every
    Mamba layer holds two arrays a lane, the convolution's tail (flat, as
    ``nemotron_h.cache_spec`` says) and the scan's state ``[N,
    d_inner]``."""
    kinds = cfg.layer_types
    lane_state = []
    for i in range(kinds.count(MAMBA)):
        lane_state += [(tail_name(i), ((cfg.mamba_d_conv - 1) * cfg.d_inner,), cfg.dtype),
                       (state_name(i), (cfg.mamba_d_state, cfg.d_inner), jnp.float32)]
    return CacheSpec(paged_layers=kinds.count(ATTENTION), row_width=cfg.n_kv_head * cfg.head_dim,
                     lane_state=tuple(lane_state), prefill_chunk=cfg.prefill_chunk)


def init_params(cfg: JambaConfig, rng=None):
    """Seeded weights in cfg.dtype, made on the device one layer at a
    time: matrices normal with std 0.02, every norm weight 1; what is not
    a matrix by the Mamba-1 convention: the convolution's weights and
    bias uniform in ``+-mamba_d_conv^-0.5`` (``nemotron_h.init_params``
    says why not std 0.02), ``A_log[c, n] = log(n + 1)`` (S4D-real),
    ``D = 1`` and ``dt_bias`` the inverse softplus of a log-uniform draw
    in ``[time_step_min, time_step_max]`` a channel, the last three
    float32: fast and slow channels both exist, and a decay is neither 1
    nor 0.  Every matrix is a PLAIN draw: what the down projections read
    (``silu(a) * b`` with a and b independent, ``o * silu(z)``) has zero
    mean, and no router reads the stream."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    d, f, inner, N, R = cfg.d_model, cfg.intermediate_size, cfg.d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    q_cols, kv_cols = cfg.n_head * cfg.head_dim, cfg.n_kv_head * cfg.head_dim

    def normal(key, *shape):
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(cfg.dtype)

    def ones(n):
        return jnp.ones((n,), cfg.dtype)

    def conv_uniform(key, *shape):
        bound = cfg.mamba_d_conv ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound).astype(cfg.dtype)

    def mamba_mixer(key):
        k = jax.random.split(key, 7)
        dt = jnp.exp(jax.random.uniform(k[0], (inner,), jnp.float32, math.log(cfg.time_step_min),
                                        math.log(cfg.time_step_max)))
        return {
            "in_proj": normal(k[1], d, 2 * inner),
            "conv_w": conv_uniform(k[2], inner, cfg.mamba_d_conv), "conv_b": conv_uniform(k[3], inner),
            "x_proj": normal(k[4], inner, R + 2 * N), "dt_norm": ones(R), "b_norm": ones(N), "c_norm": ones(N),
            "dt_proj": normal(k[5], R, inner), "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), (inner, N)),
            "D": jnp.ones((inner,), jnp.float32), "out_proj": normal(k[6], inner, d),
        }

    def attention_mixer(key):
        k = jax.random.split(key, 2)
        return {"wqkv": normal(k[0], d, q_cols + 2 * kv_cols), "wo": normal(k[1], q_cols, d)}

    def mlp(key):
        k = jax.random.split(key, 2)
        return {"norm1": ones(d), "norm2": ones(d), "w_gate_up": normal(k[0], d, 2 * f), "w_down": normal(k[1], f, d)}

    mixers = {MAMBA: mamba_mixer, ATTENTION: attention_mixer}

    def layer(kind):
        def make(key):
            k = jax.random.split(key, 2)
            return {**mixers[kind](k[0]), **mlp(k[1])}

        return jax.jit(make)

    @jax.jit
    def ends(key):
        return {"embed": normal(key, cfg.vocab_size, d), "norm": ones(d)}

    makers = {kind: layer(kind) for kind in mixers}
    keys = jax.random.split(rng, cfg.n_layer + 1)
    return {**ends(keys[0]), "layers": [makers[kind](key) for kind, key in zip(cfg.layer_types, keys[1:])]}


def serving_params(params, cfg: JambaConfig):
    """The tree a server holds, which ``init_params`` already makes."""
    return params


# ----------------------------------------------------------------------
# the layers' parts
# ----------------------------------------------------------------------
def _mamba_in(y, lp):
    """y [N, d] -> x [N, 5120] before its convolution and the gate z."""
    with jax.named_scope("mamba1.in_proj"):
        return jnp.split(y @ lp["in_proj"], 2, axis=-1)


def _mamba_params(x, lp, cfg):
    """x [N, 5120] after its convolution -> dt [N, 5120] float32 after
    its softplus, B, C [N, 16] float32 after their norms, and ``A^T``
    ``[16, 5120]`` as the scan's layout takes it."""
    with jax.named_scope("mamba1.params"):
        R, N, eps = cfg.mamba_dt_rank, cfg.mamba_d_state, cfg.layer_norm_epsilon
        d, B, C = jnp.split(x @ lp["x_proj"], [R, R + N], axis=-1)
        d = rmsnorm(d, lp["dt_norm"], eps)
        B = rmsnorm(B.astype(jnp.float32), lp["b_norm"], eps)
        C = rmsnorm(C.astype(jnp.float32), lp["c_norm"], eps)
        dt = jax.nn.softplus(jnp.dot(d, lp["dt_proj"], preferred_element_type=jnp.float32) + lp["dt_bias"])
        return dt, B, C, -jnp.exp(lp["A_log"].astype(jnp.float32)).T


def _mamba_out(o, z, lp):
    """The gate AFTER the scan, the way out."""
    with jax.named_scope("mamba1.gate_out"):
        return (o.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))).astype(o.dtype) @ lp["out_proj"]


def mamba_chunk(y, lp, cfg, cache, i, lane, start, n_valid):
    """Mamba layer i on a chunk's normed tokens y [T, d], from lane
    ``lane``'s tail and state (zeros where ``start`` is 0) -> (out [T,
    d], {the tail's name, the state's name: as they stand after the last
    real position})."""
    x, z = _mamba_in(y, lp)
    with jax.named_scope("mamba1.conv"):
        tail = jnp.where(start == 0, 0, cache[tail_name(i)][lane])
        x, tail = conv_tail(x, tail, lp["conv_w"], lp["conv_b"], n_valid)
    dt, B, C, A = _mamba_params(x, lp, cfg)
    with jax.named_scope("mamba1.scan"):
        held = jnp.where(start == 0, 0.0, cache[state_name(i)][lane])
        o, held = mamba1.scan_chunk(x, dt, A, B, C, lp["D"], held, n_valid)
    return _mamba_out(o, z, lp), {tail_name(i): tail, state_name(i): held}


def mamba_decode(y, lp, cfg, cache, i, runs):
    """Mamba layer i on one normed token a lane y [L, d]: the running
    lanes' states updated where they lie, every tail shifted -> (out [L,
    d], {the tail's name, the state's name: the whole new arrays})."""
    x, z = _mamba_in(y, lp)
    with jax.named_scope("mamba1.conv"):
        x, tail = conv_tail(x[:, None], cache[tail_name(i)], lp["conv_w"], lp["conv_b"])
        x = x[:, 0]
    dt, B, C, A = _mamba_params(x, lp, cfg)
    with jax.named_scope("mamba1.step"):
        o, state = mamba1.decode_step(x, dt, A, B, C, lp["D"], cache[state_name(i)], runs)
    return _mamba_out(o, z, lp), {tail_name(i): tail, state_name(i): state}


def _mlp(x, lp, cfg):
    with jax.named_scope("mlp.dense"):
        a, b = jnp.split(rmsnorm(x, lp["norm2"], cfg.layer_norm_epsilon) @ lp["w_gate_up"], 2, axis=-1)
        return (jax.nn.silu(a) * b) @ lp["w_down"]


def _logits(x, params, cfg):
    """The tied head: the embedding, transposed."""
    y = rmsnorm(x, params["norm"], cfg.layer_norm_epsilon)
    return jax.lax.dot_general(y, params["embed"], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


# ----------------------------------------------------------------------
# the two forwards
# ----------------------------------------------------------------------
def prefill_chunk(params, cfg: JambaConfig, cache, tokens, start, last_index, table, lane, block_size: int):
    """One chunk of one prompt: tokens [1, T] at positions ``start ..``,
    of which ``last_index[0] + 1`` are real; table [pages] the
    sequence's physical pages; lane the lane whose state it holds.
    Reads the earlier positions' K and V through the table and, unless
    ``start`` is 0 (then they read as zeros), the lane's tails and
    states.  -> (logits [1, V] at ``last_index``, k, v [2, 1, T, 1, hd]
    the chunk's rows, {}, {"conv_tail_<i>": [3 * 5120], "ssm_state_<i>":
    [16, 5120]} the lane's tail and state after the last real position,
    Mamba layer by layer, COUNTERS)."""
    T = tokens.shape[1]
    n_valid = last_index[0] + 1
    x = params["embed"][tokens[0]]
    where, room = chunk_slots(table, block_size, T, K_BLOCK)
    ks, vs, state = [], [], {}
    for lp, (kind, i) in zip(params["layers"], numbered(cfg.layer_types)):
        y = rmsnorm(x, lp["norm1"], cfg.layer_norm_epsilon)
        if kind == MAMBA:
            out, after = mamba_chunk(y, lp, cfg, cache, i, lane, start, n_valid)
            state.update(after)
        else:
            out, k, v = attention_chunk(y, lp, cfg, cache, i, where, room, start, n_valid)
            ks.append(k)
            vs.append(v)
        x = x + out
        x = x + _mlp(x, lp, cfg)
    return (_logits(x[last_index], params, cfg), jnp.stack(ks)[:, None], jnp.stack(vs)[:, None], {}, state,
            counters(COUNTERS, ssm_chunk_tokens=n_valid * cfg.layer_types.count(MAMBA),
                     kv_blocks_walked=(0, 0)))  # stated: layers.counters says why


def decode_forward_cached(params, cfg: JambaConfig, cache, tok, block_tables, lengths, block_size: int):
    """One decode step: tok [L] at positions lengths [L] (a lane's
    cached positions; 0: the lane does not run), block_tables [L,
    pages].  The Mamba layers update the running lanes' states where they
    lie and shift their tails; the attention layers read the lanes'
    pages where they lie.  -> (logits [L, V], k_new, v_new [2, L, 1,
    hd], {}, {"conv_tail_<i>", "ssm_state_<i>": the whole new arrays},
    COUNTERS)."""
    from ray_tpu.ops.attention import gqa_decode_blocks

    runs = lengths > 0
    x = params["embed"][tok]
    ks, vs, state = [], [], {}
    for lp, (kind, i) in zip(params["layers"], numbered(cfg.layer_types)):
        y = rmsnorm(x, lp["norm1"], cfg.layer_norm_epsilon)
        if kind == MAMBA:
            out, after = mamba_decode(y, lp, cfg, cache, i, runs)
            state.update(after)
        else:
            out, k, v = attention_decode(y, lp, cfg, cache, i, block_tables, lengths, block_size)
            ks.append(k)
            vs.append(v)
        x = x + out
        x = x + _mlp(x, lp, cfg)
    pages = -(-lengths // block_size) * block_size
    n_a, n_m = cfg.layer_types.count(ATTENTION), cfg.layer_types.count(MAMBA)
    return (_logits(x, params, cfg), jnp.stack(ks), jnp.stack(vs), {}, state,
            counters(COUNTERS, kv_positions_attended=lengths.sum() * n_a, kv_positions_gathered=pages.sum() * n_a,
                     ssm_lane_steps=runs.sum() * n_m,
                     kv_blocks_walked=gqa_decode_blocks(cache["k_pages"], lengths, block_size, n_a)))

"""Kimi-Linear (moonshotai/Kimi-Linear-48B-A3B-Instruct, ``model_type:
kimi_linear``) for the serving engine: a decoder of RMSNorm blocks whose
mixer is, three layers in four, Kimi Delta Attention (a gated delta-rule
STATE a sequence, no cache that grows) and, every fourth, latent
attention with NO position encoding over ONE cached row a position; and
whose feed-forward is a dense SwiGLU in the first layer and, after it, a
shared SwiGLU expert beside routed ones of which a chip may hold a
share.

The layer, on tokens ``x [T, 2304]`` (RMSNorm eps 1e-5 with a weight, no
biases; norms, softmax, sigmoids, the router and the delta rule in
float32, matmuls in the serving dtype with float32 accumulation; nothing
anywhere is rotated or otherwise told its position):

1. A KDA layer (``mixer_types`` "K"; 32 heads, ``d_k = d_v = 128``).
   ``h = rmsnorm(x)``; ``q = l2norm(silu(conv4(h W_q)))``, ``k`` alike,
   ``v = silu(conv4(h W_v))``: three causal depthwise convolutions over 4
   positions, zeros before position 0, a head's 128 columns normed to
   length 1.  ``a = -exp(A_log[h]) softplus((h W_a1) W_a2 + dt_bias)``
   (``[32, 128]``, the log-decay a CHANNEL), ``beta = sigmoid(h
   W_beta)`` (``[32]``).  ``S' = Diag(exp(a)) S``; ``S = S' + beta k (v -
   S'^T k)^T``; ``o = S^T q 128^-0.5`` (``ops/kda.py``).  ``y = W_o
   [rmsnorm_head(o) * sigmoid((h W_g1) W_g2)]``.  It caches no keys: the
   last 3 rows each convolution saw and the state, a sequence.
2. An MLA layer ("A"; 32 heads, no query compression).  ``q = h W_q``:
   heads of ``[q_nope 128 | q_pe 64]``; ``[c | k_pe] = h W_dkv`` (512 |
   64); ``c = rmsnorm(c)``.  ``[k_nope 128 | v 128]`` of head ``i`` is
   ``c W_ukv[i]``.  Query ``t`` on position ``s <= t`` scores ``(q_nope .
   k_nope + q_pe . k_pe) 192^-0.5``; softmax, times ``v``, heads side by
   side, ``W_o``.  ``mla_use_nope``: ``q_pe`` and ``k_pe`` are used as
   projected.
3. The first ``first_k_dense_replace`` layers: ``h = rmsnorm(x)``, SwiGLU
   of width 9,216.  The others: ``s = sigmoid(h W_r)`` over ALL 256
   experts; the 8 largest of ``s + b`` (the bias chooses and does not
   weigh); ``g_e = 2.446 s_e / sum of the chosen s``; ``y =
   SwiGLU_shared(h) + sum g_e SwiGLU_e(h)`` over those of the token's 8
   that are HELD here (``experts_first``, ``experts_held``).  What the
   absent experts would add is left out: on the chips of a deployment
   that share a layer the partial sums add up (``ops/moe.py``).
4. After the last layer RMSNorm and the untied head over the rows of the
   vocabulary held.

The module is a *family* to ``serve/llm/engine.py`` that STATES its cache
(``cache_spec``): a paged pool of latent rows ``[c | k_pe]`` for the MLA
layers alone (576 values laid out as ``latent_row`` = 640 columns, whole
lane tiles as ``mistral4.py`` says, keys all 576 and values the first
512, no V pool), and four arrays a lane for every KDA layer
(``kda_tail_q_<i>``, ``kda_tail_k_<i>``, ``kda_tail_v_<i>`` ``[3 *
4096]`` in the serving dtype, ``kda_state_<i>`` ``[32, 128, 128]``
float32).  Its two forwards read that cache and return what to write
into it: ``prefill_chunk`` (``ops.kda.kda_chunk_scan`` from the lane's
state and tails: on a TPU the kernel of ``ops/pallas_kda_chunk.py`` for a
bucket of whole blocks of 64, ``ops.kda.kda_chunk`` elsewhere and for a
prompt's tail under 64; ``ops.mla.expanded_attention`` over the paged
context, its XLA loop: a head's 192 query columns are not whole lane
tiles, which the latent chunk kernel's tiling asks for) and
``decode_forward_cached`` (``ops.pallas_kda.kda_decode_step`` updating
the running lanes' states in place; the ABSORBED attention of ``ops.mla.absorbed_queries`` over the
pages where they lie by ``ops.attention.mla_paged_decode_attention``).

The tree, which ``benchmark/reference_kimi_linear.py`` reads too: ``embed
[V, d]``, ``layers`` (each ``w_in [d]``, ``w_post [d]``; a KDA layer's
``wqkv [d, 3 * 4096]``, ``conv_q``, ``conv_k``, ``conv_v [4096, 4]``,
``wf_down [d, 128]``, ``wf_up [128, 4096]``, ``A_log [32]`` and
``dt_bias [4096]`` float32, ``w_beta [d, 32]``, ``wg_down [d, 128]``,
``wg_up [128, 4096]``, ``w_on [128]``, ``wo [4096, d]``; an MLA layer's
``wq [d, 32 * 192]``, ``wdkv [d, 576]``, ``w_kvn [512]``, ``wukv [512,
32 * 256]`` (a head's ``k_nope | v`` side by side), ``wo [32 * 128,
d]``; then a dense layer's ``wgu_dense [d, 2 * 9216]``, ``wd_dense
[9216, d]``, or an expert layer's ``router [d, 256]``, ``router_bias
[256]`` float32, ``wgu_shared [d, 2f]``, ``wd_shared [f, d]``, ``wgu
[held, d, 2f]``, ``wd [held, f, d]``), ``norm [d]``, ``lm_head [d,
V]``.  Weights are seeded random, made on the device a layer at a time
in the serving dtype.  There is no training path.

ASSUMED, because the source's ``config.json`` does not carry it (the
file ``benchmark/configs/kimi-linear-48b-a3b.json`` lists the same):
bf16 parameters; the two low-rank maps' inner width 128 (the KDA
``head_dim``); the convolutions have no bias and ``silu`` behind them;
the state float32 and the tails in the serving dtype; ``router_bias``
seeded normal, std 0.02; the seeded ``A_log`` and ``dt_bias``
(``init_params``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models.common import CacheSpec, rmsnorm
from ray_tpu.models.layers import chunk_context, chunk_slots, counters, numbered
from ray_tpu.ops import kda
from ray_tpu.ops.mamba2 import conv_tail
from ray_tpu.ops.mla import K_BLOCK, absorbed_queries, expanded_attention

KDA, MLA = "K", "A"
# the published order of the 27 layers (config.json: linear_attn_config.kda_layers and
# full_attn_layers, numbered from 1): every fourth latent, and the last
PUBLISHED_MIXERS = tuple(MLA if n in (4, 8, 12, 16, 20, 24, 27) else KDA for n in range(1, 28))

# What a forward returns after what it writes, summed over its layers.
# Under mistral4.COUNTERS' names and meanings, over the EXPERT layers (a
# dense layer counts nowhere): token-expert pairs the router made; those
# whose expert is held here; pairs computed; held experts that received a
# row; held experts there were; rows of the largest group; layers.  Of a
# decode step the cached positions its latent kernel calls attended, the
# positions of the whole pages they copied, and the calls there were
# (``ops.pallas_mla_paged_attention.lanes_a_call`` lanes each); the
# (lane, KDA layer) states it updated, idle lanes not counted; the real
# tokens x KDA layers a chunk's delta rule took, and those of them that
# went through the chunk kernel (``ops.kda.chunk_kernel_takes``).
COUNTERS = ("moe_pairs_routed", "moe_pairs_held", "moe_pairs", "moe_experts_hit",
            "moe_expert_slots", "moe_peak_rows", "moe_layer_programs",
            "kv_positions_attended", "kv_positions_gathered", "mla_decode_calls",
            "kda_lane_steps", "kda_chunk_tokens", "kda_chunk_kernel_tokens")

_LANE = 128  # columns of a lane tile: a cached row is whole tiles


@dataclass(frozen=True)
class KimiLinearConfig:
    """The source's ``config.json`` under the engine's names where it
    has one, the source's own elsewhere; then the share held here."""

    vocab_size: int = 163840  # rows of the vocabulary HELD (the engine's name); ids are below it
    published_vocab_size: int = 163840
    vocab_first: int = 0  # the first published row held
    mixer_types: tuple = PUBLISHED_MIXERS  # a letter a layer: num_hidden_layers of them
    first_k_dense_replace: int = 1
    d_model: int = 2304  # hidden_size
    n_head: int = 32  # num_attention_heads = num_key_value_heads (the MLA layers')
    kda_num_heads: int = 32  # linear_attn_config.num_heads
    kda_head_dim: int = 128  # linear_attn_config.head_dim: d_k = d_v
    conv_kernel: int = 4  # linear_attn_config.short_conv_kernel_size
    kda_low_rank: int = 128  # the decay's and the gate's inner width (assumed: head_dim)
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64  # the shared key's width; NOT rotated (mla_use_nope)
    v_head_dim: int = 128
    intermediate_size: int = 9216  # the width of a leading dense layer
    moe_intermediate_size: int = 1024  # the width of ONE expert, routed or shared
    n_routed_experts: int = 256  # num_experts: the router's outputs, whatever is held here
    experts_first: int = 0  # the first routed expert held
    experts_held: int = 256
    num_experts_per_tok: int = 8  # num_experts_per_token
    n_shared_experts: int = 1  # num_shared_experts
    norm_topk_prob: bool = True  # moe_renormalize
    routed_scaling_factor: float = 2.446
    max_seq_len: int = 1048576  # model_max_length
    rms_norm_eps: float = 1e-5
    # the seeded decay: A = U(1, 16), dt log-uniform (init_params)
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    prefill_chunk: int = 2048  # most tokens of one prefill program: whole blocks of the delta rule
    dtype: Any = jnp.bfloat16  # parameters and matmuls; norms, softmax, the router and the delta rule are float32

    @property
    def n_layer(self) -> int:
        return len(self.mixer_types)

    @property
    def kda_inner(self) -> int:
        """Columns of q, of k and of v in a KDA layer."""
        return self.kda_num_heads * self.kda_head_dim

    @property
    def latent_row(self) -> int:
        """Columns of a cached row: ``[c | k_pe]`` and zeros up to whole
        lane tiles (576 -> 640)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // _LANE) * _LANE

    @staticmethod
    def kimi_linear_48b_a3b(**kw) -> "KimiLinearConfig":
        return KimiLinearConfig(**kw)  # 49.1B parameters: no one chip builds it

    @staticmethod
    def kimi_linear_48b_a3b_8l_ep8(**kw) -> "KimiLinearConfig":
        """One chip's share of eight that share each layer: layers 1-8 of
        the 27 (the dense layer and seven expert layers; six KDA, two
        MLA: two whole periods), routed experts 0-31 of 256, rows
        0-20,479 of the vocabulary; both mixers and the shared expert
        whole.  4.19 GB in bf16 (benchmark/configs/kimi-linear-48b-a3b.json)."""
        return KimiLinearConfig(**{**dict(mixer_types=PUBLISHED_MIXERS[:8], experts_held=32, vocab_size=20480), **kw})

    @staticmethod
    def kimi_linear_tiny(**kw) -> "KimiLinearConfig":
        """Every width small, one period and a layer: KDA and dense, KDA,
        KDA, MLA, KDA; 4 of 16 experts' shares are what the tests cut it
        into.  A prompt of a few dozen tokens takes several chunks."""
        fields = dict(
            vocab_size=256, published_vocab_size=256, mixer_types=(KDA, KDA, KDA, MLA, KDA), d_model=64,
            n_head=8, kda_num_heads=4, kda_head_dim=16, kda_low_rank=8, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
            n_routed_experts=16, experts_held=16, num_experts_per_tok=4, max_seq_len=512, prefill_chunk=64)
        return KimiLinearConfig(**{**fields, **kw})


def softmax_scale(cfg: KimiLinearConfig) -> float:
    """``(nope + pe)^-0.5``: 192^-0.5 at the published sizes."""
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def _is_dense(cfg, i) -> bool:
    return i < cfg.first_k_dense_replace


def state_name(i: int) -> str:
    return f"kda_state_{i}"


def tail_name(which: str, i: int) -> str:
    return f"kda_tail_{which}_{i}"


# ----------------------------------------------------------------------
# the statement, the weights
# ----------------------------------------------------------------------
def cache_spec(cfg: KimiLinearConfig, block_size: int) -> CacheSpec:
    """The MLA layers page one latent row a position and no V pool (a
    position's values are its row's first ``kv_lora_rank`` columns);
    every KDA layer holds four arrays a lane, its three convolutions'
    tails and the delta rule's state (an array a layer, as
    ``minicpm_sala.cache_spec`` says: a decode step then reads and
    writes whole arrays)."""
    if cfg.prefill_chunk % kda.BLOCK:
        raise ValueError(f"a prompt chunk of {cfg.prefill_chunk} is not whole blocks of {kda.BLOCK}")
    tail = ((cfg.conv_kernel - 1) * cfg.kda_inner,)  # the rows side by side (ops.mamba2.conv_tail)
    state = (cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_head_dim)
    lane_state = []
    for i in range(cfg.mixer_types.count(KDA)):
        lane_state += [(tail_name(which, i), tail, cfg.dtype) for which in "qkv"]
        lane_state.append((state_name(i), state, jnp.float32))
    return CacheSpec(paged_layers=cfg.mixer_types.count(MLA), row_width=cfg.latent_row, v_pool=False,
                     lane_state=tuple(lane_state), prefill_chunk=cfg.prefill_chunk)


def init_params(cfg: KimiLinearConfig, rng=None):
    """Seeded weights in cfg.dtype, made on the device one layer at a
    time, the held experts one at a time within it: matrices normal with
    std 0.02, norm weights 1; the convolutions' weights uniform in
    ``+-conv_kernel^-0.5``; ``A_log = log U(1, 16)`` a head and
    ``dt_bias`` a channel the inverse softplus of a log-uniform draw in
    ``[time_step_min, time_step_max]`` (the published initialisation of
    the gate: a channel's decay a position then lies between 0.2 and
    0.999), both float32; ``router_bias`` normal with std 0.02 (float32:
    small, so that the term is exercised and the scores still decide)."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    d, f, V = cfg.d_model, cfg.moe_intermediate_size, cfg.vocab_size
    H, inner, r = cfg.kda_num_heads, cfg.kda_inner, cfg.kda_low_rank
    Ha, kv = cfg.n_head, cfg.kv_lora_rank
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim

    def normal(key, *shape):
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(cfg.dtype)

    def ones(n):
        return jnp.ones((n,), cfg.dtype)

    def conv_uniform(key):
        bound = cfg.conv_kernel ** -0.5
        return jax.random.uniform(key, (inner, cfg.conv_kernel), jnp.float32, -bound, bound).astype(cfg.dtype)

    def mixer(kind, k):
        if kind == MLA:
            return {"wq": normal(k[0], d, Ha * qk), "wdkv": normal(k[1], d, kv + cfg.qk_rope_head_dim),
                    "w_kvn": ones(kv), "wukv": normal(k[2], kv, Ha * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                    "wo": normal(k[3], Ha * cfg.v_head_dim, d)}
        dt = jnp.exp(jax.random.uniform(k[8], (inner,), jnp.float32, math.log(cfg.time_step_min),
                                        math.log(cfg.time_step_max)))
        return {"wqkv": normal(k[0], d, 3 * inner), "conv_q": conv_uniform(k[1]), "conv_k": conv_uniform(k[2]),
                "conv_v": conv_uniform(k[3]), "wf_down": normal(k[4], d, r), "wf_up": normal(k[5], r, inner),
                "A_log": jnp.log(jax.random.uniform(k[6], (H,), jnp.float32, 1.0, 16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)), "w_beta": normal(k[7], d, H),
                "wg_down": normal(k[9], d, r), "wg_up": normal(k[10], r, inner), "w_on": ones(cfg.kda_head_dim),
                "wo": normal(k[11], inner, d)}

    def feed_forward(dense, k):
        if dense:
            return {"wgu_dense": normal(k[0], d, 2 * cfg.intermediate_size),
                    "wd_dense": normal(k[1], cfg.intermediate_size, d)}
        held = cfg.experts_held
        return {"router": normal(k[0], d, cfg.n_routed_experts),
                "router_bias": 0.02 * jax.random.normal(k[1], (cfg.n_routed_experts,), jnp.float32),
                "wgu_shared": normal(k[2], d, 2 * f * cfg.n_shared_experts),
                "wd_shared": normal(k[3], f * cfg.n_shared_experts, d),
                "wgu": jax.lax.map(lambda e: normal(e, d, 2 * f), jax.random.split(k[4], held)),
                "wd": jax.lax.map(lambda e: normal(e, f, d), jax.random.split(k[5], held))}

    def layer(kind, dense):
        @jax.jit
        def make(key):
            k = jax.random.split(key, 18)
            return {"w_in": ones(d), "w_post": ones(d), **mixer(kind, k[:12]), **feed_forward(dense, k[12:])}

        return make

    @jax.jit
    def ends(key):
        k = jax.random.split(key, 2)
        return {"embed": normal(k[0], V, d), "norm": ones(d), "lm_head": normal(k[1], d, V)}

    makers = {}
    keys = jax.random.split(rng, cfg.n_layer + 1)
    layers = []
    for i, (kind, key) in enumerate(zip(cfg.mixer_types, keys[1:])):
        which = (kind, _is_dense(cfg, i))
        if which not in makers:
            makers[which] = layer(*which)
        layers.append(makers[which](key))
    return {**ends(keys[0]), "layers": layers}


def serving_params(params, cfg: KimiLinearConfig):
    """The tree a server holds, which ``init_params`` already makes."""
    return params


# ----------------------------------------------------------------------
# the KDA mixer
# ----------------------------------------------------------------------
def _l2norm(x):
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt((xf * xf).sum(-1, keepdims=True) + 1e-6)).astype(x.dtype)


def _kda_in(h, lp, cfg):
    """h [N, d] -> q, k, v [N, inner] before their convolutions; the
    log-decay a [N, H, dk] and beta [N, H], float32; the output gate
    before its sigmoid [N, inner]."""
    H, dk = cfg.kda_num_heads, cfg.kda_head_dim
    with jax.named_scope("kda.in_proj"):
        q, k, v = jnp.split(h @ lp["wqkv"], 3, axis=-1)
        f = jnp.dot(h @ lp["wf_down"], lp["wf_up"], preferred_element_type=jnp.float32)
        a = -jnp.exp(lp["A_log"])[:, None] * jax.nn.softplus(f + lp["dt_bias"]).reshape(-1, H, dk)
        beta = jax.nn.sigmoid(jnp.dot(h, lp["w_beta"], preferred_element_type=jnp.float32))
        gate = (h @ lp["wg_down"]) @ lp["wg_up"]
    return q, k, v, a, beta, gate


def _kda_heads(q, k, v, cfg):
    """q, k, v [N, inner] after their convolutions -> [N, H, dk], q and k
    of length 1 a head."""
    shape = (q.shape[0], cfg.kda_num_heads, cfg.kda_head_dim)
    return _l2norm(q.reshape(shape)), _l2norm(k.reshape(shape)), v.reshape(shape)


def _kda_out(o, gate, lp, cfg):
    """The norm over each head's columns, the gate, the way out."""
    with jax.named_scope("kda.gate_out"):
        o = rmsnorm(o, lp["w_on"], cfg.rms_norm_eps).reshape(o.shape[0], -1)
        return (o.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(o.dtype) @ lp["wo"]


def kda_chunk(h, lp, cfg, cache, i, lane, start, n_valid):
    """KDA layer i on a chunk's normed tokens h [T, d], from lane
    ``lane``'s tails and state (zeros where ``start`` is 0) -> (out [T,
    d], {the tails' names, the state's name: as they stand after the
    last real position})."""
    q, k, v, a, beta, gate = _kda_in(h, lp, cfg)
    after = {}
    with jax.named_scope("kda.conv"):
        conved = []
        for which, x in zip("qkv", (q, k, v)):
            tail = jnp.where(start == 0, 0, cache[tail_name(which, i)][lane])
            x, after[tail_name(which, i)] = conv_tail(x, tail, lp["conv_" + which], n_valid=n_valid)
            conved.append(x)
    with jax.named_scope("kda.chunk"):
        held = jnp.where(start == 0, 0.0, cache[state_name(i)][lane])
        o, after[state_name(i)] = kda.kda_chunk_scan(*_kda_heads(*conved, cfg), a, beta, held, n_valid)
    return _kda_out(o, gate, lp, cfg), after


def kda_decode(h, lp, cfg, cache, i, runs):
    """KDA layer i on one normed token a lane h [B, d]: the running
    lanes' states updated where they lie, every tail shifted -> (out [B,
    d], {the tails' names, the state's name: the whole new arrays})."""
    q, k, v, a, beta, gate = _kda_in(h, lp, cfg)
    after = {}
    with jax.named_scope("kda.conv"):
        conved = []
        for which, x in zip("qkv", (q, k, v)):
            x, after[tail_name(which, i)] = conv_tail(x[:, None], cache[tail_name(which, i)], lp["conv_" + which])
            conved.append(x[:, 0])
    with jax.named_scope("kda.decode"):
        o, after[state_name(i)] = kda.kda_decode_step(*_kda_heads(*conved, cfg), a, beta, cache[state_name(i)], runs)
    return _kda_out(o, gate, lp, cfg), after


# ----------------------------------------------------------------------
# the MLA mixer, the feed-forward
# ----------------------------------------------------------------------
def _mla_project(h, lp, cfg):
    """h [N, d] -> q_nope, q_pe [N, H, .] with the softmax scale in them
    (applied in float32), and the row to cache [N, latent_row]: the
    normed latent, the shared key as projected, zeros."""
    N, H = h.shape[0], cfg.n_head
    nope, pe, kv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    with jax.named_scope("mla.project"):
        q = (h @ lp["wq"]).reshape(N, H, nope + pe)
        q = (q.astype(jnp.float32) * softmax_scale(cfg)).astype(q.dtype)
        ckp = h @ lp["wdkv"]
        c = rmsnorm(ckp[:, :kv], lp["w_kvn"], cfg.rms_norm_eps)
        row = jnp.concatenate([c, ckp[:, kv:], jnp.zeros((N, cfg.latent_row - kv - pe), c.dtype)], axis=-1)
        return q[..., :nope], q[..., nope:], row


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


# ----------------------------------------------------------------------
# the two forwards
# ----------------------------------------------------------------------
def prefill_chunk(params, cfg: KimiLinearConfig, cache, tokens, start, last_index, table, lane,
                  block_size: int):
    """``prefill_chosen`` less its last result: what the engine takes."""
    return prefill_chosen(params, cfg, cache, tokens, start, last_index, table, lane, block_size)[:-1]


def prefill_chosen(params, cfg: KimiLinearConfig, cache, tokens, start, last_index, table, lane,
                   block_size: int):
    """One chunk of one prompt: tokens [1, T] at positions ``start ..``,
    of which ``last_index[0] + 1`` are real; table [pages] the
    sequence's physical pages; lane the lane whose state it holds.
    Reads the earlier positions' latent rows through the table and,
    unless ``start`` is 0 (then they read as zeros), the lane's tails and
    states.  -> (logits [1, V] at ``last_index``, the chunk's rows [La,
    1, T, latent_row], None (no V pool), {}, {"kda_tail_<q|k|v>_<i>": [3
    * 4096], "kda_state_<i>": [32, 128, 128]} as they stand after the
    last real position, KDA layer by layer, COUNTERS, and for the checks
    the experts each layer's router chose [L, T, k] (-1 in a dense
    layer))."""
    T = tokens.shape[1]
    n_valid = last_index[0] + 1
    kda_tokens = n_valid * cfg.mixer_types.count(KDA)
    in_kernel = kda.chunk_kernel_takes(T, cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_head_dim)
    x = params["embed"][tokens[0]]
    where, room = chunk_slots(table, block_size, T, K_BLOCK)
    rows_out, state, counts, chose = [], {}, [], []
    for n, (lp, (kind, i)) in enumerate(zip(params["layers"], numbered(cfg.mixer_types))):
        h = rmsnorm(x, lp["w_in"], cfg.rms_norm_eps)
        if kind == KDA:
            out, after = kda_chunk(h, lp, cfg, cache, i, lane, start, n_valid)
            state.update(after)
        else:
            q_nope, q_pe, row = _mla_project(h, lp, cfg)
            ctx = chunk_context(cache["k_pages"], i, where, room, row, start)
            out = expanded_attention(q_nope, q_pe, ctx, lp["wukv"], start, n_valid, cfg) @ lp["wo"]
            rows_out.append(row)
        x = x + out
        y, c, top_e = _feed_forward(x, lp, cfg, _is_dense(cfg, n))
        x = x + y
        chose.append(top_e)
        if c is not None:
            counts.append(c)
    return (_logits(x[last_index], params, cfg), jnp.stack(rows_out)[:, None], None, {}, state,
            counters(COUNTERS, counts, cfg.experts_held, kda_chunk_tokens=kda_tokens,
                     kda_chunk_kernel_tokens=kda_tokens * in_kernel),
            jnp.stack(chose))


def decode_forward_cached(params, cfg: KimiLinearConfig, cache, tok, block_tables, lengths,
                          block_size: int):
    """``decode_chosen`` less its last result: what the engine takes."""
    return decode_chosen(params, cfg, cache, tok, block_tables, lengths, block_size)[:-1]


def decode_chosen(params, cfg: KimiLinearConfig, cache, tok, block_tables, lengths, block_size: int):
    """One decode step: tok [B] at positions lengths [B] (a lane's
    cached positions; 0: the lane does not run), block_tables [B,
    pages].  The KDA layers update the running lanes' states where they
    lie and shift their tails; the MLA layers read the lanes' latent
    pages where they lie, absorbed.  -> (logits [B, V], the fed tokens'
    rows [La, B, latent_row], None, {}, {"kda_tail_<q|k|v>_<i>",
    "kda_state_<i>": the whole new arrays}, COUNTERS, and for the checks
    the experts each layer's router chose [L, B, k])."""
    from ray_tpu.ops.attention import mla_paged_decode_attention
    from ray_tpu.ops.pallas_mla_paged_attention import lanes_a_call

    B, H = tok.shape[0], cfg.n_head
    nope, kv, dv = cfg.qk_nope_head_dim, cfg.kv_lora_rank, cfg.v_head_dim
    runs = lengths > 0
    x = params["embed"][tok]
    rows_out, state, counts, chose = [], {}, [], []
    for n, (lp, (kind, i)) in enumerate(zip(params["layers"], numbered(cfg.mixer_types))):
        h = rmsnorm(x, lp["w_in"], cfg.rms_norm_eps)
        if kind == KDA:
            out, after = kda_decode(h, lp, cfg, cache, i, runs)
            state.update(after)
        else:
            q_nope, q_pe, row = _mla_project(h, lp, cfg)
            with jax.named_scope("mla.absorb"):
                q = absorbed_queries(q_nope, q_pe, lp["wukv"], cfg)
            with jax.named_scope("mla.attend"):
                o_lat = mla_paged_decode_attention(q, row, cache["k_pages"], i, block_tables, lengths,
                                                   block_size=block_size, v_width=kv)
            with jax.named_scope("mla.absorb"):
                w_uv = lp["wukv"].reshape(kv, H, nope + dv)[..., nope:]
                out = jnp.einsum("bhc,chd->bhd", o_lat, w_uv).reshape(B, H * dv) @ lp["wo"]
            rows_out.append(row)
        x = x + out
        y, c, top_e = _feed_forward(x, lp, cfg, _is_dense(cfg, n))
        x = x + y
        chose.append(top_e)
        if c is not None:
            counts.append(c)
    pages = -(-lengths // block_size) * block_size
    n_a, n_k = cfg.mixer_types.count(MLA), cfg.mixer_types.count(KDA)
    calls = B // lanes_a_call(B, H, cfg.latent_row, kv, cfg.dtype)
    return (_logits(x, params, cfg), jnp.stack(rows_out), None, {}, state,
            counters(COUNTERS, counts, cfg.experts_held, kv_positions_attended=lengths.sum() * n_a,
                     kv_positions_gathered=pages.sum() * n_a, mla_decode_calls=calls * n_a,
                     kda_lane_steps=runs.sum() * n_k),
            jnp.stack(chose))

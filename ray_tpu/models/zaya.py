"""ZAYA1 (Zyphra/ZAYA1-8B, ``model_type: zaya``) for the serving engine:
a decoder whose every layer is the same TWO parts, each behind its own
norm and merged into the stream by learned scales, and which hands a
second, narrow stream from layer to layer beside ``x``:

    x = E[tok];  r = 0                                       r [T, 256] float32
    x = a1 * x + b1 * CCA(rmsnorm(x, w1))                    [scale_residual_merge]
    x = a2 * x + b2 * MoE(rmsnorm(x, w2), r);  r = r_l       (r_l: the router's, below)
    logits = rmsnorm(x, w_f) E^T                             [tie_word_embeddings]

- ``CCA``, compressed convolutional attention (arXiv:2510.04476) on ``y``:
  the latents ``[q~ | k~ | v1 | v2] = y W`` (8 + 2 heads of 128, then
  two value rows of 128); two causal convolutions of kernel 2 along the
  sequence over ``[q~ | k~]``, depthwise and then grouped by head; the
  q-k mean from the latents before the convolutions; every head over its
  L2 norm times ``sqrt(128)``, a key head times its temperature ``tau``
  (``ops/cca.py`` has the equations); rotation of the first 64 of each
  head's 128 values, theta 5,000,000; the VALUE SHIFT, K/V head 0
  holding ``y_t W_v1`` and head 1 ``y_{t-1} W_v2``; causal attention, 4
  queries a K/V head, scores ``q . k / sqrt(128)``; ``W_o`` (1024 ->
  d).  It caches ``k_t`` (normed, tempered, rotated) and ``v_t``, 256
  values each a position, AND a tail a sequence: ``[q~ | k~]`` and the
  first convolution's output of the last position it saw, and that
  position's ``y W_v2``.
- ``MoE``: ``u = y W_dn`` (d -> 256); ``r_l = u + gamma r_{l-1}`` (depth
  averaging: the router's state, handed on as it is); ``z =
  rmsnorm(r_l)``, two hidden layers of 256 with biases and gelu, ``p =
  softmax`` in float32 over **17 outputs**: the 16 experts and "no
  expert"; ``e = argmax(p + beta)`` (the balancing biases choose and do
  not weigh); ``p[e] W_d,e (silu(y W_g,e) * (y W_u,e))`` where ``e <
  16`` and **0** where ``e = 16``.  Output 16 is an expert nobody
  holds: ``ops/moe.py``'s ``held`` sorts its pairs behind every group.

The module is a *family* to ``serve/llm/engine.py`` that STATES its cache
(``cache_spec``), the first whose EVERY layer states both kinds: K and V
pages of 256 values a position, and one array a lane a layer
(``cca_tail_<i>`` ``[2 * 1280 + 128]`` in the serving dtype).  ``r`` is
a position's own (``r_l`` of a position needs that position's
``r_{l-1}`` alone) and is not cached.  Its two forwards read the cache
and return what to write into it, as the Nemotron-H family's do; a
chunk attends over the paged context through the shared grouped-query
layer's half that takes q, k and v (``models/layers.py:attend_chunk``);
a decode step attends through
``ops.attention.gqa_paged_decode_attention`` with its scale left at
``hd^-0.5`` (the temperature is in ``k`` before it is cached).
``benchmark/reference_zaya1.py`` is the plain float32 forward of the
same equations and reads the same tree: ``embed [V, d]`` (the head too),
``norm [d]``, ``layers``, each ``norm1, a1, b1, norm2, a2, b2 [d]``;
``wqkv [d, 1280 + 256]``, ``conv0_w [1280, 2]``, ``conv0_b [1280]``,
``conv1_w [10, 2, 128, 128]``, ``conv1_b [1280]``, ``tau [2]``, ``wo
[1024, d]``; ``router_down [d, 256]``, ``router_gamma []``,
``router_norm [256]``, ``router_w1, router_w2 [256, 256]``,
``router_b1, router_b2 [256]``, ``router_w3 [256, 17]``, ``router_beta
[17]``; ``w_in [held, d, 2 * 2048]`` (gate | up side by side),
``w_down [held, 2048, d]``.  Weights are seeded random, made on the
device a layer at a time in the serving dtype.  There is no training
path.

ASSUMED, because the catalog's row of the source does not settle it
(``benchmark/configs/zaya1-8b.json`` lists the same, each with its
reason): the order and form of CCA's steps; which values are shifted
(half of the K/V HEADS); ``sqrt(128)`` on both normed sides; rotation
AFTER the norm; the router's depth, biases, gelu and norm; that the skip
output exists and yields 0; ``p[e]`` not renormalised; the residual
scales as four vectors a layer; bf16 parameters and tails; the seeded
weights (``init_params``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models.common import CacheSpec, rmsnorm, rope
from ray_tpu.models.layers import attend_chunk, chunk_slots, counters
from ray_tpu.ops import cca
from ray_tpu.ops.attention import K_BLOCK

# What a forward returns after what it writes, summed over its layers,
# as ``models/granite_hybrid.py`` names them (every layer has an expert
# part: pairs are tokens x 1 a layer) less the scan's, and one of this
# family's own: the pairs whose token chose output 16, no expert.  Where
# every expert is held, ``held + skipped == routed``.
COUNTERS = ("moe_pairs_routed", "moe_pairs_held", "moe_pairs", "moe_experts_hit",
            "moe_expert_slots", "moe_peak_rows", "moe_layer_programs",
            "kv_positions_attended", "kv_positions_gathered", "moe_pairs_skipped",
            "kv_blocks_walked", "kv_blocks_whole")


# The seeded draws no key of the source sizes (init_params): the depth
# averaging's weight, and the std of ``router_w3`` at which a token's 17
# logits spread by 1.5 (their standard deviation read 1.49 on 20,000
# tokens at the published router widths, three seeds; PR 54).
_ROUTER_GAMMA, _ROUTER_LOGIT_STD = 0.5, 0.3


@dataclass(frozen=True)
class ZayaConfig:
    """The source's ``config.json`` under the engine's names where it
    has one, the source's own elsewhere (the source's key in the
    comment); then the share held here."""

    vocab_size: int = 262272
    n_layer: int = 40  # num_hidden_layers: every one of layer_types is "hybrid"
    d_model: int = 2048  # hidden_size
    n_head: int = 8  # num_attention_heads
    n_kv_head: int = 2  # num_key_value_heads
    head_dim: int = 128
    cca_time0: int = 2  # the depthwise convolution's kernel
    cca_time1: int = 2  # the grouped convolution's
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5000000.0  # rope_parameters.hybrid.rope_theta
    num_experts: int = 16  # the router has one output more: no expert
    experts_first: int = 0  # the first expert held
    experts_held: int = 16
    num_experts_per_tok: int = 1
    moe_intermediate_size: int = 2048
    router_hidden_size: int = 256
    max_seq_len: int = 131072  # max_position_embeddings
    layer_norm_epsilon: float = 1e-5  # rms_norm_eps
    prefill_chunk: int = 2048  # most tokens of one prefill program
    dtype: Any = jnp.bfloat16  # parameters, tails and matmuls; norms, softmax, the router's carry and sums float32

    @property
    def latent_dim(self) -> int:
        """Columns the convolutions run over: every query head, then every key head."""
        return (self.n_head + self.n_kv_head) * self.head_dim

    @property
    def shifted_dim(self) -> int:
        """Columns of a position's values that are the PREVIOUS token's: half of the K/V heads."""
        return self.n_kv_head // 2 * self.head_dim

    @property
    def tail_dim(self) -> int:
        return 2 * self.latent_dim + self.shifted_dim

    @staticmethod
    def zaya1_8b(**kw) -> "ZayaConfig":
        return ZayaConfig(**kw)  # 8.84B parameters by the config's widths: no one chip builds it

    @staticmethod
    def zaya1_8b_20l(**kw) -> "ZayaConfig":
        """The first of two pipeline stages of 20 layers each: every
        expert, all 17 router outputs, the whole vocabulary, every width
        as published.  4,688,636,304 parameters, 9.38 GB in bf16
        (benchmark/configs/zaya1-8b.json)."""
        return ZayaConfig(**{**dict(n_layer=20), **kw})

    @staticmethod
    def zaya1_tiny(**kw) -> "ZayaConfig":
        """Every width small, four queries a K/V head as published, 4
        experts and the skip output.  A prompt of a few dozen tokens
        takes several chunks."""
        fields = dict(vocab_size=256, n_layer=4, d_model=128, n_head=8, n_kv_head=2, head_dim=16, num_experts=4,
                      experts_held=4, moe_intermediate_size=64, router_hidden_size=16, max_seq_len=512,
                      prefill_chunk=8)
        return ZayaConfig(**{**fields, **kw})


def tail_name(i: int) -> str:
    return f"cca_tail_{i}"


# ----------------------------------------------------------------------
# the statement, the weights
# ----------------------------------------------------------------------
def cache_spec(cfg: ZayaConfig, block_size: int) -> CacheSpec:
    """EVERY layer pages K and V of the K/V heads alone and holds one
    array a lane beside them: the tail of its convolutions and of its
    value shift (an array a layer, as ``minicpm_sala.cache_spec`` says;
    flat, as ``ops/mamba2.py:conv_tail`` says)."""
    if (cfg.cca_time0, cfg.cca_time1) != (2, 2):
        raise ValueError(f"ops/cca.py writes kernels of 2 and 2, not {cfg.cca_time0} and {cfg.cca_time1}")
    if cfg.n_kv_head % 2 or cfg.n_head % cfg.n_kv_head or cfg.num_experts_per_tok != 1:
        raise ValueError("the value shift takes half of an even number of K/V heads, and the router chooses one output")
    lane_state = tuple((tail_name(i), (cfg.tail_dim,), cfg.dtype) for i in range(cfg.n_layer))
    return CacheSpec(paged_layers=cfg.n_layer, row_width=cfg.n_kv_head * cfg.head_dim,
                     lane_state=lane_state, prefill_chunk=cfg.prefill_chunk)


def init_params(cfg: ZayaConfig, rng=None):
    """Seeded weights in cfg.dtype, made on the device one layer at a
    time, the held experts one at a time within it, drawn so that NO
    mechanism is invisible to a check (a scale of 1, a gamma of 0 or a
    router whose 17 logits all read 0 would pass with the mechanism
    deleted): matrices normal with std 0.02, norm weights 1; ``conv0_w``
    uniform in +-0.707 and ``conv1_w`` normal with std ``(2 hd)^-0.5``
    (either convolution keeps its input's scale), their biases 0; the
    four residual scales uniform in [0.75, 1.25]; ``tau`` uniform in
    [1.5, 2.5] (below); ``router_gamma`` 0.5; ``router_down`` normal with std ``d^-0.5`` and
    ``router_w1``, ``router_w2`` with ``R^-0.5`` (unit scale through the
    router's layers), their biases 0; ``router_w3`` normal with std
    0.3 (``_ROUTER_LOGIT_STD``); ``router_beta`` 0.  ``router_w2`` and
    ``router_w3`` read a gelu's output, whose mean is positive: as
    ``nemotron_h.init_params`` says of what reads relu^2, a plain draw
    adds the SAME vector to every token's logits, an output's bias of
    the seed's own, so their columns sum to ZERO over the hidden axis.
    ``tau``: a score is ``tau sqrt(hd) cos``, and under a temperature
    near 1 a query of seeded weights attends some 70 of 1,000 keys about
    alike: every layer then hands each token of a sequence the MEAN of
    that sequence's values, the same vector, which the token's own part
    is averaged away beside, and the stream collapses onto it with depth
    (59% of what the sixth layer's router reads, and one output taking
    5.4 times its share of a sequence's tokens; PERF.md section 6, PR
    54).  At 1.5 to 2.5 a query gives its own position about two thirds
    of its weight and a handful of others the rest, as a trained head
    does: the common part stays at 6% and no layer's largest share of a
    sequence passes 1.7 times 1/17, while the cached rows still carry a
    third of every attention output."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    d, V, f, R, hd = cfg.d_model, cfg.vocab_size, cfg.moe_intermediate_size, cfg.router_hidden_size, cfg.head_dim
    S, heads = cfg.latent_dim, cfg.n_head + cfg.n_kv_head

    def normal(key, *shape, std=0.02):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(cfg.dtype)

    def zero_sum(key, *shape, std):
        w = std * jax.random.normal(key, shape, jnp.float32)
        return (w - w.mean(-2, keepdims=True)).astype(cfg.dtype)

    def uniform(key, *shape, lo, hi):
        return jax.random.uniform(key, shape, jnp.float32, lo, hi).astype(cfg.dtype)

    def ones(n):
        return jnp.ones((n,), cfg.dtype)

    def zeros(n):
        return jnp.zeros((n,), cfg.dtype)

    @jax.jit
    def layer(key):
        k = jax.random.split(key, 16)
        scales = {name: uniform(k[10 + j], d, lo=0.75, hi=1.25) for j, name in enumerate(("a1", "b1", "a2", "b2"))}
        return {
            "norm1": ones(d), "norm2": ones(d), **scales,
            "wqkv": normal(k[0], d, S + 2 * cfg.shifted_dim), "wo": normal(k[1], cfg.n_head * hd, d),
            "conv0_w": uniform(k[2], S, 2, lo=-0.707, hi=0.707), "conv0_b": zeros(S),
            "conv1_w": normal(k[3], heads, 2, hd, hd, std=(2 * hd) ** -0.5), "conv1_b": zeros(S),
            "tau": uniform(k[4], cfg.n_kv_head, lo=1.5, hi=2.5),
            "router_down": normal(k[5], d, R, std=d ** -0.5),
            "router_gamma": jnp.asarray(_ROUTER_GAMMA, cfg.dtype), "router_norm": ones(R),
            "router_w1": normal(k[6], R, R, std=R ** -0.5), "router_b1": zeros(R),
            "router_w2": zero_sum(k[7], R, R, std=R ** -0.5), "router_b2": zeros(R),
            "router_w3": zero_sum(k[8], R, cfg.num_experts + 1, std=_ROUTER_LOGIT_STD),
            "router_beta": zeros(cfg.num_experts + 1),
            "w_in": jax.lax.map(lambda e: normal(e, d, 2 * f), jax.random.split(k[9], cfg.experts_held)),
            "w_down": jax.lax.map(lambda e: normal(e, f, d), jax.random.split(k[14], cfg.experts_held)),
        }

    @jax.jit
    def ends(key):
        return {"embed": normal(key, V, d), "norm": ones(d)}

    keys = jax.random.split(rng, cfg.n_layer + 1)
    return {**ends(keys[0]), "layers": [layer(key) for key in keys[1:]]}


def serving_params(params, cfg: ZayaConfig):
    """The tree a server holds, which ``init_params`` already makes."""
    return params


# ----------------------------------------------------------------------
# the layers' parts
# ----------------------------------------------------------------------
def _latents(y, lp, cfg):
    """y [N, d] -> s [N, S] = [q~ | k~], v1, v2 [N, shifted_dim]."""
    S, vh = cfg.latent_dim, cfg.shifted_dim
    sv = y @ lp["wqkv"]
    return sv[:, :S], sv[:, S:S + vh], sv[:, S + vh:]


def _rotated(x, pos, cfg):
    """The first ``partial_rotary_factor`` of each head's values rotated
    by its position, the rest as they are.  x [N, ..., hd]; pos [N]."""
    rot = int(cfg.head_dim * cfg.partial_rotary_factor)
    heads = x.reshape(x.shape[0], -1, cfg.head_dim)
    turned = rope(heads[..., :rot], pos, cfg.rope_theta)
    return jnp.concatenate([turned, heads[..., rot:]], axis=-1).reshape(x.shape)


def _qkv(s, c1, v1, v2_prev, lp, cfg, pos):
    """The mixed latents -> q [N, G, R, hd], k, v [N, G, hd] as the
    attention and the cache take them."""
    q, k = cca.cca_heads(s, c1, lp["tau"], cfg.n_head, cfg.n_kv_head)
    v = jnp.concatenate([v1, v2_prev], axis=-1).reshape(-1, cfg.n_kv_head, cfg.head_dim)
    return _rotated(q, pos, cfg), _rotated(k, pos, cfg), v


def cca_chunk(y, lp, cfg, cache, i, lane, where, room, start, n_valid):
    """Layer i's attention on a chunk's normed tokens y [T, d], from
    lane ``lane``'s tail (zeros where ``start`` is 0) over the
    sequence's cached rows and the chunk's own -> (out [T, d], k, v [T,
    G, hd], the tail after the last real position)."""
    with jax.named_scope("attn.cca.mix"):
        s, v1, v2 = _latents(y, lp, cfg)
        tail = jnp.where(start == 0, 0, cache[tail_name(i)][lane])
        c1, v2_prev, tail = cca.cca_mix_chunk(s, v2, tail, lp, n_valid)
        q, k, v = _qkv(s, c1, v1, v2_prev, lp, cfg, start + jnp.arange(y.shape[0]))
    with jax.named_scope("attn.cca"):
        return attend_chunk(q, k, v, cache, i, where, room, start, n_valid) @ lp["wo"], k, v, tail


def cca_decode(y, lp, cfg, cache, i, block_tables, lengths, block_size):
    """Layer i's attention on one normed token a lane y [B, d] over the
    lanes' pages where they lie -> (out [B, d], k, v [B, G, hd], the
    tails [B, tail_dim]: a running lane's moved on, another's as it
    was)."""
    from ray_tpu.ops.attention import gqa_paged_decode_attention

    with jax.named_scope("attn.cca.mix"):
        s, v1, v2 = _latents(y, lp, cfg)
        c1, v2_prev, tails = cca.cca_mix_step(s, v2, cache[tail_name(i)], lp, lengths > 0)
        q, k, v = _qkv(s, c1, v1, v2_prev, lp, cfg, lengths)
    with jax.named_scope("attn.cca"):
        o = gqa_paged_decode_attention(q, k, v, cache["k_pages"], cache["v_pages"], i, block_tables, lengths,
                                       block_size=block_size)
        return o.reshape(y.shape[0], -1) @ lp["wo"], k, v, tails


def _router(y, r, lp, cfg):
    """The router on normed tokens y [N, d] and the state r [N, R]
    float32 the layer before handed on -> (this layer's state, the
    chosen output's probability [N, 1] float32, the chosen output [N, 1]
    int32: an expert, or ``num_experts``: none)."""
    f32 = jnp.float32

    def dense(a, w, b):
        return jax.nn.gelu(jnp.dot(a.astype(cfg.dtype), lp[w], preferred_element_type=f32) + lp[b].astype(f32),
                           approximate=False)

    with jax.named_scope("moe.router"):
        r = jnp.dot(y, lp["router_down"], preferred_element_type=f32) + lp["router_gamma"].astype(f32) * r
        a = dense(dense(rmsnorm(r, lp["router_norm"], cfg.layer_norm_epsilon), "router_w1", "router_b1"),
                  "router_w2", "router_b2")
        p = jax.nn.softmax(jnp.dot(a.astype(cfg.dtype), lp["router_w3"], preferred_element_type=f32), axis=-1)
    with jax.named_scope("moe.route"):
        top_e = jnp.argmax(p + lp["router_beta"].astype(f32), axis=-1).astype(jnp.int32)[:, None]
        return r, jnp.take_along_axis(p, top_e, axis=-1), top_e


def _experts(y, r, lp, cfg):
    """The expert part on normed tokens y [T, d] and the router's state
    r [T, R]: what to add to the stream before its scale (the chosen
    expert's output times its probability where it is held, 0 where it
    is absent or output 16 was chosen), this layer's state, the layer's
    counters [routed, held, computed, hit, peak, skipped], and the
    outputs chosen [T, 1]."""
    from ray_tpu.ops.moe import moe_experts

    r, top_p, top_e = _router(y, r, lp, cfg)
    with jax.named_scope("moe.route"):
        here = (top_e >= cfg.experts_first) & (top_e < cfg.experts_first + cfg.experts_held)
        skipped = top_e == cfg.num_experts
    # output 16 is an expert nobody holds: the share is always stated, even where all 16 are held
    out, c = moe_experts(y, top_p, top_e, lp["w_in"], lp["w_down"], held=(cfg.experts_first, cfg.experts_held))
    counts = jnp.concatenate([jnp.stack([jnp.int32(top_e.size), here.sum(dtype=jnp.int32)]), c,
                              skipped.sum(dtype=jnp.int32)[None]])
    return out, r, counts, top_e


def _merge(x, out, a, b):
    """``a * x + b * out``, float32 inside, in x's dtype."""
    return (a.astype(jnp.float32) * x.astype(jnp.float32) + b.astype(jnp.float32) * out.astype(jnp.float32)
            ).astype(x.dtype)


def _logits(x, params, cfg):
    """The tied head: the embedding's rows, transposed."""
    y = rmsnorm(x, params["norm"], cfg.layer_norm_epsilon)
    return jax.lax.dot_general(y, params["embed"], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


# ----------------------------------------------------------------------
# the two forwards
# ----------------------------------------------------------------------
def prefill_chunk(params, cfg: ZayaConfig, cache, tokens, start, last_index, table, lane, block_size: int):
    """``prefill_chosen`` less its last result: what the engine takes."""
    return prefill_chosen(params, cfg, cache, tokens, start, last_index, table, lane, block_size)[:-1]


def prefill_chosen(params, cfg: ZayaConfig, cache, tokens, start, last_index, table, lane, block_size: int):
    """One chunk of one prompt: tokens [1, T] at positions ``start ..``,
    of which ``last_index[0] + 1`` are real; table [pages] the
    sequence's physical pages; lane the lane whose tails it holds.
    Reads the earlier positions' K and V through the table and, unless
    ``start`` is 0 (then they read as zeros), the lane's tails.  ->
    (logits [1, V] at ``last_index``, k, v [L, 1, T, G, hd] the chunk's
    rows, {}, {"cca_tail_<i>": [tail_dim]} the lane's tail after the
    last real position, layer by layer, COUNTERS, and for the checks the
    output each layer's router chose [L, T, 1])."""
    T = tokens.shape[1]
    n_valid = last_index[0] + 1
    x = params["embed"][tokens[0]]
    r = jnp.zeros((T, cfg.router_hidden_size), jnp.float32)
    where, room = chunk_slots(table, block_size, T, K_BLOCK)
    ks, vs, state, counts, chose = [], [], {}, [], []
    for i, lp in enumerate(params["layers"]):
        out, k, v, state[tail_name(i)] = cca_chunk(
            rmsnorm(x, lp["norm1"], cfg.layer_norm_epsilon), lp, cfg, cache, i, lane, where, room, start, n_valid)
        ks.append(k)
        vs.append(v)
        x = _merge(x, out, lp["a1"], lp["b1"])
        out, r, c, top_e = _experts(rmsnorm(x, lp["norm2"], cfg.layer_norm_epsilon), r, lp, cfg)
        counts.append(c)
        chose.append(top_e)
        x = _merge(x, out, lp["a2"], lp["b2"])
    return (_logits(x[last_index], params, cfg), jnp.stack(ks)[:, None], jnp.stack(vs)[:, None], {}, state,
            counters(COUNTERS, counts, cfg.experts_held, kv_blocks_walked=(0, 0)),  # stated: layers.counters says why
            jnp.stack(chose))


def decode_forward_cached(params, cfg: ZayaConfig, cache, tok, block_tables, lengths, block_size: int):
    """``decode_chosen`` less its last result: what the engine takes."""
    return decode_chosen(params, cfg, cache, tok, block_tables, lengths, block_size)[:-1]


def decode_chosen(params, cfg: ZayaConfig, cache, tok, block_tables, lengths, block_size: int):
    """One decode step: tok [B] at positions lengths [B] (a lane's
    cached positions; 0: the lane does not run), block_tables [B,
    pages].  Every layer moves the running lanes' tails on and reads the
    lanes' pages where they lie.  -> (logits [B, V], k_new, v_new [L, B,
    G, hd], {}, {"cca_tail_<i>": the whole new array}, COUNTERS, and for
    the checks the output each layer's router chose [L, B, 1])."""
    from ray_tpu.ops.attention import gqa_decode_blocks

    x = params["embed"][tok]
    r = jnp.zeros((tok.shape[0], cfg.router_hidden_size), jnp.float32)
    ks, vs, state, counts, chose = [], [], {}, [], []
    for i, lp in enumerate(params["layers"]):
        out, k, v, state[tail_name(i)] = cca_decode(
            rmsnorm(x, lp["norm1"], cfg.layer_norm_epsilon), lp, cfg, cache, i, block_tables, lengths, block_size)
        ks.append(k)
        vs.append(v)
        x = _merge(x, out, lp["a1"], lp["b1"])
        out, r, c, top_e = _experts(rmsnorm(x, lp["norm2"], cfg.layer_norm_epsilon), r, lp, cfg)
        counts.append(c)
        chose.append(top_e)
        x = _merge(x, out, lp["a2"], lp["b2"])
    pages = -(-lengths // block_size) * block_size
    return (_logits(x, params, cfg), jnp.stack(ks), jnp.stack(vs), {}, state,
            counters(COUNTERS, counts, cfg.experts_held, kv_positions_attended=lengths.sum() * cfg.n_layer,
                     kv_positions_gathered=pages.sum() * cfg.n_layer,
                     kv_blocks_walked=gqa_decode_blocks(cache["k_pages"], lengths, block_size, cfg.n_layer)),
            jnp.stack(chose))

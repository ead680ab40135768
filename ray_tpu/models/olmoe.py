"""OLMoE (allenai/OLMoE-1B-7B; arXiv:2409.02060) for the serving engine.

A decoder of RMSNorm blocks: multi-head attention with rotary positions
and an RMSNorm over the whole query and key projections, then 64 SwiGLU
experts of which a float32 softmax router picks 8 a token, their
weights NOT renormalised.  Untied head, no biases.

Pure functions over a parameter tree, like the inference plane of
``models/gpt2.py``; the module is a *family* to ``serve/llm/engine.py``:
``init_params``, ``cache_spec``, ``prefill_forward``, ``decode_forward_paged``, and a
config whose sizes go by the engine's names (``n_layer``, ``d_model``,
``n_head``, ``max_seq_len``, ``vocab_size``, ``dtype``).  Its forwards
return, after K and V, the int32 counters named by ``COUNTERS``.

Parameters live in the serving dtype and are made on the device a layer
at a time (``init_params``): the published model is 6.9B parameters, and
a float32 copy of the tree would be 28 GB.  The tree is ``embed [V, d]``,
``layers`` (a list of ``w_in [d]``, ``wqkv [d, 3d]``, ``w_qn [d]``,
``w_kn [d]``, ``wo [d, d]``, ``w_post [d]``, ``router [d, E]``,
``wgu [E, d, 2f]`` with gate and up side by side, ``wd [E, f, d]``),
``norm [d]``, ``lm_head [d, V]``; ``benchmark/reference_olmoe.py`` reads
the same tree.  There is no training path yet (ROADMAP.md Queue 2).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models.common import CacheSpec, rmsnorm, rope

# What a forward returns after its K and V, summed over its layers
# (``ops.moe.moe_experts`` counts the first three where they happen):
# token-expert pairs computed; experts that received at least one row;
# rows of the largest group; experts there were; layers.
COUNTERS = ("moe_pairs", "moe_experts_hit", "moe_peak_rows", "moe_expert_slots",
            "moe_layer_programs")


@dataclass(frozen=True)
class OlmoeConfig:
    """The source's ``config.json`` under the engine's names where it
    has one, the source's own elsewhere."""

    vocab_size: int = 50304
    n_layer: int = 16  # num_hidden_layers
    n_head: int = 16  # num_attention_heads = num_key_value_heads
    d_model: int = 2048  # hidden_size
    max_seq_len: int = 4096  # max_position_embeddings
    num_experts: int = 64
    num_experts_per_tok: int = 8
    intermediate_size: int = 1024  # the width of ONE expert
    norm_topk_prob: bool = False
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16  # parameters and matmuls; norms and the router's softmax are float32

    @staticmethod
    def olmoe_1b_7b(**kw) -> "OlmoeConfig":
        return OlmoeConfig(**kw)  # 6.92B parameters, 1.3B active a token

    @staticmethod
    def olmoe_1b_7b_12l(**kw) -> "OlmoeConfig":
        """Twelve of the sixteen layers: what one 16 GB chip holds in
        bf16 beside a KV pool (benchmark/configs/olmoe-1b-7b.json)."""
        return OlmoeConfig(n_layer=12, **kw)

    @staticmethod
    def olmoe_tiny(**kw) -> "OlmoeConfig":
        return OlmoeConfig(vocab_size=256, n_layer=2, n_head=4, d_model=64, max_seq_len=128,
                           num_experts=8, num_experts_per_tok=2, intermediate_size=32, **kw)


def init_params(cfg: OlmoeConfig, rng=None):
    """Seeded weights (normal, std 0.02; norm weights 1) in cfg.dtype,
    made on the device one layer at a time."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    d, f, E, V = cfg.d_model, cfg.intermediate_size, cfg.num_experts, cfg.vocab_size

    def normal(key, *shape):
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(cfg.dtype)

    def ones():
        return jnp.ones((d,), cfg.dtype)

    @jax.jit
    def layer(key):
        k = jax.random.split(key, 5)
        return {
            "w_in": ones(), "wqkv": normal(k[0], d, 3 * d), "w_qn": ones(), "w_kn": ones(),
            "wo": normal(k[1], d, d), "w_post": ones(), "router": normal(k[2], d, E),
            "wgu": normal(k[3], E, d, 2 * f), "wd": normal(k[4], E, f, d),
        }

    @jax.jit
    def ends(key):
        k = jax.random.split(key, 2)
        return {"embed": normal(k[0], V, d), "norm": ones(), "lm_head": normal(k[1], d, V)}

    keys = jax.random.split(rng, cfg.n_layer + 1)
    return {**ends(keys[0]), "layers": [layer(key) for key in keys[1:]]}


def cache_spec(cfg: OlmoeConfig, block_size: int):
    """What the family caches (``models/common.py:CacheSpec``): K and V
    of all heads, a position, in every layer; nothing else."""
    return CacheSpec(paged_layers=cfg.n_layer, row_width=cfg.d_model)


def serving_params(params, cfg: OlmoeConfig):
    """The tree a server holds, which ``init_params`` already makes:
    every leaf is in ``cfg.dtype``, as the forwards' matmuls read it."""
    return params


def _qkv(h, lp, cfg, pos):
    """The layer's queries, keys and values of tokens h [..., d] at
    positions pos [...]: q and k normed over all d columns, split into
    heads, rotated.  -> three [..., H, Dh]."""
    q, k, v = jnp.split(h @ lp["wqkv"], 3, axis=-1)
    q = rmsnorm(q, lp["w_qn"], cfg.rms_norm_eps)
    k = rmsnorm(k, lp["w_kn"], cfg.rms_norm_eps)
    q, k, v = (t.reshape(*t.shape[:-1], cfg.n_head, -1) for t in (q, k, v))
    return rope(q, pos, cfg.rope_theta), rope(k, pos, cfg.rope_theta), v


def _experts(x, lp, cfg):
    """The expert half of a block on tokens x [T, d]: the output to add
    to x, and ``moe_experts``' counters."""
    from ray_tpu.ops.moe import moe_experts

    h = rmsnorm(x, lp["w_post"], cfg.rms_norm_eps)
    with jax.named_scope("moe.route"):
        logits = jnp.dot(h, lp["router"], preferred_element_type=jnp.float32)
        top_p, top_e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.num_experts_per_tok)
        if cfg.norm_topk_prob:
            top_p = top_p / top_p.sum(-1, keepdims=True)
    return moe_experts(h, top_p, top_e, lp["wgu"], lp["wd"])


def _counters(cfg, per_layer):
    """COUNTERS of one program from its layers' [pairs, hit, peak]."""
    consts = jnp.array([cfg.num_experts * cfg.n_layer, cfg.n_layer], jnp.int32)
    return jnp.concatenate([jnp.stack(per_layer).sum(0).astype(jnp.int32), consts])


def prefill_forward(params, cfg: OlmoeConfig, tokens, last_index=None):
    """Full-prompt forward from position 0, as ``gpt2.prefill_forward``:
    tokens [B, T] -> (logits_last [B, vocab], k [L, B, T, H, Dh] (after
    the rotation: what decode attends back to), v, counters)."""
    from ray_tpu.ops.attention import reference_causal_attention

    B, T = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    x = params["embed"][tokens]
    ks, vs, counts = [], [], []
    for lp in params["layers"]:
        q, k, v = _qkv(rmsnorm(x, lp["w_in"], cfg.rms_norm_eps), lp, cfg, pos)
        att = reference_causal_attention(q, k, v).reshape(B, T, cfg.d_model)
        x = x + att @ lp["wo"]
        y, c = _experts(x.reshape(B * T, cfg.d_model), lp, cfg)
        x = x + y.reshape(B, T, cfg.d_model)
        ks.append(k)
        vs.append(v)
        counts.append(c)
    x_last = x[:, -1] if last_index is None else x[jnp.arange(B), last_index]
    logits = rmsnorm(x_last, params["norm"], cfg.rms_norm_eps) @ params["lm_head"]
    return logits, jnp.stack(ks), jnp.stack(vs), _counters(cfg, counts)


def decode_forward_paged(params, cfg: OlmoeConfig, tok, k_pages, v_pages,
                         block_tables, lengths, block_size: int):
    """One decode step over a paged KV pool read in place, as
    ``gpt2.decode_forward_paged``: tok [B], lengths [B] a lane's cached
    positions and so its fed token's position -> (logits [B, vocab],
    k_new [L, B, H, Dh], v_new, counters)."""
    from ray_tpu.ops.attention import paged_decode_attention

    x = params["embed"][tok]
    ks, vs, counts = [], [], []
    for i, lp in enumerate(params["layers"]):
        q, k, v = _qkv(rmsnorm(x, lp["w_in"], cfg.rms_norm_eps), lp, cfg, lengths)
        att = paged_decode_attention(
            q, k, v, k_pages, v_pages, i, block_tables, lengths, block_size=block_size
        )
        x = x + att.reshape(-1, cfg.d_model) @ lp["wo"]
        y, c = _experts(x, lp, cfg)
        x = x + y
        ks.append(k)
        vs.append(v)
        counts.append(c)
    logits = rmsnorm(x, params["norm"], cfg.rms_norm_eps) @ params["lm_head"]
    return logits, jnp.stack(ks), jnp.stack(vs), _counters(cfg, counts)

"""GPT-2 in Flax, TPU-first.

The north-star workload ("Ray Train GPT-2 tokens/sec/chip",
BASELINE.json).  Design notes:

- bf16 compute / f32 params+optimizer (MXU-native precision).
- Param names (qkv / attn_out / mlp_up / mlp_down / wte / wpe / lm_head)
  are what ray_tpu.train.sharding.gpt2_partition_rules matches: mesh
  layouts come from that one rule table.
- The fused qkv kernel is one [d, 3d] leaf, columns q | k | v (the
  tree the serving forwards, the checkpoints and the benchmark's
  reference read).  Under a mesh with a `model` axis it is stored by
  rows and exchanged as weights, so that q, k and v are computed on the
  device that holds their heads and no activation moves around the
  attention kernel (`_qkv_by_head`); with no such axis it is a Dense.
- `remat` wraps each block with jax.checkpoint to trade FLOPs for HBM.
- Attention goes through ray_tpu.ops.attention which picks a fused
  implementation (Pallas splash/ring kernel on TPU, reference einsum
  elsewhere); sequence parallelism shards the seq dim over the mesh
  axis `sp_axis` names.
- Static shapes everywhere; the block stack uses a Python loop (unrolled
  by trace) — swap to nn.scan for very deep configs.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50304  # 50257 padded to a multiple of 128 for the MXU
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    max_seq_len: int = 1024
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    use_bias: bool = True
    # Sequence parallelism: when mesh has a >1 `sp_axis`, attention runs
    # as ring attention over it (ops.ring_attention).  Mesh is static
    # metadata for tracing (hashable, compared by identity of devices).
    mesh: Any = None
    sp_axis: Optional[str] = None

    @staticmethod
    def tiny(**kw) -> "GPT2Config":
        return GPT2Config(vocab_size=512, n_layer=2, n_head=4, d_model=128, max_seq_len=128, **kw)

    @staticmethod
    def small(**kw) -> "GPT2Config":
        return GPT2Config(**kw)  # 124M

    @staticmethod
    def medium(**kw) -> "GPT2Config":
        return GPT2Config(n_layer=24, n_head=16, d_model=1024, **kw)  # 350M

    @staticmethod
    def large(**kw) -> "GPT2Config":
        return GPT2Config(n_layer=36, n_head=20, d_model=1280, **kw)  # 774M


class Attention(nn.Module):
    cfg: GPT2Config

    @nn.compact
    def __call__(self, x, mask=None):
        cfg = self.cfg
        d_head = cfg.d_model // cfg.n_head
        B, T = x.shape[0], x.shape[1]
        from ray_tpu.ops.attention import causal_attention, mesh_split

        qkv = nn.Dense(3 * cfg.d_model, use_bias=cfg.use_bias, dtype=cfg.dtype,
                       param_dtype=cfg.param_dtype, name="qkv")
        # the sequence axis is ring attention's: it splits T, not B
        split = mesh_split(B, cfg.n_head, skip=(cfg.sp_axis,))
        if split is None or split[2] is None or self.is_initializing():
            q, k, v = jnp.split(qkv(x), 3, axis=-1)
        else:
            q, k, v = _qkv_by_head(x, qkv.variables["params"], cfg.dtype, *split)

        def heads(t):
            return t.reshape(B, T, cfg.n_head, d_head)

        out = causal_attention(
            heads(q), heads(k), heads(v), mesh=cfg.mesh, sp_axis=cfg.sp_axis
        )
        out = out.reshape(B, T, cfg.d_model)
        return nn.Dense(cfg.d_model, use_bias=cfg.use_bias, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="attn_out")(out)


@partial(jax.jit, static_argnums=(2, 3, 4, 5))  # the layers of a model trace and lower it once
def _qkv_by_head(x, p, dtype, mesh, batch_axes, head_axes):
    """The fused projection under a mesh whose ``model`` axis (n wide)
    divides the heads: q, k, v [B, T, d] of ``x @ kernel + bias``, each
    leaving with its columns over ``model``, a head's on one device: the
    layout ``ops.attention``'s shard_map of the flash kernel asks for.

    The kernel [d, 3d] is stored q | k | v, so no block of its columns
    holds a head's q, k and v, and a projection sharded by columns emits
    activations that must cross the mesh before the kernel.  Stored by
    ROWS over ``model`` (``gpt2_partition_rules``), a device holds d/n
    rows of every column; one all-to-all of the weights in the compute
    dtype swaps those for all d rows of its own heads' columns (the
    gradient goes back the same way), and the matmul is local.  The
    weights are a fifth of the bytes of q, k, v and their gradients at
    the mesh cell's batch, and do not grow with it."""
    from jax.experimental.layout import Layout, with_layout_constraint
    from jax.sharding import PartitionSpec as P

    (axis,) = head_axes
    n = mesh.shape[axis]
    d = x.shape[-1]
    bias = [p["bias"].astype(dtype).reshape(3, n, d // n)] if "bias" in p else []

    def row_major(a):
        # Left to itself the TPU compiler lays the exchanged blocks out
        # rows-minor and transposes the float32 kernel, both AdamW
        # moments and the gradient to match, 59 MB of copies a layer;
        # pinned, what stands around an exchange is one pass over the
        # 4.9 MB it moves.  The last two dims (rows and columns of a
        # block) are the tiled ones.
        return with_layout_constraint(a, Layout(major_to_minor=tuple(range(a.ndim))))

    def local(x, w, *b):  # x [B/b, T, d], w [d/n, 3d], b [3, 1, d/n]
        with jax.named_scope("gpt2.qkv_exchange"):
            # [to device, q|k|v, my rows, its columns] -> [from device, q|k|v, its rows, my columns]
            w = row_major(row_major(w).reshape(d // n, 3, n, d // n).transpose(2, 1, 0, 3))
            w = row_major(jax.lax.all_to_all(w, axis, 0, 0, tiled=True))
        y = jnp.einsum("btd,cdk->cbtk", x, w.transpose(1, 0, 2, 3).reshape(3, d, d // n))
        return y + b[0][:, :, None] if b else y

    # only the axes named here are manual: any other (ring attention's
    # sequence axis over T) stays the partitioner's.  The kernel's spec
    # is how gpt2_partition_rules stores it (held to each other in
    # tests/test_sharding_rules.py); stored otherwise, it is resharded
    # to this first
    return jax.shard_map(
        local, mesh=mesh, axis_names={axis, *(batch_axes or ())},
        in_specs=(P(batch_axes, None, None), P(axis, None)) + (P(None, axis, None),) * len(bias),
        out_specs=P(None, batch_axes, None, axis),
    )(x, p["kernel"].astype(dtype), *bias)


class MLP(nn.Module):
    cfg: GPT2Config

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h = nn.Dense(4 * cfg.d_model, use_bias=cfg.use_bias, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="mlp_up")(x)
        h = nn.gelu(h)
        return nn.Dense(cfg.d_model, use_bias=cfg.use_bias, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="mlp_down")(h)


class Block(nn.Module):
    cfg: GPT2Config

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        x = x + Attention(cfg, name="attn")(
            nn.LayerNorm(dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="ln_1")(x)
        )
        x = x + MLP(cfg, name="mlp")(
            nn.LayerNorm(dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="ln_2")(x)
        )
        return x


class GPT2(nn.Module):
    cfg: GPT2Config

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        B, T = tokens.shape
        pos = jnp.arange(T)[None, :]
        wte = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                       param_dtype=cfg.param_dtype, name="wte")
        x = wte(tokens)
        x = x + nn.Embed(cfg.max_seq_len, cfg.d_model, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="wpe")(pos)
        block_cls = Block
        if cfg.remat:
            block_cls = nn.remat(Block, prevent_cse=False)
        for i in range(cfg.n_layer):
            x = block_cls(cfg, name=f"h_{i}")(x)
        x = nn.LayerNorm(dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="ln_f")(x)
        logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                          param_dtype=cfg.param_dtype, name="lm_head")(x)
        return logits


def init_params(cfg: GPT2Config, rng=None, batch: int = 2):
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    tokens = jnp.zeros((batch, min(cfg.max_seq_len, 128)), dtype=jnp.int32)
    return GPT2(cfg).init(rng, tokens)["params"]


def loss_fn(params, tokens, targets, cfg: GPT2Config):
    """Next-token cross entropy; targets = tokens shifted by caller
    (logsumexp form — see models/common.py next_token_loss)."""
    from ray_tpu.models.common import next_token_loss

    return next_token_loss(GPT2(cfg).apply({"params": params}, tokens), targets)


def make_train_step(cfg: GPT2Config, optimizer):
    """Returns train_step(params, opt_state, tokens, targets) ->
    (params, opt_state, loss).  Pure; callers jit it with shardings."""
    from ray_tpu.models import common

    return common.make_train_step(loss_fn, cfg, optimizer)


def make_adamw(lr: float = 3e-4, weight_decay: float = 0.1):
    import optax

    return optax.adamw(lr, b1=0.9, b2=0.95, weight_decay=weight_decay)


# ----------------------------------------------------------------------
# Inference plane: prefill / single-token decode with external KV cache.
#
# The serving engine (ray_tpu/serve/llm) owns WHERE keys/values live (a
# paged block pool); these functions own the math.  They are pure-jnp
# forwards over the same param tree the Flax module trains (names line
# up 1:1 — wte/wpe/h_i/{ln_1,attn{qkv,attn_out},ln_2,mlp{...}}/ln_f/
# lm_head), so served weights are exactly the trained ones: the forwards
# take that tree as it is, or as ``serving_params`` lays it out for a
# server.  Callers jit them (the engine jits gather -> decode -> scatter
# as one step).
# ----------------------------------------------------------------------

_LN_EPS = 1e-6  # flax.linen.LayerNorm default, matches the training path


def _ln(x, p, dtype):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    out = (xf - mu) / jnp.sqrt(var + _LN_EPS)
    return (out * p["scale"] + p["bias"]).astype(dtype)


def _dense(x, p, dtype):
    out = x @ p["kernel"].astype(dtype)
    if "bias" in p:
        out = out + p["bias"].astype(dtype)
    return out


def cache_spec(cfg: GPT2Config, block_size: int):
    """What the family caches for a sequence it serves
    (``models/common.py:CacheSpec``): K and V of all heads, a position,
    in every layer; nothing else."""
    from ray_tpu.models.common import CacheSpec

    return CacheSpec(paged_layers=cfg.n_layer, row_width=cfg.d_model)


def serving_params(params, cfg: GPT2Config):
    """The tree a server holds: every leaf in the dtype ``prefill_forward``
    and ``decode_forward_paged`` compute with.  The kernels and biases of
    ``_dense`` and the two embeddings go to ``cfg.dtype`` once, here, and
    not in every program that reads them; LayerNorm's scale and bias stay
    as they are (``_ln`` multiplies in float32).  The forwards give the
    same result to the bit on either tree: a trainer's float32 tree is
    rounded at each use the same way."""

    def held(path, leaf):
        layer_norm = path[-2].key.startswith("ln_")
        return leaf if layer_norm else leaf.astype(cfg.dtype)

    return jax.tree_util.tree_map_with_path(held, params)


def _split_heads(t, n_head):
    *lead, d = t.shape
    return t.reshape(*lead, n_head, d // n_head)


def prefill_forward(params, cfg: GPT2Config, tokens, last_index=None):
    """Full-prompt forward from position 0.

    tokens [B, T] -> (logits_last [B, vocab], k [L, B, T, H, Dh],
    v [L, B, T, H, Dh]).  Causal attention within the prompt; the
    returned per-layer K/V are what the decode path attends back to.
    ``last_index`` [B] selects which position's logits to return (for
    right-padded prompts — pad K/V are discarded by the caller's
    scatter); default is the final position.
    """
    dtype = cfg.dtype
    B, T = tokens.shape
    pos = jnp.arange(T)[None, :]
    x = params["wte"]["embedding"].astype(dtype)[tokens]
    x = x + params["wpe"]["embedding"].astype(dtype)[pos]
    ks, vs = [], []
    for i in range(cfg.n_layer):
        blk = params[f"h_{i}"]
        h = _ln(x, blk["ln_1"], dtype)
        qkv = _dense(h, blk["attn"]["qkv"], dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q, k, v = (_split_heads(t, cfg.n_head) for t in (q, k, v))
        from ray_tpu.ops.attention import reference_causal_attention

        att = reference_causal_attention(q, k, v)
        att = att.reshape(B, T, cfg.d_model)
        x = x + _dense(att, blk["attn"]["attn_out"], dtype)
        h2 = _ln(x, blk["ln_2"], dtype)
        m = nn.gelu(_dense(h2, blk["mlp"]["mlp_up"], dtype))
        x = x + _dense(m, blk["mlp"]["mlp_down"], dtype)
        ks.append(k)
        vs.append(v)
    x = _ln(x, params["ln_f"], dtype)
    if last_index is None:
        x_last = x[:, -1, :]
    else:
        x_last = x[jnp.arange(B), last_index, :]
    logits_last = _dense(x_last, params["lm_head"], dtype)
    return logits_last, jnp.stack(ks), jnp.stack(vs)


def decode_forward_paged(params, cfg: GPT2Config, tok, k_pages, v_pages,
                         block_tables, lengths, block_size: int):
    """One decode step over a paged KV pool read in place: tok [B] the
    current token ids; k_pages/v_pages [L, num_blocks * block_size,
    H * Dh]; block_tables [B, pages] the physical block of each logical
    page of a lane (scratch block 0 where it holds none); lengths [B]
    the cached positions of a lane, which is also the position of its
    fed token.  Returns (logits [B, vocab], k_new [L, B, H, Dh], v_new
    [L, B, H, Dh]): the fed token's own key and value are among what it
    attends to, and the caller writes them into the pool at position
    ``lengths``."""
    from ray_tpu.ops.attention import paged_decode_attention

    dtype = cfg.dtype
    x = params["wte"]["embedding"].astype(dtype)[tok]
    x = x + params["wpe"]["embedding"].astype(dtype)[lengths]
    k_news, v_news = [], []
    for i in range(cfg.n_layer):
        blk = params[f"h_{i}"]
        h = _ln(x, blk["ln_1"], dtype)
        qkv = _dense(h, blk["attn"]["qkv"], dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q, k, v = (_split_heads(t, cfg.n_head) for t in (q, k, v))  # [B, H, Dh]
        att = paged_decode_attention(
            q, k, v, k_pages, v_pages, i, block_tables, lengths, block_size=block_size
        ).reshape(tok.shape[0], cfg.d_model)
        x = x + _dense(att, blk["attn"]["attn_out"], dtype)
        h2 = _ln(x, blk["ln_2"], dtype)
        m = nn.gelu(_dense(h2, blk["mlp"]["mlp_up"], dtype))
        x = x + _dense(m, blk["mlp"]["mlp_down"], dtype)
        k_news.append(k)
        v_news.append(v)
    x = _ln(x, params["ln_f"], dtype)
    logits = _dense(x, params["lm_head"], dtype)
    return logits, jnp.stack(k_news), jnp.stack(v_news)


def generate_greedy(params, cfg: GPT2Config, tokens, n_new: int):
    """Reference full-forward greedy generation (no KV cache): re-runs
    the Flax model over the growing sequence.  O(T^2) per token — test
    oracle and tiny-scale baseline only."""
    model = GPT2(cfg)
    out = tokens
    for _ in range(n_new):
        logits = model.apply({"params": params}, out)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(out.dtype)
        out = jnp.concatenate([out, nxt[:, None]], axis=1)
    return out[:, tokens.shape[1]:]


def num_params(params) -> int:
    from ray_tpu.models.common import num_params as _n

    return _n(params)


def flops_per_token(cfg: GPT2Config, seq_len: int) -> float:
    """Approximate training FLOPs/token: 6*N + attention term."""
    n = (
        cfg.n_layer * (12 * cfg.d_model**2)
        + cfg.vocab_size * cfg.d_model * 2
        + cfg.max_seq_len * cfg.d_model
    )
    attn = cfg.n_layer * 12 * seq_len * cfg.d_model  # fwd+bwd attention matmuls
    return 6.0 * n + attn

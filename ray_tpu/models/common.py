"""Shared scaffolding for the model families.

One copy of the loss and the pure train step that gpt2.py builds on, of
the sampler the serving engine applies to whatever family's logits
(``sample_logits``), of what a served family states of its cache
(``CacheSpec``), and of the layer helpers more than one served family
uses (``rmsnorm``, ``rope``, ``yarn_inv_freq``, ``pool_rows``).  The
layers families share, which take a layer's parameters and the cache,
are ``models/layers.py``'s: a family imports from here and from there,
never from another family.  Laying a state out
on a mesh and jitting the step over it is ``ray_tpu.train.sharding``'s
(``GspmdPlan.shard_init`` / ``jit_train_step``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class CacheSpec:
    """What a family caches for a sequence it serves: its statement to
    ``serve/llm/engine.py``, which allocates, donates, writes and counts
    by it (docs/serving.md "Model families").

    Pages a sequence reserves by its length: ``k_pages`` and, unless
    the family states it has no V pool (``v_pool`` False: a family whose
    one cached row a position serves as key and value both), ``v_pages``
    of ``[paged_layers, slots, row_width]``, and for each of
    ``page_extras`` (name, rows a page, width, dtype) a pool of
    ``[paged_layers, pages * rows, width]`` addressed through the same
    block table.  State a lane owns whatever its sequence's length: for
    each of ``lane_state`` (name, shape, dtype) one array of ``[lanes,
    *shape]``, which reads as zeros to the prefill program that starts a
    sequence (position 0), by whatever path the sequence took the lane.

    ``prefill_chunk`` is the most tokens one prefill program takes: a
    longer prompt goes in as chunk programs in order, each reading what
    the earlier ones wrote.  0: a prompt is one program, which reads no
    cache."""

    paged_layers: int
    row_width: int
    page_extras: tuple = ()
    lane_state: tuple = ()
    prefill_chunk: int = 0
    v_pool: bool = True

    @property
    def reads_cache(self) -> bool:
        """Whether the family's forwards read the cache themselves and
        return what to write into it (``prefill_chunk``,
        ``decode_forward_cached``), or the cache is K and V a layer,
        which the plain forwards are handed (``prefill_forward``,
        ``decode_forward_paged``)."""
        return bool(self.page_extras or self.lane_state or self.prefill_chunk)

    @property
    def names(self) -> tuple:
        """The cache's arrays in the order the engine's programs take
        and return them."""
        return ("k_pages", *(("v_pages",) if self.v_pool else ()),
                *(e[0] for e in self.page_extras), *(s[0] for s in self.lane_state))


def next_token_loss(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Cross entropy in logsumexp form: never materializes the full
    [B, T, V] f32 log-prob tensor (the cast fuses into the reduction) —
    ~10% faster end-to-end at GPT-2-small on v5e than log_softmax +
    gather, identical value."""
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return (lse - tgt.astype(jnp.float32)).mean()


def make_train_step(loss_fn: Callable, cfg, optimizer):
    """train_step(params, opt_state, tokens, targets) for a
    loss_fn(params, tokens, targets, cfg)."""

    def train_step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets, cfg)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree.map(lambda p, u: p + u, params, updates)
        return params, opt_state, loss

    return train_step


def num_params(params) -> int:
    return int(sum(np.prod(p.shape) for p in jax.tree_util.tree_leaves(params)))


def sample_logits(logits, rng, temperature, top_k: int = 0):
    """Per-sequence sampling: temperature <= 0 means greedy (argmax);
    otherwise softmax sampling at that temperature, optionally truncated
    to the top_k highest-probability tokens (static; 0 = off).

    logits [B, V], temperature [B] -> token ids [B] (int32).
    """
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / temp
    if top_k and top_k > 0 and top_k < logits.shape[-1]:
        kth = jnp.sort(scaled, axis=-1)[:, -top_k][:, None]
        scaled = jnp.where(scaled < kth, -1e30, scaled)
    sampled = jax.random.categorical(rng, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temperature > 0.0, sampled, greedy)


def rmsnorm(x, w, eps):
    """x over the root of its mean square along the last axis (float32),
    times w, in x's dtype."""
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps)
    return (out * w.astype(jnp.float32)).astype(x.dtype)


def yarn_inv_freq(theta, dim, factor, original_context, beta_fast, beta_slow) -> list:
    """The rotary frequency of each of a head's ``dim / 2`` pairs under
    YaRN, as Python floats: below the ramp (fast pairs) the plain
    ``theta^(-2i/dim)``, above it that over ``factor``, between them a
    linear blend, the ramp's ends where a pair turns ``beta_fast`` and
    ``beta_slow`` times in ``original_context`` positions (the published
    ``yarn_find_correction_range`` / ``linear_ramp``)."""

    def correction_dim(rotations):
        return dim * math.log(original_context / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i in range(dim // 2):
        freq = theta ** (-2.0 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)  # 0: kept; 1: interpolated
        out.append(freq / factor * ramp + freq * (1.0 - ramp))
    return out


def rope(x, pos, theta, inv_freq=None, factor=None):
    """Rotary embedding, half-split (rotate_half) convention.
    x [..., H, Dh]; pos [...] int, a token's index in its sequence.
    `inv_freq` (Dh / 2 Python floats, ``yarn_inv_freq``'s) replaces the
    plain ``theta^(-2i/Dh)``; `factor` multiplies cos and sin (YaRN's
    ``attention_factor``: a score then carries its square)."""
    half = x.shape[-1] // 2
    if inv_freq is None:
        inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    else:
        inv = jnp.asarray(inv_freq, jnp.float32)
    ang = pos.astype(jnp.float32)[..., None, None] * inv  # [..., 1, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if factor is not None:
        cos, sin = cos * factor, sin * factor
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def pool_rows(pool, layer, where):
    """Rows ``where`` [...] of layer ``layer`` of a pool [L, P, D], taken
    from the pool addressed as [L * P, D]: ``pool[layer][where]`` makes
    XLA copy the whole layer out first (277 MB of K a sparse layer a
    decode step: 3.5 ms of a 23.6 ms step on the chip, PR 30)."""
    L, P, D = pool.shape
    return pool.reshape(L * P, D)[layer * P + where]

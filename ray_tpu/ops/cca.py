"""Compressed convolutional attention's mixing (Zyphra, "Compressed
Convolutional Attention", arXiv:2510.04476), for one layer: what stands
between the latent projections and the attention itself, in the two
forms a server needs.

A position's latents lie side by side, ``s_t = [q~_t | k~_t]``: ``H + G``
heads of ``hd`` columns (``S`` in all).  Two causal convolutions of
kernel 2 run along the sequence, neither with an activation:

    c0_t    = b0 + w0[:, 0] * s_{t-1} + w0[:, 1] * s_t                 depthwise
    c1_t[h] = b1[h] + c0_{t-1}[h] W1[h, 0] + c0_t[h] W1[h, 1]          grouped by head

with ``s_{-1} = c0_{-1} = 0``; then the q-k mean from the latents BEFORE
the convolutions, the L2 norm of every head to ``sqrt(hd)`` and a key
head's temperature (``cca_heads``).  The values are shifted, not mixed:
K/V head 0 holds ``y_t W_v1`` and head 1 ``y_{t-1} W_v2``.

So a sequence owns a TAIL whatever its length: ``s_{t-1}``, ``c0_{t-1}``
and ``y_{t-1} W_v2`` of the last position it saw, ``2 * S + hd`` values
side by side (flat, for the reason ``ops/mamba2.py:conv_tail`` gives).
``cca_mix_chunk`` takes a run of positions from a tail and returns the
tail after the last real one (prefill, a chunk at a time);
``cca_mix_step`` takes one position a lane (decode).  ``c0`` is rounded
to the latents' dtype before the second convolution reads it, inside a
chunk as across its boundary, so that where a prompt is cut moves
nothing.  Plain ``jax.numpy``; XLA fuses it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _conv0(prev, cur, w0, b0):
    """b0 + w0[:, 0] * prev + w0[:, 1] * cur, float32, in cur's dtype."""
    wf = w0.astype(jnp.float32)
    acc = b0.astype(jnp.float32) + wf[:, 0] * prev.astype(jnp.float32) + wf[:, 1] * cur.astype(jnp.float32)
    return acc.astype(cur.dtype)


def _conv1(prev, cur, w1, b1):
    """b1[h] + prev[h] W1[h, 0] + cur[h] W1[h, 1] for rows [N, S] of
    heads of hd; w1 [heads, 2, hd, hd]; float32 sums, in cur's dtype."""
    heads, _, hd, _ = w1.shape
    N = cur.shape[0]
    acc = jnp.einsum("nhd,hde->nhe", prev.reshape(N, heads, hd), w1[:, 0], preferred_element_type=jnp.float32)
    acc = acc + jnp.einsum("nhd,hde->nhe", cur.reshape(N, heads, hd), w1[:, 1], preferred_element_type=jnp.float32)
    return (acc.reshape(N, heads * hd) + b1.astype(jnp.float32)).astype(cur.dtype)


def _shifted(first, rows):
    """rows [T, C] one position later: ``first`` [C], then all but the last."""
    return jnp.concatenate([first[None].astype(rows.dtype), rows[:-1]])


def cca_mix_chunk(s, v2, tail, w, n_valid):
    """s [T, S] the latents and v2 [T, hd] ``y W_v2`` at consecutive
    positions, of which the first ``n_valid`` (a traced scalar, at
    least 1) are real; tail [2 * S + hd] as it stood before the first
    (zeros before position 0); w: a layer's parameters, of which
    ``conv0_w`` [S, 2], ``conv0_b`` [S], ``conv1_w`` [heads, 2, hd, hd]
    and ``conv1_b`` [S] are read.  -> (c1 [T, S], the previous
    position's v2 [T, hd], the tail after position ``n_valid - 1``).
    Rows past ``n_valid`` are pads: what they give means nothing and
    they leave the tail alone."""
    S = s.shape[1]
    tail = tail.astype(s.dtype)
    c0 = _conv0(_shifted(tail[:S], s), s, w["conv0_w"], w["conv0_b"])
    c1 = _conv1(_shifted(tail[S:2 * S], c0), c0, w["conv1_w"], w["conv1_b"])
    after = jnp.concatenate([jax.lax.dynamic_index_in_dim(a, n_valid - 1, keepdims=False) for a in (s, c0, v2)])
    return c1, _shifted(tail[2 * S:], v2), after


def cca_mix_step(s, v2, tail, w, active):
    """One position a lane: s [B, S], v2 [B, hd], tail [B, 2 * S + hd]
    each lane's own, active [B] bool.  -> (c1 [B, S], the previous
    position's v2 [B, hd], the tails [B, 2 * S + hd]: a running lane's
    after this position, another's as it was)."""
    S = s.shape[1]
    c0 = _conv0(tail[:, :S], s, w["conv0_w"], w["conv0_b"])
    c1 = _conv1(tail[:, S:2 * S], c0, w["conv1_w"], w["conv1_b"])
    after = jnp.concatenate([s, c0, v2], axis=-1).astype(tail.dtype)
    return c1, tail[:, 2 * S:].astype(v2.dtype), jnp.where(active[:, None], after, tail)


def cca_heads(s, c1, tau, n_head: int, n_kv_head: int):
    """The q-k mean, the norm and the temperature.  s, c1 [N, S] the
    latents before and after the convolutions, ``n_head`` query heads
    then ``n_kv_head`` key heads of hd; tau [n_kv_head].  Query head h
    reads key head ``h // R``, ``R = n_head / n_kv_head``:

        q[h] = c1_q[h] + (q~[h] + k~[h // R]) / 2
        k[g] = c1_k[g] + (k~[g] + mean of q~[h] over the heads of g) / 2

    then every head over its L2 norm times ``sqrt(hd)``, a key head
    times ``tau`` besides.  float32 inside.  -> (q [N, G, R, hd], k [N,
    G, hd]) in s's dtype, not yet rotated."""
    N = s.shape[0]
    G, R = n_kv_head, n_head // n_kv_head
    hd = s.shape[1] // (n_head + n_kv_head)
    sf, cf = s.astype(jnp.float32), c1.astype(jnp.float32)
    q_lat, k_lat = sf[:, :n_head * hd].reshape(N, G, R, hd), sf[:, n_head * hd:].reshape(N, G, hd)
    q = cf[:, :n_head * hd].reshape(N, G, R, hd) + 0.5 * (q_lat + k_lat[:, :, None])
    k = cf[:, n_head * hd:].reshape(N, G, hd) + 0.5 * (k_lat + q_lat.mean(2))

    def unit(x):  # times sqrt(hd) over its L2 norm: over the root of its mean square
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + 1e-12)

    return unit(q).astype(s.dtype), (unit(k) * tau.astype(jnp.float32)[None, :, None]).astype(s.dtype)

"""The lightning (linear-attention) recurrence of one head,

    S_t = lambda S_{t-1} + k_t^T v_t        (d x d, float32)
    o_t = q_t S_t / sqrt(d)

with a fixed decay ``lambda = exp(-slope)`` a head, in the two forms a
server needs: ``lightning_chunk`` takes a run of positions and the state
before it (prefill: a chunked scan, blocks of ``_BLOCK`` positions, the
products inside a block as masked matmuls and the state carried from
block to block), ``lightning_step`` one position a lane (decode: a
rank-one update of ``[B, H, d, d]``, elementwise in float32).

Every power of lambda is taken as ``exp(-slope * n)`` with ``n >= 0``:
the factored form ``lambda^i * lambda^-j`` overflows for the fast heads
(lambda 0.43) within a block.  Plain ``jax.numpy``; XLA fuses it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# positions of one block of the chunked scan: the intra-block products
# are [_BLOCK, _BLOCK] a head, the state is carried across blocks
_BLOCK = 256
_HI = jax.lax.Precision.HIGHEST  # the state's matmuls stay float32 on the TPU


def lightning_slopes(n_head: int):
    """``slope_h = 2^(-8 h / n_head)``, h = 1..n_head: the fixed per-head
    decay of Lightning Attention-2, ``lambda_h = exp(-slope_h)``."""
    return 2.0 ** (-8.0 * jnp.arange(1, n_head + 1, dtype=jnp.float32) / n_head)


def lightning_chunk(q, k, v, state, n_valid, slopes):
    """q, k, v [T, H, d] at consecutive positions, of which the first
    ``n_valid`` (a traced scalar) are real; state [H, d, d] float32 as
    it stood before the first.  -> (o [T, H, d] in q's dtype, the state
    after position ``n_valid - 1``).  Rows past ``n_valid`` are pads:
    their outputs mean nothing and they leave the state alone."""
    T, H, d = q.shape
    cb = min(T, _BLOCK)
    nb = T // cb
    i = jnp.arange(cb, dtype=jnp.float32)
    s = slopes.astype(jnp.float32)
    # lambda^(i-j) for j <= i, else 0; lambda^(i+1) for the carried state
    gap = i[:, None] - i[None, :]
    intra = jnp.where(gap >= 0, jnp.exp(-s[:, None, None] * jnp.maximum(gap, 0.0)), 0.0)  # [H, cb, cb]
    carried = jnp.exp(-s[:, None] * (i + 1.0))  # [H, cb]
    scale = 1.0 / (d ** 0.5)

    def block(S, xs):
        qb, kb, vb, off = xs  # [cb, H, d]
        m = jnp.clip(n_valid - off, 0, cb).astype(jnp.float32)  # real positions of this block
        a = jnp.einsum("ihd,jhd->hij", qb, kb, preferred_element_type=jnp.float32) * intra
        o = jnp.einsum("hij,jhd->ihd", a.astype(vb.dtype), vb, preferred_element_type=jnp.float32)
        qf = qb.astype(jnp.float32) * carried.T[:, :, None]
        o = o + jnp.einsum("ihd,hde->ihe", qf, S, precision=_HI)
        # the state after the block's last real position
        w = jnp.where(i[None, :] < m, jnp.exp(-s[:, None] * jnp.maximum(m - 1.0 - i[None, :], 0.0)), 0.0)
        kw = kb.astype(jnp.float32) * w.T[:, :, None]
        S = jnp.exp(-s * m)[:, None, None] * S + jnp.einsum(
            "jhd,jhe->hde", kw, vb.astype(jnp.float32), precision=_HI)
        return S, (o * scale).astype(qb.dtype)

    def blocks(x):
        return x.reshape(nb, cb, H, d)

    offs = jnp.arange(nb, dtype=jnp.int32) * cb
    state, o = jax.lax.scan(block, state, (blocks(q), blocks(k), blocks(v), offs))
    return o.reshape(T, H, d), state


def lightning_step(q, k, v, state, slopes):
    """One position a lane: q, k, v [B, H, d]; state [B, H, d, d]
    float32.  -> (o [B, H, d] in q's dtype, the new state)."""
    d = q.shape[-1]
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    lam = jnp.exp(-slopes.astype(jnp.float32))[None, :, None, None]
    state = lam * state + kf[..., :, None] * vf[..., None, :]
    o = (q.astype(jnp.float32)[..., :, None] * state).sum(-2) / (d ** 0.5)
    return o.astype(q.dtype), state

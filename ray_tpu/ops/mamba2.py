"""The Mamba-2 (state-space duality) mixer's two stateful parts, for one
layer (and its stateless way out, ``gated_group_norm``): a causal
depthwise convolution of width ``K`` that needs the last ``K - 1`` rows
it saw (the TAIL), and the selective scan of one head,

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T      ([P, N], float32)
    y_t = S_t C_t + D x_t

with a decay computed from the token (``dt_t`` after its softplus, ``A``
negative, both a head's own) and ``B_t``, ``C_t`` shared by the heads of
a group.  In the two forms a server needs: ``ssd_chunk`` takes a run of
positions and the state before it (prefill: the chunked form, blocks of
``chunk`` positions, inside a block the masked ``C B^T`` with cumulative
decays as matmuls, the state carried from block to block), ``ssm_step``
one position a lane (decode: a rank-one update of ``[B, H, P, N]``,
elementwise in float32; the definition of
``ops.pallas_mamba2.mamba2_decode_step``, which does the same in place).

Every decay is ``exp`` of a sum of ``dt A <= 0`` terms, never a
quotient of two: the factored form overflows within a block for a fast
head.  Plain ``jax.numpy``; XLA fuses it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST  # the state's matmuls stay float32 on the TPU


def conv_tail(x, tail, w, b=None, n_valid=None):
    """The convolution and the tail after it.  x [..., T, C] at
    consecutive positions, of which the first ``n_valid`` (a traced
    scalar; None: all) are real; tail [..., (K - 1) * C] the K - 1 rows
    before the first, side by side (zeros before position 0: kept flat,
    because an array whose last two dims are ``[3, C]`` is padded to 16
    rows on the chip); w [C, K], b [C] (None: a convolution without a
    bias).  -> (``silu(b + sum_j w[:, j]
    x_{t-K+1+j})`` [..., T, C] in x's dtype, the tail after position
    ``n_valid - 1``)."""
    T, C = x.shape[-2:]
    K = w.shape[1]
    wf = w.astype(jnp.float32)
    if T == 1 and n_valid is None:
        # one token a lane: the tail's rows are slices of whole lane tiles, and no row is moved
        new = x[..., 0, :]
        if b is None:
            acc = wf[:, K - 1] * new.astype(jnp.float32)
        else:
            acc = b.astype(jnp.float32) + wf[:, K - 1] * new.astype(jnp.float32)
        for j in range(K - 1):
            acc = acc + wf[:, j] * tail[..., j * C:(j + 1) * C].astype(jnp.float32)
        return jax.nn.silu(acc).astype(x.dtype)[..., None, :], jnp.concatenate([tail[..., C:], new], axis=-1)
    full = jnp.concatenate([tail.reshape(*tail.shape[:-1], K - 1, C), x], axis=-2)  # row i is position i - (K - 1)
    acc = 0.0 if b is None else b.astype(jnp.float32)
    for j in range(K):
        acc = acc + wf[:, j] * jax.lax.slice_in_dim(full, j, j + T, axis=-2).astype(jnp.float32)
    end = T if n_valid is None else n_valid
    after = jax.lax.dynamic_slice_in_dim(full, end, K - 1, axis=-2)
    return jax.nn.silu(acc).astype(x.dtype), after.reshape(tail.shape)


def gated_group_norm(o, z, w, groups, eps):
    """The mixer's way out before its last matmul: the scan's output o
    [N, H, P] times ``silu`` of the gate z [N, H * P] (float32), RMSNorm
    over each group's columns, times w [H * P] -> [N, H * P] in o's
    dtype."""
    N = o.shape[0]
    u = o.reshape(N, -1).astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    u = u.reshape(N, groups, -1)
    u = u * jax.lax.rsqrt((u * u).mean(-1, keepdims=True) + eps)
    return (u.reshape(N, -1) * w.astype(jnp.float32)).astype(o.dtype)


def ssd_chunk(x, dt, A, B, C, D, state, n_valid, chunk: int = 128):
    """x [T, H, P] at consecutive positions, of which the first
    ``n_valid`` (a traced scalar) are real; dt [T, H] float32, after its
    softplus; A, D [H]; B, C [T, G, N]; state [H, P, N] float32 as it
    stood before the first.  T is whole blocks of ``chunk`` or less than
    one.  -> (y [T, H, P] in x's dtype, the state after position
    ``n_valid - 1``).  Rows past ``n_valid`` are pads: their outputs
    mean nothing and they leave the state alone (their ``dt`` is taken
    as 0: no decay, no update)."""
    T, H, P = x.shape
    G = B.shape[1]
    cb = min(T, chunk)
    nb = T // cb
    assert nb * cb == T, f"{T} positions are not whole blocks of {cb}"
    real = jnp.arange(T) < n_valid
    dt = jnp.where(real[:, None], dt.astype(jnp.float32), 0.0)
    a = dt * A.astype(jnp.float32)  # [T, H], <= 0: a position's log-decay
    causal = jnp.tril(jnp.ones((cb, cb), bool))

    def block(S, xs):
        xb, dtb, ab, Bb, Cb = xs  # [cb, G, R, P], [cb, G, R], [cb, G, R], [cb, G, N], [cb, G, N]
        cum = jnp.cumsum(ab, axis=0)  # the log-decay from the block's start through position i
        # exp(cum_i - cum_j) for j <= i: what is left at i of what j added
        gap = cum[:, None] - cum[None, :]  # [i, j, G, R]
        left = jnp.where(causal[:, :, None, None], jnp.exp(jnp.where(causal[:, :, None, None], gap, 0.0)), 0.0)
        cbt = jnp.einsum("ign,jgn->ijg", Cb, Bb, preferred_element_type=jnp.float32)
        m = cbt[..., None] * left * dtb[None]  # [i, j, G, R]
        y = jnp.einsum("ijgr,jgrp->igrp", m.astype(xb.dtype), xb, preferred_element_type=jnp.float32)
        # what the carried state gives position i
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "ign,grpn->igrp", Cb.astype(jnp.float32), S, precision=_HI)
        # the state after the block: what is left of S, and of every position's update
        last = jnp.exp(cum[-1] - cum) * dtb  # [j, G, R]
        xw = xb.astype(jnp.float32) * last[..., None]
        S = jnp.exp(cum[-1])[..., None, None] * S + jnp.einsum(
            "jgrp,jgn->grpn", xw, Bb.astype(jnp.float32), precision=_HI)
        return S, y

    def blocks(v, *shape):
        return v.reshape(nb, cb, *shape)

    R, N = H // G, B.shape[2]  # a group's heads side by side
    state, y = jax.lax.scan(
        block, state.reshape(G, R, P, N),
        (blocks(x, G, R, P), blocks(dt, G, R), blocks(a, G, R), blocks(B, G, N), blocks(C, G, N)))
    y = y.reshape(T, H, P) + D.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    return y.astype(x.dtype), state.reshape(H, P, N)


def ssm_step(x, dt, A, B, C, D, state, active=None):
    """One position a lane: x [B, H, P]; dt [B, H] float32, after its
    softplus; A, D [H]; B, C [B, G, N]; state [B, H, P, N] float32;
    active [B] bool (None: every lane).  -> (y [B, H, P] in x's dtype,
    the new state; a lane that is not active keeps its state, and its y
    means nothing)."""
    Bn, H, P = x.shape
    G = B.shape[1]
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    Bh = jnp.repeat(B.astype(jnp.float32), H // G, axis=1)  # [B, H, N]
    Ch = jnp.repeat(C.astype(jnp.float32), H // G, axis=1)
    decay = jnp.exp(dtf * A.astype(jnp.float32))[..., None, None]
    new = decay * state + (dtf[..., None] * xf)[..., None] * Bh[:, :, None, :]
    y = (new * Ch[:, :, None, :]).sum(-1) + D.astype(jnp.float32)[:, None] * xf
    if active is not None:
        new = jnp.where(active[:, None, None, None], new, state)
    return y.astype(x.dtype), new


def ssm_decode_step(x, dt, A, B, C, D, state, active):
    """``ssm_step`` by the backend: on a TPU, where the shapes fit its
    tiling, the Pallas kernel that updates the running lanes' states in
    the buffer they lie in (ops.pallas_mamba2); elsewhere the plain
    form."""
    if jax.default_backend() == "tpu":  # as the paged attentions: the CPU tests take the plain path
        from ray_tpu.ops import pallas_mamba2 as kernel

        if kernel.kernel_takes(*state.shape[1:], B.shape[1]):
            return kernel.mamba2_decode_step(x, dt, A, B, C, D, state, active)
    return ssm_step(x, dt, A, B, C, D, state, active)

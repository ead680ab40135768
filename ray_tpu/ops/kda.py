"""The gated delta rule of one head (Kimi Delta Attention,
arXiv:2510.26692): a state ``S [d_k, d_v]`` float32 a head that every
position first decays a CHANNEL at a time and then corrects by a
rank-one step toward its own value,

    S'  = Diag(alpha_t) S_{t-1}                 alpha_t = exp(a_t), a_t <= 0, [d_k]
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T    = (I - beta_t k_t k_t^T) S' + beta_t k_t v_t^T
    o_t = S_t^T q_t * d_k^-0.5

The matrix that multiplies the state is NOT diagonal, so a run of
positions is no sum of decayed outer products (``ops/lightning.py``,
``ops/mamba2.py``).  In the two forms a server needs: ``kda_step`` takes
one position a lane (decode; the definition of
``ops.pallas_kda.kda_decode_step``, which does the same in place), and
``kda_chunk`` a run of positions and the state before it (prefill; the
definition of ``ops.pallas_kda_chunk.kda_chunk_scan``, which keeps a
block's working set in VMEM), in blocks of ``BLOCK`` positions.  Inside a
block, with ``g_i`` the sum of ``a`` from the block's first position
through i and ``S_0`` the state before it, the corrections ``u_j = beta_j (v_j - S'_j^T k_j)`` solve

    (I + Diag(beta) strict_lower(A)) U = Diag(beta) (V - (K exp(G)) S_0)
    A[j, i] = sum_c k_j[c] k_i[c] exp(g_j[c] - g_i[c])

a unit lower-triangular system (the WY / UT form), whose inverse ``T``
does not depend on ``S_0``: all blocks' ``T V`` and ``T (K exp(G))``
are made at once, and only ``U = T V - (T K exp(G)) S_0``, the outputs
``(Q exp(G)) S_0 + P U`` (``P`` as ``A`` with q's rows, the diagonal
kept) and the next state ``exp(g_last) S_0 + (K exp(g_last - G))^T U``
are carried from block to block.

POWERS OF alpha.  ``a`` reaches the softplus's range (a decay of
``exp(-50)`` a position is a float32 zero, one of ``exp(-1e-4)`` nearly
one), and a block's ``g`` then spans thousands: the factored form
``(K exp(G)) (K exp(-G))^T`` overflows.  Every power here is ``exp`` of a
difference that is NEVER positive: inside a sub-block of ``SUB``
positions ``exp(g_j - g_i)`` is taken directly for ``i <= j`` (a ``[SUB,
SUB, d_k]`` tensor, a sixteenth of the block's); between sub-blocks the
difference is split at the last position ``r`` before the row's
sub-block, ``exp(g_j - g_r) exp(g_r - g_i)`` with ``i <= r < j``, both
factors at most 1, and the products are matmuls again.  A factor that
underflows to 0 stands for a product that is smaller still.  Plain
``jax.numpy``, float32 at ``Precision.HIGHEST``; XLA fuses it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BLOCK = 64  # positions a block of the chunked form: the triangular system is [BLOCK, BLOCK] a head
SUB = 16  # positions a sub-block: exp(g_j - g_i) is taken directly inside it
_HI = jax.lax.Precision.HIGHEST  # the state's matmuls stay float32 on the TPU


def kda_step(q, k, v, a, beta, state, active=None):
    """One position a lane: q, k, a [B, H, dk] (a the log-decay, <= 0), v
    [B, H, dv], beta [B, H], state [B, H, dk, dv] float32; active [B]
    bool (None: every lane).  -> (o [B, H, dv] in v's dtype, the new
    state; a lane that is not active keeps its state, and its o means
    nothing)."""
    qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))
    decayed = jnp.exp(a.astype(jnp.float32))[..., None] * state
    u = beta.astype(jnp.float32)[..., None] * (vf - (kf[..., None] * decayed).sum(-2))
    new = decayed + kf[..., None] * u[..., None, :]
    o = (qf[..., None] * new).sum(-2) * (q.shape[-1] ** -0.5)
    if active is not None:
        new = jnp.where(active[:, None, None, None], new, state)
    return o.astype(v.dtype), new


def _decayed_products(rows, keys, g, diagonal: bool):
    """``M[j, i] = sum_c rows_j[c] keys_i[c] exp(g_j[c] - g_i[c])`` for
    ``i < j`` (``i <= j`` with ``diagonal``), 0 elsewhere, of one block:
    rows, keys, g [..., C, dk] float32, g the inclusive running sum of
    the block's log-decays -> [..., C, C].  No power of a positive
    number is taken (the module's docstring says how)."""
    C, dk = g.shape[-2:]
    sub = min(SUB, C)
    n = C // sub
    lead = g.shape[:-2]
    # inside a sub-block, directly
    rs, ks, gs = (t.reshape(*lead, n, sub, dk) for t in (rows, keys, g))
    j, i = jnp.arange(sub)[:, None], jnp.arange(sub)[None, :]
    inside = (i <= j) if diagonal else (i < j)
    gap = jnp.where(inside[..., None], gs[..., :, None, :] - gs[..., None, :, :], 0.0)  # [.., n, sub, sub, dk]
    near = jnp.where(inside, (rs[..., :, None, :] * ks[..., None, :, :] * jnp.exp(gap)).sum(-1), 0.0)
    # between sub-blocks, split at the last position before the row's sub-block
    ref = jnp.concatenate([jnp.zeros((*lead, 1, dk), g.dtype), gs[..., :-1, -1, :]], axis=-2)  # [.., n, dk]
    left = rs * jnp.exp(gs - ref[..., :, None, :])  # [.., n, sub, dk]: g_j <= g_ref
    before = jnp.arange(C)[None, :] < (jnp.arange(n) * sub)[:, None]  # [n, C]: column i lies before sub-block I
    right = jnp.where(before[..., None],
                      jnp.exp(jnp.where(before[..., None], ref[..., :, None, :] - g[..., None, :, :], 0.0)), 0.0)
    right = right * keys[..., None, :, :]  # [.., n, C, dk]: g_ref <= g_i
    far = jnp.einsum("...njc,...nic->...nji", left, right, precision=_HI)  # [.., n, sub, C]
    # a sub-block's own columns lie on the diagonal of [n, sub, n, sub]
    eye = jnp.eye(n, dtype=near.dtype)
    near = (near[..., :, :, None, :] * eye[:, None, :, None]).reshape(*lead, C, C)
    return far.reshape(*lead, C, C) + near


def _carried(state, tv, tk, p, q_in, k_out, g_last):
    """The part of ``kda_chunk`` that goes from block to block: the state
    [H, dk, dv] before the first block and, a block a row, ``T V``, ``T K
    exp(G)``, ``P``, ``Q exp(G)``, ``K exp(g_last - G)`` and ``g_last`` ->
    (the state after the last block, o [nb, H, cb, dv])."""

    def block(S, xs):
        tv, tk, p, q_in, k_out, g_last = xs
        u = tv - jnp.einsum("hjc,hcd->hjd", tk, S, precision=_HI)
        o = jnp.einsum("hic,hcd->hid", q_in, S, precision=_HI) + jnp.einsum("hij,hjd->hid", p, u, precision=_HI)
        S = jnp.exp(g_last)[..., None] * S + jnp.einsum("hjc,hjd->hcd", k_out, u, precision=_HI)
        return S, o

    return jax.lax.scan(block, state, (tv, tk, p, q_in, k_out, g_last))


def kda_chunk(q, k, v, a, beta, state, n_valid):
    """q, k, a [T, H, dk] at consecutive positions, of which the first
    ``n_valid`` (a traced scalar) are real; v [T, H, dv]; beta [T, H];
    state [H, dk, dv] float32 as it stood before the first.  T is whole
    blocks of ``BLOCK`` or less than one.  -> (o [T, H, dv] in v's
    dtype, the state after position ``n_valid - 1``).  Rows past
    ``n_valid`` are pads: their outputs mean nothing and they leave the
    state alone (their ``a`` and ``beta`` are taken as 0: no decay, no
    correction)."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    cb = min(T, BLOCK)
    nb = T // cb
    assert nb * cb == T, f"{T} positions are not whole blocks of {cb}"
    real = (jnp.arange(T) < n_valid)[:, None]
    a = jnp.where(real[..., None], a.astype(jnp.float32), 0.0)
    beta = jnp.where(real, beta.astype(jnp.float32), 0.0)

    def blocks(x):  # [T, H, ...] -> [nb, H, cb, ...]
        return jnp.moveaxis(x.astype(jnp.float32).reshape(nb, cb, *x.shape[1:]), 2, 1)

    qb, kb, vb, ab = blocks(q), blocks(k), blocks(v), blocks(a)
    bb = blocks(beta)[..., None]  # [nb, H, cb, 1]
    g = jnp.cumsum(ab, axis=-2)  # the log-decay from the block's start through position i
    # every block's triangular system at once: T = (I + Diag(beta) strict_lower(A))^-1 Diag(beta)
    # (forward substitution, XLA's: no power of the system is taken, so keys that repeat cost no digits)
    system = jnp.eye(cb, dtype=jnp.float32) + bb * _decayed_products(kb, kb, g, diagonal=False)
    solve = jax.vmap(jax.vmap(lambda m, r: jax.scipy.linalg.solve_triangular(m, r, lower=True, unit_diagonal=True)))
    eg = jnp.exp(g)
    tv_tk = solve(system, bb * jnp.concatenate([vb, kb * eg], axis=-1))  # [nb, H, cb, dv + dk]
    tv, tk = tv_tk[..., :dv], tv_tk[..., dv:]
    p = _decayed_products(qb, kb, g, diagonal=True)  # [nb, H, cb, cb]
    q_in = qb * eg  # what the carried state gives position i
    k_out = kb * jnp.exp(g[..., -1:, :] - g)  # what is left at the block's end of what position j added
    state, o = _carried(state, tv, tk, p, q_in, k_out, g[..., -1, :])
    o = jnp.moveaxis(o, 1, 2).reshape(T, H, dv) * (dk ** -0.5)
    return o.astype(v.dtype), state


def chunk_kernel_takes(T, H, dk, dv) -> bool:
    """Whether ``kda_chunk_scan`` gives a chunk of these shapes to the
    kernel: on a TPU, whole blocks of positions of heads whose columns are
    whole lane tiles (a bucket under ``BLOCK`` tokens keeps the plain
    form)."""
    if jax.default_backend() != "tpu":  # as the paged attentions: the CPU tests take the plain path
        return False
    from ray_tpu.ops import pallas_kda_chunk as kernel

    return kernel.kernel_takes(T, H, dk, dv)


def kda_chunk_scan(q, k, v, a, beta, state, n_valid):
    """``kda_chunk`` by the backend and the shapes: the Pallas kernel
    (ops.pallas_kda_chunk) where ``chunk_kernel_takes``, elsewhere the
    plain form."""
    if chunk_kernel_takes(*q.shape, v.shape[-1]):
        from ray_tpu.ops import pallas_kda_chunk as kernel

        return kernel.kda_chunk_scan(q, k, v, a, beta, state, n_valid)
    return kda_chunk(q, k, v, a, beta, state, n_valid)


def kda_decode_step(q, k, v, a, beta, state, active):
    """``kda_step`` by the backend: on a TPU, where the shapes fit its
    tiling, the Pallas kernel that updates the running lanes' states in
    the buffer they lie in (ops.pallas_kda); elsewhere the plain form."""
    if jax.default_backend() == "tpu":  # as the paged attentions: the CPU tests take the plain path
        from ray_tpu.ops import pallas_kda as kernel

        if kernel.kernel_takes(*state.shape[1:]):
            return kernel.kda_decode_step(q, k, v, a, beta, state, active)
    return kda_step(q, k, v, a, beta, state, active)

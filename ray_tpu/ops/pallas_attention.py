"""Flash attention (forward + backward) as Pallas TPU kernels.

Forward: grid (batch*heads, q_blocks, k_blocks), k sequential
("arbitrary") — K/V stream through VMEM one (block_k, D) tile per step,
m/l/o accumulate in VMEM scratch, scores never touch HBM.  The kernel
also emits per-row logsumexp L (shape [BH, nq, block_q]) for the
backward pass.

Backward: delta = rowsum(do ∘ o) is computed in XLA (cheap, elementwise),
then two kernels recompute p = exp(s − L) blockwise:
  dq kernel:  grid (BH, nq, nk), nk sequential — accumulates dq.
  dkv kernel: grid (BH, nk, nq), nq sequential — accumulates dk, dv.
Causal block-skipping applies in all three kernels (≈2× FLOP savings).

`flash_attention` wires these into jax.custom_vjp; interpret=True runs
the same kernels on CPU for tests.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")
    )


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                block_q, block_k, num_kb, scale, causal):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_scr[...][:, 0]
        l_prev = l_scr[...][:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        if causal:
            p = jnp.where(kpos <= qpos, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ()))
        )
        m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    if causal:
        pl.when(ki * block_k <= qi * block_q + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == num_kb - 1)
    def _finish():
        o_ref[...] = (acc_scr[...] / l_scr[...][:, :1]).astype(o_ref.dtype)
        lse_ref[...] = (m_scr[...][:, 0] + jnp.log(l_scr[...][:, 0]))[None, :]


def _flash_fwd_impl(qf, kf, vf, *, block_q, block_k, scale, causal, interpret):
    BH, T, D = qf.shape
    num_kb = T // block_k
    kernel = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, num_kb=num_kb,
        scale=scale, causal=causal,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(BH, T // block_q, num_kb),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), qf.dtype),
            jax.ShapeDtypeStruct((BH, 1, T), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(qf, kf, vf)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr, *,
               block_q, block_k, num_kb, scale, causal):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _compute():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        lse = lse_ref[...][0]  # [block_q]
        delta = delta_ref[...][0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dov = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))  # [bq, bk]
        ds = p * (dov - delta[:, None]) * scale
        dq_scr[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())))

    if causal:
        pl.when(ki * block_k <= qi * block_q + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == num_kb - 1)
    def _finish():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, block_q, block_k, num_qb, scale, causal):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _compute():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        lse = lse_ref[...][0]
        delta = delta_ref[...][0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # [bq, bk]
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dv_scr[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))  # [bk, D]
        dov = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dov - delta[:, None]) * scale
        dk_scr[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())))  # [bk, D]

    if causal:
        # The q block contributes unless it is entirely above the diagonal.
        pl.when(qi * block_q + block_q - 1 >= ki * block_k)(_compute)
    else:
        _compute()

    @pl.when(qi == num_qb - 1)
    def _finish():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_impl(qf, kf, vf, do, out, lse, *, block_q, block_k, scale, causal, interpret):
    BH, T, D = qf.shape
    nq, nk = T // block_q, T // block_k
    delta = (do.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)[:, None, :]  # [BH, 1, T]

    dq_kernel = functools.partial(
        _dq_kernel, block_q=block_q, block_k=block_k, num_kb=nk, scale=scale, causal=causal
    )
    dq = pl.pallas_call(
        dq_kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, 1, block_q), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((None, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), qf.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(qf, kf, vf, do, lse, delta)

    dkv_kernel = functools.partial(
        _dkv_kernel, block_q=block_q, block_k=block_k, num_qb=nq, scale=scale, causal=causal
    )
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, 1, block_q), lambda b, i, j: (b, 0, j)),
            pl.BlockSpec((None, 1, block_q), lambda b, i, j: (b, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), qf.dtype),
            jax.ShapeDtypeStruct((BH, T, D), qf.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(qf, kf, vf, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API with custom vjp
# ---------------------------------------------------------------------------
def _to_bh(t):
    B, T, H, D = t.shape
    return t.transpose(0, 2, 1, 3).reshape(B * H, T, D)


def _from_bh(t, B, H):
    BH, T, D = t.shape
    return t.reshape(B, H, T, D).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block_q, block_k, interpret):
    out, _ = _fwd(q, k, v, causal, block_q, block_k, interpret)
    return out


def _fwd(q, k, v, causal, block_q, block_k, interpret):
    B, T, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    out, lse = _flash_fwd_impl(
        _to_bh(q), _to_bh(k), _to_bh(v),
        block_q=block_q, block_k=block_k, scale=scale, causal=causal, interpret=interpret,
    )
    return _from_bh(out, B, H), (q, k, v, _from_bh(out, B, H), lse)


def _bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    B, T, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    dq, dk, dv = _flash_bwd_impl(
        _to_bh(q), _to_bh(k), _to_bh(v), _to_bh(g), _to_bh(out), lse,
        block_q=block_q, block_k=block_k, scale=scale, causal=causal, interpret=interpret,
    )
    return _from_bh(dq, B, H), _from_bh(dk, B, H), _from_bh(dv, B, H)


_flash.defvjp(_fwd, _bwd)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 1024, block_k: int = 1024,
                    interpret: bool = False):
    """[B, T, H, D] flash attention (differentiable, Pallas fwd+bwd)."""
    B, T, H, D = q.shape
    # Shrink blocks to the largest power-of-two divisor of T at or under
    # the requested size, so any T that is a multiple of 128 works with
    # the (large, faster) defaults.
    def fit(block: int) -> int:
        b = min(block, T)
        while b > 128 and T % b:
            b //= 2
        return b

    block_q, block_k = fit(block_q), fit(block_k)
    if T % block_q or T % block_k:
        raise ValueError(f"seq len {T} must divide block sizes ({block_q}, {block_k})")
    return _flash(q, k, v, causal, block_q, block_k, interpret)

"""The Mamba-1 (selective state space) mixer's scan, for one layer:

    h_t = exp(dt_t[None, :] * A) * h_{t-1} + B_t[:, None] * (dt_t * x_t)[None, :]   ([N, D], float32)
    y_t = C_t h_t + D * x_t

with a decay for EVERY value of the state (``A`` is a matrix: a row a
state index n, a column a channel), ``dt_t`` a step a channel computed
from the token (after its softplus), and ``B_t``, ``C_t`` ``[N]`` shared
by all the channels.  There are no heads and no groups, and no form of
it is a matmul: where Mamba-2's one decay a head lets a block of
positions be a masked ``C B^T`` product (``ops/mamba2.py:ssd_chunk``),
here the same form is a ``[positions, positions, D, N]`` tensor.  The
convolution before it and its tail are ``ops.mamba2.conv_tail``, which
takes any ``[C, K]``.

The state lies TRANSPOSED to how the equations are usually printed:
``[N, D]``, the channels along the minor axis.  N is 16: along the 128
lanes of a vector register it would fill an eighth of each; along the
sublanes it is two whole float32 tiles, the channels fill the lanes,
``dt``, ``x`` and ``D`` are rows along them, ``B_t`` and ``C_t`` columns
broadcast along them, and the contraction with ``C_t`` is a sum of 16
rows.  ``A`` arrives laid the same way (``[N, D]``: the published
``-exp(A_log)``, transposed).

Two forms a server needs, each the DEFINITION of a kernel of
``ops/pallas_mamba1.py`` and the path the CPU takes:
``selective_scan_chunk`` a run of positions from a lane's state
(prefill; a position at a time, ``lax.scan``), ``ssm1_step`` one
position a lane (decode).  Plain ``jax.numpy``, float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def selective_scan_chunk(x, dt, A, B, C, D, state, n_valid):
    """x [T, D] at consecutive positions, of which the first ``n_valid``
    (a traced scalar) are real; dt [T, D] float32, after its softplus; A
    [N, D]; B, C [T, N]; D [D]; state [N, D] float32 as it stood before
    the first.  -> (y [T, D] in x's dtype, the state after position
    ``n_valid - 1``).  Rows past ``n_valid`` are pads: their outputs mean
    nothing and they leave the state alone (their ``dt`` is taken as 0:
    no decay, no update)."""
    T = x.shape[0]
    xf, Af = x.astype(jnp.float32), A.astype(jnp.float32)
    dtf = jnp.where((jnp.arange(T) < n_valid)[:, None], dt.astype(jnp.float32), 0.0)

    def step(h, at):
        x_t, dt_t, B_t, C_t = at
        h = jnp.exp(dt_t[None, :] * Af) * h + B_t[:, None] * (dt_t * x_t)[None, :]
        return h, (h * C_t[:, None]).sum(0)

    state, y = jax.lax.scan(step, state, (xf, dtf, B.astype(jnp.float32), C.astype(jnp.float32)))
    return (y + D.astype(jnp.float32) * xf).astype(x.dtype), state


def ssm1_step(x, dt, A, B, C, D, state, active=None):
    """One position a lane: x [L, D]; dt [L, D] float32, after its
    softplus; A [N, D]; B, C [L, N]; D [D]; state [L, N, D] float32;
    active [L] bool (None: every lane).  -> (y [L, D] in x's dtype, the
    new state; a lane that is not active keeps its state, and its y
    means nothing)."""
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    decay = jnp.exp(dtf[:, None, :] * A.astype(jnp.float32))
    new = decay * state + B.astype(jnp.float32)[:, :, None] * (dtf * xf)[:, None, :]
    y = (new * C.astype(jnp.float32)[:, :, None]).sum(1) + D.astype(jnp.float32) * xf
    if active is not None:
        new = jnp.where(active[:, None, None], new, state)
    return y.astype(x.dtype), new


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"  # as the paged attentions: the CPU tests take the plain path


def scan_chunk(x, dt, A, B, C, D, state, n_valid):
    """``selective_scan_chunk`` by the backend: on a TPU, where the
    shapes fit its tiling, the Pallas kernel that keeps the state in
    VMEM over the chunk's positions (ops.pallas_mamba1); elsewhere the
    plain form."""
    if _on_tpu():
        from ray_tpu.ops import pallas_mamba1 as kernel

        if kernel.chunk_kernel_takes(x.shape[0], *state.shape):
            return kernel.mamba1_chunk_scan(x, dt, A, B, C, D, state, n_valid)
    return selective_scan_chunk(x, dt, A, B, C, D, state, n_valid)


def decode_step(x, dt, A, B, C, D, state, active):
    """``ssm1_step`` by the backend: on a TPU, where the shapes fit its
    tiling, the Pallas kernel that updates the running lanes' states in
    the buffer they lie in (ops.pallas_mamba1); elsewhere the plain
    form."""
    if _on_tpu():
        from ray_tpu.ops import pallas_mamba1 as kernel

        if kernel.step_kernel_takes(*state.shape):
            return kernel.mamba1_decode_step(x, dt, A, B, C, D, state, active)
    return ssm1_step(x, dt, A, B, C, D, state, active)

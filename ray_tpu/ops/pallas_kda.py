"""One decode step of a gated-delta-rule (KDA) layer over the lanes'
states, updated IN PLACE (Pallas TPU): ``ops.kda.kda_step`` is its
definition and the path the CPU takes.

The states are the layer's lane-state array ``[lanes, H, dk, dv]``
float32 (2 MB a lane at 32 heads of 128 x 128): a decode step reads and
writes every running lane's, and that is all it has to move, a dozen
operations a value.  As ``ops/pallas_mamba2.py``: one program a layer
whose grid walks the RUNNING lanes (their numbers arrive as scalar
prefetch, the idle lanes behind them repeat the last one's block index,
which copies nothing and runs nothing); a step has the lane's state in
VMEM and writes the new one back INTO THE BUFFER IT CAME FROM
(``input_output_aliases``), the next lane's on its way in and the last
one's on its way out behind this one's arithmetic.  An idle lane's state
is left as it is.

A head's state is ``[dk, dv]`` with dv the 128 lanes of a vector
register, and a head takes three passes over its sixteen registers, all
on the vector units in float32:

    S' = alpha S                  alpha varies along dk: a column, broadcast along the lanes
    r  = k^T S'                   a sum over dk: registers added up, then eight sublanes
    S  = S' + k (beta (v - r))    a column times a row
    o  = q^T S * dk^-0.5          a sum over dk again

``q``, ``k`` and ``alpha`` vary along dk and arrive transposed ``[dk,
H]`` so that a head's column broadcasts along the lanes; ``v`` and ``o``
are rows ``[H, dv]`` as they lie; ``beta`` is one scalar a head, read
from SMEM.  Both sums run over the sublanes' axis: adds of whole
registers and one short reduction a head, no sum across lanes.  The
state's values are what ``kda_step`` computes; ``r`` and ``o`` are the
same 128 products summed in another order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the states of two lanes in and two out beside the temporaries: past the
# 16 MB a kernel gets unasked
_VMEM_BYTES = 48 * 1024 * 1024


def kernel_takes(H, dk, dv) -> bool:
    """The shapes the kernel's tiling can take: a head's state whole
    (8, 128) float32 tiles."""
    return dv % 128 == 0 and dk % 8 == 0


def _kernel(order_ref, n_ref,                                  # scalar prefetch (SMEM)
            beta_ref, q_ref, k_ref, alpha_ref, v_ref, s_ref,   # inputs
            o_ref, so_ref,                                     # outputs (so_ref is s_ref's buffer)
            *, scale):
    i = pl.program_id(0)
    H = s_ref.shape[1]

    @pl.when(i < n_ref[0])
    def _():
        lane = order_ref[i]
        q, k, alpha = q_ref[0], k_ref[0], alpha_ref[0]         # [dk, H]: a head a column
        for h in range(H):
            kc = k[:, h:h + 1]                                 # [dk, 1]
            decayed = alpha[:, h:h + 1] * s_ref[0, h]          # [dk, dv]
            r = (kc * decayed).sum(axis=0, keepdims=True)      # [1, dv]
            u = beta_ref[lane, h] * (v_ref[0, h:h + 1, :] - r)
            new = decayed + kc * u
            so_ref[0, h] = new
            o_ref[0, h:h + 1, :] = (q[:, h:h + 1] * new).sum(axis=0, keepdims=True) * scale


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_decode_step(q, k, v, a, beta, state, active, *, interpret=False):
    """The arguments and results of ``ops.kda.kda_step`` (active [B]
    bool given): q, k, a [B, H, dk], v [B, H, dv], beta [B, H], state
    [B, H, dk, dv] float32 -> (o [B, H, dv] in v's dtype, which means
    nothing for a lane that is not active; the new states, in the buffer
    the old ones came in where the caller donates it).
    ``interpret=True`` runs the same kernel on the CPU for tests."""
    Bn, H, dk = q.shape
    dv = v.shape[-1]

    def columns(x):  # [B, H, dk] -> [B, dk, H] float32
        return x.astype(jnp.float32).transpose(0, 2, 1)

    # the running lanes first, in order; behind them the last of them again
    n = active.sum(dtype=jnp.int32)
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)
    order = jnp.where(jnp.arange(Bn) < n, order, order[jnp.maximum(n - 1, 0)])

    def a_lane(*tail):
        return lambda i, order, n: (order[i], *tail)

    o, state = pl.pallas_call(
        functools.partial(_kernel, scale=dk ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(Bn,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),          # every lane's beta, a scalar a head
                pl.BlockSpec((1, dk, H), a_lane(0, 0)),
                pl.BlockSpec((1, dk, H), a_lane(0, 0)),
                pl.BlockSpec((1, dk, H), a_lane(0, 0)),
                pl.BlockSpec((1, H, dv), a_lane(0, 0)),
                pl.BlockSpec((1, H, dk, dv), a_lane(0, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, H, dv), a_lane(0, 0)),
                pl.BlockSpec((1, H, dk, dv), a_lane(0, 0, 0)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((Bn, H, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={7: 1},  # counting the two prefetched: the states
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_BYTES),
        name="kda_decode_step",
        interpret=interpret,
    )(order, n.reshape(1), beta.astype(jnp.float32), columns(q), columns(k), jnp.exp(columns(a)),
      v.astype(jnp.float32), state)
    o = jnp.where(active[:, None, None], o, 0.0)  # an idle lane's block was never written
    return o.astype(v.dtype), state

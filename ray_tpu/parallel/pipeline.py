"""SPMD pipeline parallelism over a "pp" mesh axis.

GPipe-style microbatched pipelining, written the TPU way: one SPMD
program under ``shard_map`` where every device runs the same scan and
activations rotate between pipeline stages with ``lax.ppermute`` over
ICI — there is no per-stage actor, no host-side scheduling, and the
whole pipeline (all stages x all microbatches) is a single jitted
computation XLA can overlap (reference substrate being replaced:
compiled-DAG pipelines in python/ray/dag/compiled_dag_node.py:1639;
the SPMD formulation follows the public scaling-book recipe).

Schedule: with S stages and M microbatches the scan runs S-1+M steps.
At step t, stage s computes microbatch t-s (when 0 <= t-s < M): stage 0
feeds from the input queue, later stages from the activation received
over ppermute at the end of the previous step; the last stage writes
its result into the output buffer.  Bubble fraction = (S-1)/(S-1+M).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def stack_stage_params(params_per_stage: list) -> Any:
    """Stack a list of per-stage parameter pytrees along a new leading
    axis (to be sharded over "pp")."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *params_per_stage)


def pipeline_spmd(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    mesh: Mesh,
    axis: str = "pp",
) -> Callable[[Any, jax.Array], jax.Array]:
    """Build the pipelined forward: ``f(stage_params, microbatches)``.

    stage_fn(stage_params_slice, x) -> y — one stage's computation; the
      output must have the same shape/dtype as ``x`` (inter-stage
      activations rotate through a single buffer).
    stage_params — pytree whose leaves have leading dim = pp size
      (see :func:`stack_stage_params`); sharded over ``axis``.
    microbatches — [M, ...] array of M microbatch inputs (replicated
      over ``axis``; shard other mesh axes as usual).

    Returns [M, ...] outputs (from the last stage, replicated over
    ``axis`` via the final gather-by-broadcast).
    """
    pp = mesh.shape[axis]

    def run(stage_params, microbatches):
        # Inside shard_map: leaves of stage_params have leading dim 1
        # (this device's stage); microbatches are full M.
        stage_params = jax.tree.map(lambda x: x[0], stage_params)
        stage = lax.axis_index(axis)
        m = microbatches.shape[0]
        steps = pp - 1 + m
        zero = jnp.zeros_like(microbatches[0])
        outputs0 = jnp.zeros_like(microbatches)

        def step(carry, t):
            recv, outputs = carry
            mb_idx = t - stage  # which microbatch this stage works on
            active = (mb_idx >= 0) & (mb_idx < m)
            feed = lax.cond(
                stage == 0,
                lambda: microbatches[jnp.clip(mb_idx, 0, m - 1)],
                lambda: recv,
            )
            y = stage_fn(stage_params, feed)
            y = jnp.where(active, y, zero)
            # Last stage: record its finished microbatch.
            is_last = stage == pp - 1
            outputs = lax.cond(
                is_last & active,
                lambda o: lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(mb_idx, 0, m - 1), 0
                ),
                lambda o: o,
                outputs,
            )
            # Rotate activations stage s -> s+1 (ring; the wraparound
            # value into stage 0 is ignored — stage 0 always feeds from
            # the input queue).
            nxt = lax.ppermute(y, axis, [(i, (i + 1) % pp) for i in range(pp)])
            return (nxt, outputs), None

        (_, outputs), _ = lax.scan(step, (zero, outputs0), jnp.arange(steps))
        # Outputs live on the last stage; broadcast them to every stage
        # so the result is replicated over the pp axis.
        outputs = lax.psum(
            jnp.where(stage == pp - 1, outputs, jnp.zeros_like(outputs)), axis
        )
        return outputs

    # stage params: sharded over pp on the leading dim; microbatches
    # replicated across pp (other axes handled by the caller's shardings).
    return jax.shard_map(
        run,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )


def microbatch(x: jax.Array, n_micro: int) -> jax.Array:
    """[B, ...] -> [M, B/M, ...]."""
    b = x.shape[0]
    if b % n_micro:
        raise ValueError(f"batch {b} not divisible by {n_micro} microbatches")
    return x.reshape(n_micro, b // n_micro, *x.shape[1:])


def bubble_fraction(pp: int, n_micro: int, v: int = 1) -> float:
    """Idle fraction of the schedule: each device is busy M*v of the
    pp*v+M-1 total steps.  v=1 reduces to GPipe's (pp-1)/(pp-1+M); at
    M=pp the interleaved case is (pp-1)/(pp-1+M*v) — the same layer
    count pipelining with a v-fold smaller relative bubble (the
    Megatron interleaved-1F1B bubble result)."""
    total = pp * v + n_micro - 1
    return (total - n_micro * v) / total


def pipeline_interleaved(
    first_fn: Callable[[Any, jax.Array], jax.Array],
    mid_fn: Callable[[Any, jax.Array], jax.Array],
    last_fn: Callable[[Any, jax.Array, jax.Array], jax.Array],
    mesh: Mesh,
    n_virtual: int = 1,
    axis: str = "pp",
) -> Callable:
    """Interleaved virtual-stage pipeline with NON-UNIFORM end stages
    (the Megatron interleaved schedule, in one SPMD program).

    Unlike :func:`pipeline_spmd`, the first and last stages need not
    preserve the rotating activation shape: ``first_fn`` consumes the
    raw input microbatch (e.g. token ids -> embeddings) on device 0,
    and ``last_fn`` consumes the final activation plus the microbatch's
    auxiliary input (e.g. targets -> loss) on the last device — embed
    and head are true pipeline stages instead of replicated pre/post
    work.  Each device additionally holds ``n_virtual`` layer chunks
    (device d owns chunks d, d+pp, ..): a microbatch circulates the
    ring v laps, shrinking the bubble from (S-1)/(S-1+M) to
    (pp-1)/(pp-1+M*v) for the same S = pp*v total stages.

    f(first_params, chunk_params, last_params, inputs, aux) -> [M, ...]
      chunk_params — leaves [pp, v, ...] (see
        :func:`stack_stage_params_interleaved`)
      inputs — [M, ...] raw microbatches (M <= pp: issue in rounds
        upstream for more)
      aux — [M, ...] per-microbatch auxiliary input for last_fn

    Returns [M, ...] of last_fn outputs, replicated over ``axis``.
    """
    pp = mesh.shape[axis]
    v = n_virtual

    def run(first_params, chunk_params, last_params, inputs, aux):
        chunk_params = jax.tree.map(lambda x: x[0], chunk_params)  # [v, ...]
        d = lax.axis_index(axis)
        m = inputs.shape[0]
        if m > pp:
            raise ValueError(
                f"interleaved schedule needs n_micro ({m}) <= pp ({pp}); "
                "issue microbatch rounds upstream"
            )
        # last microbatch (j=m-1) exits device pp-1 on lap v-1 at step
        # (m-1) + (v-1)*pp + (pp-1) → pp*v + m - 1 steps total
        steps = pp * v + m - 1
        # probe shapes: the rotating buffer is first_fn's output
        act_shape = jax.eval_shape(first_fn, first_params, inputs[0])
        zero_act = jnp.zeros(act_shape.shape, act_shape.dtype)
        out_shape = jax.eval_shape(
            last_fn, last_params, zero_act, aux[0]
        )
        outputs0 = jnp.zeros((m,) + out_shape.shape, out_shape.dtype)

        def step(carry, t):
            recv, outputs = carry
            tp = t - d
            lap = tp // pp
            j = tp % pp  # microbatch index (m <= pp: no collisions)
            active = (tp >= 0) & (lap < v) & (j < m)
            lap_c = jnp.clip(lap, 0, v - 1)
            j_c = jnp.clip(j, 0, m - 1)
            # device 0, lap 0: enter the ring through first_fn
            x = lax.cond(
                (d == 0) & (lap == 0),
                lambda: first_fn(first_params, inputs[j_c]),
                lambda: recv,
            )
            my_chunk = jax.tree.map(
                lambda p: lax.dynamic_index_in_dim(p, lap_c, 0, keepdims=False),
                chunk_params,
            )
            y = mid_fn(my_chunk, x)
            y = jnp.where(active, y, zero_act)
            # last device, last lap: exit through last_fn (inside the
            # cond so non-exit devices/steps skip the head compute)
            is_exit = (d == pp - 1) & (lap == v - 1) & active
            outputs = lax.cond(
                is_exit,
                lambda o: lax.dynamic_update_index_in_dim(
                    o, last_fn(last_params, y, aux[j_c]), j_c, 0
                ),
                lambda o: o,
                outputs,
            )
            nxt = lax.ppermute(y, axis, [(i, (i + 1) % pp) for i in range(pp)])
            return (nxt, outputs), None

        (_, outputs), _ = lax.scan(step, (zero_act, outputs0), jnp.arange(steps))
        outputs = lax.psum(
            jnp.where(d == pp - 1, outputs, jnp.zeros_like(outputs)), axis
        )
        return outputs

    return jax.shard_map(
        run,
        mesh=mesh,
        in_specs=(P(), P(axis), P(), P(), P()),
        out_specs=P(),
        check_vma=False,
    )


def stack_stage_params_interleaved(params_per_stage: list, pp: int, v: int) -> Any:
    """Stack S = pp*v per-chunk parameter pytrees into leaves of shape
    [pp, v, ...] with device d owning chunks d, d+pp, ... (the
    interleaved assignment)."""
    if len(params_per_stage) != pp * v:
        raise ValueError(f"need {pp * v} chunks, got {len(params_per_stage)}")
    per_device = []
    for d in range(pp):
        chunks = [params_per_stage[d + l * pp] for l in range(v)]
        per_device.append(jax.tree.map(lambda *xs: jnp.stack(xs), *chunks))
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_device)

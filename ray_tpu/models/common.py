"""Shared scaffolding for the model families.

One copy of the sharded-init / train-step recipe (Megatron layouts from
parallel.sharding, donated state, explicit batch placement) that
gpt2.py and llama.py both build on — the models differ in architecture,
not in how they train — and of the sampler the serving engine applies
to whatever family's logits (``sample_logits``).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np


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


def make_sharded_train_state(init_fn: Callable, mesh, optimizer, rules=None, rng=None):
    """Initialize params + opt state directly ON the mesh with the
    Megatron-style layout from parallel.sharding (no host-side giant
    arrays; init is jitted with output shardings).

    init_fn(rng) -> params pytree.  Returns (params, opt_state, specs).
    """
    from ray_tpu.parallel.sharding import gpt_sharding_rules, infer_param_spec, tree_shardings

    rng = rng if rng is not None else jax.random.PRNGKey(0)
    rules = rules if rules is not None else gpt_sharding_rules()
    abstract = jax.eval_shape(init_fn, rng)
    specs = infer_param_spec(abstract, rules, mesh)
    shardings = tree_shardings(mesh, specs)
    params = jax.jit(init_fn, out_shardings=shardings)(rng)
    opt_state = jax.jit(optimizer.init)(params)  # follows param shardings
    return params, opt_state, specs


def make_sharded_train_step(step_fn: Callable, mesh):
    """jit the step with donated state + explicit batch placement
    (dp over batch, sp over sequence); param/opt layouts come from the
    committed shardings set at init."""
    from jax.sharding import NamedSharding

    from ray_tpu.parallel.sharding import batch_spec

    data_sharding = NamedSharding(mesh, batch_spec(mesh))
    from ray_tpu._private import profiling

    jit_fn = jax.jit(step_fn, donate_argnums=(0, 1))
    jitted = profiling.instrument_jit("train_step", jit_fn)

    # Traced under the mesh, so that code which cannot be partitioned
    # automatically (ops.attention's Pallas kernel) can see it.
    def run(params, opt_state, tokens, targets):
        tokens = jax.device_put(tokens, data_sharding)
        targets = jax.device_put(targets, data_sharding)
        with jax.set_mesh(mesh):
            out = jitted(params, opt_state, tokens, targets)
        profiling.report_device_memory()
        return out

    def lower(*args):
        with jax.set_mesh(mesh):
            return jit_fn.lower(*args)

    run.data_sharding = data_sharding
    # the step's own lowering, for checks on what was compiled
    run.lower = lower
    return run


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

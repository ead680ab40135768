"""GSPMD execution plan: mesh construction + NamedSharding-jitted steps.

``build_plan(config)`` turns a :class:`ShardingConfig` into a
:class:`GspmdPlan` bound to a concrete device mesh.  The plan owns the
three recipes the pjit paper path needs (PAPERS.md "Scalable Training of
Language Models using JAX pjit and TPUv4"):

* ``shard_init``  — initialize params + optimizer state directly ON the
  mesh (jit with output shardings; no host-side giant arrays);
* ``jit_train_step`` — compile the step with EXPLICIT ``NamedSharding``
  in/out shardings (params/opt over the rule layout, batch over the
  ``batch`` axis, loss replicated) and donated state;
* ``save_checkpoint`` / ``load_checkpoint`` — per-shard persistence that
  re-shards onto the CURRENT mesh at load, which is what makes the
  elastic resize path (shrink/grow whole hosts of a slice) a plain
  restore instead of a bespoke migration.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

from ray_tpu.train.sharding.rules import ShardingConfig, match_partition_rules

# What lets the TPU compiler start an all-reduce asynchronously and fuse
# it beside independent work: the backward pass's sums of dx over `model`
# then run under the weight-gradient matmuls of their layer.  The two
# work only TOGETHER (either alone compiles the synchronous program), and
# the CPU compiler rejects `xla_tpu_*` names.
_OVERLAP_COLLECTIVES = {
    "xla_enable_async_all_reduce": True,
    "xla_tpu_enable_async_collective_fusion_fuse_all_reduce": True,
}


def build_mesh(config: ShardingConfig, devices: Optional[Sequence] = None):
    """Device mesh with the config's axes over ``devices`` (default: the
    global ``jax.devices()`` view — under jax.distributed that spans the
    whole worker group)."""
    import jax

    from ray_tpu.parallel.mesh import create_mesh

    devices = list(devices if devices is not None else jax.devices())
    shape = config.resolve_shape(len(devices))
    # create_mesh orders known dp/tp-style axes first; batch/model are
    # unknown to AXIS_ORDER so dict order (config.mesh order) is kept.
    ordered = {a: shape[a] for a in config.mesh}
    return create_mesh(ordered, devices)


class GspmdPlan:
    """A ShardingConfig bound to a mesh; all jits carry explicit
    NamedSharding in/out shardings."""

    def __init__(self, config: ShardingConfig, mesh):
        self.config = config
        self.mesh = mesh

    # -- specs ----------------------------------------------------------
    def param_specs(self, params: Any) -> Any:
        """PartitionSpec pytree for a (possibly abstract) param tree."""
        return match_partition_rules(self.config.rules(), params, self.mesh)

    def param_shardings(self, params: Any) -> Any:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        specs = self.param_specs(params)
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s),
            specs,
            is_leaf=lambda x: isinstance(x, P),
        )

    def data_sharding(self):
        """[batch, ...] arrays shard their leading dim over the batch
        axis (everything else replicated)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        axis = self.config.batch_axis
        size = self.mesh.shape.get(axis, 1)
        return NamedSharding(self.mesh, P(axis if size > 1 else None))

    def replicated(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P())

    # -- state ----------------------------------------------------------
    def shard_init(
        self, init_fn: Callable[[Any], Any], optimizer, rng=None
    ) -> Tuple[Any, Any]:
        """(params, opt_state) initialized on-mesh: ``init_fn(rng)`` is
        jitted with the rule layout as output shardings; the optimizer
        init follows the param shardings leaf-for-leaf."""
        import jax

        from ray_tpu._private import profiling

        rng = rng if rng is not None else jax.random.PRNGKey(0)
        abstract = jax.eval_shape(init_fn, rng)
        shardings = self.param_shardings(abstract)
        # Partition-invariant RNG: without it, XLA partitions the
        # threefry stream along the output sharding and a model=2 init
        # draws DIFFERENT weights than the same seed unsharded — loss
        # parity with the data-parallel baseline would be unprovable.
        prev = jax.config.jax_threefry_partitionable
        jax.config.update("jax_threefry_partitionable", True)
        try:
            params = profiling.instrument_jit(
                "gspmd_shard_init", jax.jit(init_fn, out_shardings=shardings))(rng)
        finally:
            jax.config.update("jax_threefry_partitionable", prev)
        # Optimizer moments mirror the param tree (their paths carry the
        # same suffixes, so the SAME rules shard them); schedule scalars
        # replicate.  Without explicit out_shardings the init's outputs
        # land on one device and the first step mixes device sets.
        abstract_opt = jax.eval_shape(optimizer.init, params)
        opt_specs = match_partition_rules(
            self.config.rules(), abstract_opt, self.mesh, strict=False
        )
        from jax.sharding import NamedSharding, PartitionSpec as P

        opt_shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s),
            opt_specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        opt_state = profiling.instrument_jit(
            "gspmd_opt_init", jax.jit(optimizer.init, out_shardings=opt_shardings))(params)
        return params, opt_state

    def _step_compiler_options(self) -> Optional[dict]:
        """The step's compile options: `_OVERLAP_COLLECTIVES` where the
        mesh is several TPUs (there are sums to overlap and a compiler
        that knows the names), none anywhere else, so that one device and
        a CPU mesh compile the program they always did."""
        several_tpus = self.mesh.size > 1 and self.mesh.devices.flat[0].platform == "tpu"
        return _OVERLAP_COLLECTIVES if several_tpus else None

    def jit_train_step(self, step_fn: Callable, params: Any, opt_state: Any):
        """jit ``step_fn(params, opt_state, tokens, targets) ->
        (params, opt_state, loss)`` with explicit NamedSharding in/out
        shardings and donated state; on a mesh of several TPUs the
        compiler may start the step's all-reduces asynchronously.  The
        returned callable device_puts host batches onto the batch-axis
        layout before dispatch."""
        import jax

        from ray_tpu._private import profiling

        param_sh = jax.tree_util.tree_map(lambda x: x.sharding, params)
        opt_sh = jax.tree_util.tree_map(lambda x: x.sharding, opt_state)
        data_sh = self.data_sharding()
        jit_fn = jax.jit(
            step_fn,
            in_shardings=(param_sh, opt_sh, data_sh, data_sh),
            out_shardings=(param_sh, opt_sh, self.replicated()),
            donate_argnums=(0, 1),
            compiler_options=self._step_compiler_options(),
        )
        jitted = profiling.instrument_jit("gspmd_train_step", jit_fn)

        # Traced under the mesh, so that code which cannot be
        # partitioned automatically (ops.attention's Pallas kernel) can
        # see it.
        def run(params, opt_state, tokens, targets):
            tokens = jax.device_put(tokens, data_sh)
            targets = jax.device_put(targets, data_sh)
            with jax.set_mesh(self.mesh):
                return jitted(params, opt_state, tokens, targets)

        def lower(*args):
            with jax.set_mesh(self.mesh):
                return jit_fn.lower(*args)

        run.data_sharding = data_sh
        # the step's own lowering, for checks on what was compiled
        run.lower = lower
        return run

    # -- checkpoint -----------------------------------------------------
    def save_checkpoint(self, state: Any, path: str) -> None:
        from ray_tpu.train.sharding.checkpoint import save_sharded

        save_sharded(state, path, self.mesh)

    def load_checkpoint(self, path: str, like: Any) -> Any:
        """Restore ``state`` re-sharded onto THIS plan's mesh.  ``like``
        supplies the target layout (a live state tree or one built from
        param_shardings); the saved mesh may have had a different size —
        shards are reassembled host-side and re-placed."""
        from ray_tpu.train.sharding.checkpoint import load_sharded

        return load_sharded(path, like)


def build_plan(
    config: Optional[ShardingConfig] = None, devices: Optional[Sequence] = None
) -> GspmdPlan:
    config = config or ShardingConfig()
    return GspmdPlan(config, build_mesh(config, devices))


def plan_from_context() -> GspmdPlan:
    """Inside ``train_loop_per_worker``: bind the trainer's
    ShardingConfig to the CURRENT global device view (which, under
    jax.distributed, spans the whole worker group; under elastic
    training it changes per generation, so call this on every loop
    (re)entry)."""
    from ray_tpu.train.context import get_context

    config = get_context().get_sharding_config()
    if config is None:
        raise RuntimeError(
            "this run has no ShardingConfig — pass "
            "JaxTrainer(..., sharding_config=ShardingConfig(...))"
        )
    return build_plan(config)

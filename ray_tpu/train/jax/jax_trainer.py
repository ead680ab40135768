"""JaxTrainer — the north-star trainer (BASELINE.json: "a new JaxTrainer
... shards JAX/Flax train_loop_per_worker across a v5e pod").

DataParallelTrainer with the JaxConfig backend: each worker is one jax
process on one TPU host; inside train_loop_per_worker the user binds the
trainer's ShardingConfig to the global device view
(ray_tpu.train.sharding.plan_from_context) and jits a sharded train step
through the plan — collectives ride ICI inside the program, the mesh and
the layout of every parameter come from ray_tpu.train.sharding.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

from ray_tpu.air.config import RunConfig, ScalingConfig
from ray_tpu.train.base_trainer import DataParallelTrainer
from ray_tpu.train._checkpoint import Checkpoint
from ray_tpu.train.jax.config import JaxConfig


class JaxTrainer(DataParallelTrainer):
    _default_backend_config = JaxConfig()

    def __init__(
        self,
        train_loop_per_worker: Callable,
        *,
        train_loop_config: Optional[Dict[str, Any]] = None,
        jax_config: Optional[JaxConfig] = None,
        scaling_config: Optional[ScalingConfig] = None,
        run_config: Optional[RunConfig] = None,
        datasets: Optional[Dict[str, Any]] = None,
        resume_from_checkpoint: Optional[Checkpoint] = None,
        sharding_config: Optional[Any] = None,
    ):
        """``sharding_config`` (a
        :class:`ray_tpu.train.sharding.ShardingConfig`) declares the
        GSPMD layout for this run: a batch x model device mesh over the
        worker group plus regex partition rules.  It travels to every
        rank's session — inside the loop,
        ``train.get_context().get_sharding_config()`` /
        ``sharding.plan_from_context()`` bind it to the live global
        device view (docs/sharded_training.md)."""
        super().__init__(
            train_loop_per_worker,
            train_loop_config=train_loop_config,
            backend_config=jax_config or JaxConfig(),
            scaling_config=scaling_config,
            run_config=run_config,
            datasets=datasets,
            resume_from_checkpoint=resume_from_checkpoint,
        )
        self.sharding_config = sharding_config

    def _constructor_state(self):
        state = super()._constructor_state()
        # This constructor names the backend config `jax_config`.
        state["jax_config"] = state.pop("backend_config")
        state["sharding_config"] = self.sharding_config
        return state

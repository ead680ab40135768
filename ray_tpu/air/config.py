"""Run/scaling configs (reference: python/ray/air/config.py:102
ScalingConfig, as_placement_group_factory :267; RunConfig/FailureConfig/
CheckpointConfig).  TPU-first addition: `use_tpu` + `topology` drive
slice-aware placement (one worker per TPU host, all chips visible)."""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from ray_tpu.exceptions import TPUPlacementError


def _tpu_hosts() -> List[float]:
    """TPU chip counts of the live nodes that advertise any."""
    import ray_tpu

    return [
        n["Resources"]["TPU"]
        for n in ray_tpu.nodes()
        if n["Alive"] and n["Resources"].get("TPU")
    ]


@dataclass
class ScalingConfig:
    num_workers: int = 1
    use_tpu: bool = False
    use_gpu: bool = False  # parity with the reference API; ignored on TPU
    resources_per_worker: Optional[Dict[str, float]] = None
    trainer_resources: Optional[Dict[str, float]] = None
    placement_strategy: str = "PACK"
    # TPU topology, e.g. "v5litepod-16": one worker per host in the slice.
    topology: Optional[str] = None
    # Elastic training floor: when set (and < num_workers), the trainer
    # treats world size as dynamic — a preempted/dead rank shrinks the
    # group to the largest healthy size >= min_workers (checkpoint,
    # re-rendezvous, resume; NOT charged to FailureConfig.max_failures),
    # and the group grows back toward num_workers at the next epoch
    # boundary once capacity returns.  None = fixed-size (the classic
    # whole-group-restart recovery).
    min_workers: Optional[int] = None

    def __post_init__(self):
        if self.min_workers is not None:
            if self.min_workers < 1:
                raise ValueError(
                    f"ScalingConfig.min_workers must be >= 1, got {self.min_workers}"
                )
            if self.min_workers > self.num_workers:
                raise ValueError(
                    f"ScalingConfig.min_workers ({self.min_workers}) cannot "
                    f"exceed num_workers ({self.num_workers})"
                )

    @property
    def elastic(self) -> bool:
        """True when the group may run below num_workers (min_workers set)."""
        return self.min_workers is not None and self.min_workers < self.num_workers

    def _worker_resources(self) -> Dict[str, float]:
        if self.resources_per_worker is not None:
            return dict(self.resources_per_worker)
        if self.use_tpu:
            # One worker per TPU host, holding every chip the host
            # advertises: detected by its raylet or given to init(),
            # never assumed here.
            hosts = _tpu_hosts()
            if not hosts:
                raise TPUPlacementError(
                    "ScalingConfig(use_tpu=True), but no node of this cluster "
                    "advertises a TPU resource (ray_tpu.cluster_resources())"
                )
            return {"TPU": float(min(hosts))}
        return {"CPU": 1.0}

    def check_tpu_placement(self) -> None:
        """Refuse a TPU worker group that would put two worker processes
        on one host's chips, before any placement group waits for it."""
        if not self._worker_resources().get("TPU"):
            return
        hosts = _tpu_hosts()
        if self.num_workers > len(hosts):
            raise TPUPlacementError(
                f"{self.num_workers} TPU training workers asked for, but the "
                f"cluster has {len(hosts)} TPU host(s).  A chip belongs to "
                "one process and nothing assigns chip ids to processes that "
                "share a host, so each TPU worker must own all chips of its "
                "own host: use num_workers=1 per host and shard over its "
                "chips inside the worker (ShardingConfig)."
            )

    def as_placement_group_factory(self):
        from ray_tpu.util.placement_group import placement_group

        bundles = [self._worker_resources() for _ in range(self.num_workers)]
        # TPU workers spread one-per-host so each owns its host's chips
        # (libtpu allows one process per chip set); CPU workers pack.
        strategy = "SPREAD" if self.use_tpu else self.placement_strategy

        def factory():
            return placement_group(bundles, strategy=strategy)

        return factory


@dataclass
class FailureConfig:
    max_failures: int = 0
    fail_fast: bool = False


@dataclass
class CheckpointConfig:
    num_to_keep: Optional[int] = None
    checkpoint_score_attribute: Optional[str] = None
    checkpoint_score_order: str = "max"
    checkpoint_frequency: int = 0


@dataclass
class RunConfig:
    name: Optional[str] = None
    storage_path: Optional[str] = None
    failure_config: Optional[FailureConfig] = None
    checkpoint_config: Optional[CheckpointConfig] = None
    verbose: int = 1
    log_to_file: bool = False
    # Trial stop criteria: dict ({"training_iteration": 10} /
    # {"metric": threshold}) or callable(result)->bool (reference:
    # air.RunConfig(stop=...) / tune.run stop).
    stop: Optional[Any] = None

    def resolved_storage_path(self) -> str:
        return self.storage_path or os.path.expanduser("~/ray_tpu_results")

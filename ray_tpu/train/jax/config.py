"""JaxConfig backend: bootstraps `jax.distributed` across the worker
group — the SPMD process-group equivalent of the reference's
`dist.init_process_group("nccl", ...)` (reference:
python/ray/train/torch/config.py:153; XLA precedent
train/torch/xla/config.py:120).

After on_start every worker is one jax process in a multi-host runtime:
`jax.devices()` is the global device list, collectives ride ICI inside
jitted programs, and `ray_tpu.parallel.create_mesh` builds pod-wide
meshes.  Actor restarts re-enter through the same rendezvous (an actor
restart means the whole group restarts — XLA's world is static, unlike
NCCL's per-rank rejoin; SURVEY.md §7 hard parts)."""

from __future__ import annotations

import logging
import os
from dataclasses import dataclass
from typing import Optional

from ray_tpu.train.backend import Backend, BackendConfig

logger = logging.getLogger(__name__)


@dataclass
class JaxConfig(BackendConfig):
    # None = auto: distributed init iff more than one worker.
    distributed: Optional[bool] = None

    def backend_cls(self):
        return _JaxBackend


def _get_coordinator(self_unused=None):
    import socket

    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.bind(("0.0.0.0", 0))
    port = s.getsockname()[1]
    s.close()
    try:
        ip = socket.gethostbyname(socket.gethostname())
    except OSError:
        ip = "127.0.0.1"
    return f"{ip}:{port}"


def _init_jax_distributed(coordinator: str, world_size: int, rank: int):
    import jax

    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=world_size,
        process_id=rank,
    )
    return len(jax.devices())


def _shutdown_jax_distributed():
    import jax

    try:
        jax.distributed.shutdown()
    except Exception:
        pass
    return True


class _JaxBackend(Backend):
    def on_start(self, worker_group, backend_config: JaxConfig):
        n = worker_group.num_workers
        distributed = backend_config.distributed
        if distributed is None:
            distributed = n > 1
        if not distributed:
            return
        # Elastic re-rendezvous: surviving workers may already hold a
        # jax.distributed runtime from the previous generation — tear it
        # down first so initialize() forms the new, resized world (no-op
        # on fresh processes).
        try:
            worker_group.execute(_shutdown_jax_distributed)
        except Exception:
            pass
        coordinator = worker_group.execute_single(0, _get_coordinator)
        logger.info("jax.distributed coordinator at %s (%d processes)", coordinator, n)
        refs = [
            w.execute_fn.remote(_init_jax_distributed, coordinator, n, rank)
            for rank, w in enumerate(worker_group.workers)
        ]
        import ray_tpu

        device_counts = ray_tpu.get(refs)
        logger.info("jax.distributed up: global devices per worker %s", device_counts)

    def on_shutdown(self, worker_group, backend_config: JaxConfig):
        try:
            worker_group.execute(_shutdown_jax_distributed)
        except Exception:
            pass

"""Device mesh construction.

The sharded training plane names its own axes (``ShardingConfig.mesh``,
by default ``("batch", "model")``) and builds them through
``create_mesh``.  Axis names ``create_mesh`` orders itself, for meshes
made by hand (ring attention over ``sp``, the in-jit pipeline over
``pp``):

    dp — data parallel (batch dim)
    fsdp — sharded data parallel (params sharded over dp replicas)
    tp — tensor/model parallel (hidden dims)
    sp — sequence/context parallel (sequence dim; ring attention)
    pp — pipeline parallel (layer dim)
    ep — expert parallel (MoE experts)

On real TPU pods the mesh should follow the physical topology so tp/sp
ride ICI; `create_mesh` defers to jax's device order which preserves
torus locality for contiguous slices.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

AXIS_ORDER = ("pp", "dp", "fsdp", "sp", "tp", "ep")


@dataclass
class MeshConfig:
    """Named axis sizes; -1 on one axis means 'absorb remaining devices'."""

    axes: Dict[str, int] = field(default_factory=dict)

    def resolve(self, n_devices: int) -> Dict[str, int]:
        axes = dict(self.axes)
        if not axes:
            return {"dp": n_devices}
        unknown = [k for k, v in axes.items() if v == -1]
        known = int(np.prod([v for v in axes.values() if v > 0])) if axes else 1
        if len(unknown) > 1:
            raise ValueError("at most one axis may be -1")
        if unknown:
            if n_devices % known:
                raise ValueError(f"{n_devices} devices not divisible by {known}")
            axes[unknown[0]] = n_devices // known
        else:
            # A strict subset of devices is allowed (e.g. an sp-only mesh
            # over 4 of 8 devices); more than available is not.
            if known > n_devices:
                raise ValueError(f"mesh axes {axes} product {known} > {n_devices} devices")
        return axes


def create_mesh(axes: Dict[str, int], devices: Optional[Sequence] = None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    cfg = MeshConfig(dict(axes)).resolve(len(devices))
    names = [a for a in AXIS_ORDER if a in cfg] + [a for a in cfg if a not in AXIS_ORDER]
    shape = [cfg[n] for n in names]
    arr = np.array(devices[: int(np.prod(shape))]).reshape(shape)
    return Mesh(arr, axis_names=tuple(names))

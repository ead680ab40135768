"""TPU accelerator manager — first-class TPU resources in the scheduler.

Equivalent of the reference's TPU support (reference:
python/ray/_private/accelerators/tpu.py:71 — GCE metadata detection :48,
TPU_VISIBLE_CHIPS :155, pod-type resources like "TPU-v4-16-head" :311,
get_current_node_additional_resources :334), built TPU-first: a node in a
slice advertises

    TPU                      — chips on this host
    TPU-<type>               — accelerator type (e.g. TPU-v5litepod-16)
    TPU-<type>-head          — 1.0 only on worker 0 of the slice, so a
                               placement group can pin the coordinator
    tpu-slice:<name>         — slice-affinity label resource

Detection never touches JAX: the process that detects is the driver or
a raylet, and a process that initialises the TPU backend takes the chip
from the workers that need it.  Chips on this host are counted from the
device files the TPU driver exposes (``/dev/accel*``, or one numbered
VFIO group per chip under ``/dev/vfio``); ``TPU_CHIPS_PER_HOST``
overrides the count.  The accelerator type and slice identity come from
``TPU_ACCELERATOR_TYPE`` / ``TPU_WORKER_ID`` or the GCE metadata server;
where neither says, they stay unknown and no typed resource is
advertised.
"""

from __future__ import annotations

import glob
import os
import urllib.request
from typing import Dict, Optional

GCE_TPU_METADATA_URL = "http://metadata.google.internal/computeMetadata/v1/instance/attributes/"
_METADATA_HEADERS = {"Metadata-Flavor": "Google"}


def _query_gce_metadata(key: str, timeout: float = 0.5) -> Optional[str]:
    try:
        req = urllib.request.Request(GCE_TPU_METADATA_URL + key, headers=_METADATA_HEADERS)
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.read().decode()
    except Exception:
        return None


class TPUAcceleratorManager:
    """Static methods only, mirroring the reference's plugin interface."""

    _cached: Optional[dict] = None

    # -- detection ---------------------------------------------------------
    @classmethod
    def _detect(cls) -> dict:
        if cls._cached is not None:
            return cls._cached
        info = {"chips": 0, "accelerator_type": None, "worker_id": 0, "pod_name": None, "topology": None}
        env_chips = os.environ.get("TPU_CHIPS_PER_HOST")
        if env_chips:
            info["chips"] = int(env_chips)
        else:
            info["chips"] = len(glob.glob("/dev/accel*")) or len(
                [p for p in glob.glob("/dev/vfio/*") if os.path.basename(p).isdigit()]
            )
        if info["chips"]:
            info["accelerator_type"] = os.environ.get("TPU_ACCELERATOR_TYPE")
            info["worker_id"] = int(os.environ.get("TPU_WORKER_ID") or 0)
            if info["accelerator_type"] is None and not (
                os.environ.get("RAY_TPU_SKIP_METADATA") or os.environ.get("TPU_SKIP_MDS_QUERY")
            ):
                accel = _query_gce_metadata("accelerator-type")
                if accel:
                    info["accelerator_type"] = accel
                    info["pod_name"] = _query_gce_metadata("instance-id")
                    info["worker_id"] = int(_query_gce_metadata("agent-worker-number") or 0)
                    info["topology"] = _query_gce_metadata("tpu-env")
        cls._cached = info
        return info

    # -- reference-parity interface ---------------------------------------
    @staticmethod
    def get_resource_name() -> str:
        return "TPU"

    @classmethod
    def get_current_node_num_accelerators(cls) -> int:
        return cls._detect()["chips"]

    @classmethod
    def get_current_node_accelerator_type(cls) -> Optional[str]:
        return cls._detect()["accelerator_type"]

    @classmethod
    def get_current_node_additional_resources(cls) -> Dict[str, float]:
        """Pod-type + head resources for slice-topology-aware placement."""
        info = cls._detect()
        out: Dict[str, float] = {}
        accel = info["accelerator_type"]
        if not info["chips"] or not accel:
            return out
        out[f"TPU-{accel}"] = float(info["chips"])
        if info["worker_id"] == 0:
            out[f"TPU-{accel}-head"] = 1.0
        if info["pod_name"]:
            out[f"tpu-slice:{info['pod_name']}"] = 1.0
        return out

    @classmethod
    def get_current_pod_name(cls) -> Optional[str]:
        return cls._detect()["pod_name"]

    @classmethod
    def get_current_pod_worker_count(cls) -> Optional[int]:
        info = cls._detect()
        accel = info["accelerator_type"]
        if not accel:
            return None
        try:
            total = int(str(accel).split("-")[-1])
            return max(1, total // max(1, info["chips"]))
        except ValueError:
            return None

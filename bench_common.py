"""Shared bench provenance: every bench record header says what device it
was captured on, so that no CPU capture can be filed under a per-chip
metric (the BENCH_r05 class, ROADMAP environment note).

Usage in every bench*.py:

    from bench_common import provenance
    rec = {"metric": ..., "value": ..., **provenance()}

``provenance()`` initialises JAX in the calling process, which takes the
chip: a parent calls it once the children that need the chip are done.
It raises where JAX finds no TPU; these scripts measure a chip or fail.
"""

from __future__ import annotations

import functools


@functools.lru_cache(maxsize=1)
def provenance() -> dict:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"this benchmark measures a TPU; JAX found {dev.platform!r} "
            f"({dev.device_kind})"
        )
    return {"on_tpu": True, "platform": "tpu", "device_kind": dev.device_kind}

"""Worker process entrypoint (reference:
python/ray/_private/workers/default_worker.py).  Connects back to the
raylet that spawned it (addresses via env) and runs the task loop."""

from __future__ import annotations

import logging
import sys
import time


def main():
    t_entry = time.time()  # where this process's ``setup.worker`` phase begins
    logging.basicConfig(level=logging.INFO, format="[worker %(asctime)s] %(message)s")
    import os
    import sys as _sys

    # Debugging aid: RAY_TPU_WORKER_STACK_DUMP_S=N dumps every thread's
    # stack to the worker log every N seconds (hung-worker triage).
    dump_s = os.environ.get("RAY_TPU_WORKER_STACK_DUMP_S")
    if dump_s:
        import faulthandler

        faulthandler.dump_traceback_later(float(dump_s), repeat=True, exit=False)

    # A sitecustomize may have imported jax and pinned a platform before
    # this runs; the job's JAX_PLATFORMS env must win in workers.
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "jax" in _sys.modules:
        try:
            _sys.modules["jax"].config.update("jax_platforms", platforms)
        except Exception:
            pass
    from ray_tpu._private import profiling
    from ray_tpu._private.worker import get_global_worker

    # ends where a replica's ``__init__`` enters (serve/_private/replica.py);
    # a worker that hosts none leaves it open and records nothing
    profiling.begin_setup(at=t_entry).enter("setup.worker")
    worker = get_global_worker()
    worker.connect_worker()
    try:
        worker.main_loop()
    except KeyboardInterrupt:
        pass
    sys.exit(0)


if __name__ == "__main__":
    main()

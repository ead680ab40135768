"""What both runners do inside the process that holds the chip."""

from __future__ import annotations

import glob
import os
import shutil
import tempfile
import time

_COMPILES = {"n": 0, "installed": False}


def count_compiles() -> int:
    """Compilations this process has made so far.  JAX reports each
    trace, lowering and backend compile (or load from the persistent
    cache) of a new program as a ``/jax/core/compile/...`` event; a jit
    call that finds its program in memory reports none.  The count is
    taken at both ends of the window: inside it there must be none."""
    if not _COMPILES["installed"]:
        import jax.monitoring

        def on_event(name, _secs, **_kw):
            if "/compile/" in name:
                _COMPILES["n"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)
        _COMPILES["installed"] = True
    return _COMPILES["n"]


def device_facts() -> dict:
    import jax

    dev = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in dev]
    return {"platform": dev[0].platform, "kind": dev[0].device_kind, "count": len(dev),
            "memory_peak_bytes": max(peaks), "pid": os.getpid()}


def _rep_settle(rep):
    """After set-up, as an operator does after warm-up: one full
    collection, then what lives (430k objects, most of them the traced
    programs' jaxprs and executables) is moved out of the collector's
    sight.  A full collection of them takes 0.16 s of the replica's loop
    thread, with one decode program in flight that is ten steps without
    a token, and whether a window met none, one or three of them decided
    its rate by up to 3% (1,707.98-1,757.78 tokens/s in six runs; my chip
    runs, PR 33).  The engine does not do this itself yet (PERF.md
    section 7).  Run in the replica through ``__ray_call__``."""
    import gc

    gc.collect()
    gc.freeze()
    return gc.get_freeze_count()


class Tracer:
    """``jax.profiler`` around part of a window, in the process that
    holds the chip; `facts` reduces the trace there, so only numbers
    travel."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.on = False
        self.t_start = self.t_stop = None

    def start(self):
        import jax

        # host TraceMes (the benchmark's annotations) and device events;
        # no Python call stacks, which slow the host and swell the trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.on, self.t_start = True, time.time()

    def stop(self):
        import jax

        self.t_stop = time.time()
        jax.profiler.stop_trace()
        self.on = False

    def facts(self, annotations, keep_dir=None, first_s=None) -> dict:
        from benchmark import trace_reduce

        try:
            planes = trace_reduce.load(trace_reduce.find_xplane(self.dir))
            facts = trace_reduce.reduce(planes, annotations, first_s=first_s)
            facts["host_window_s"] = self.t_stop - self.t_start
            if keep_dir:  # the raw trace, for reading by hand
                os.makedirs(keep_dir, exist_ok=True)
                for path in glob.glob(os.path.join(self.dir, "plugins", "profile", "*", "*")):
                    if os.path.getsize(path) < 24 << 20:
                        shutil.copy(path, keep_dir)
            return facts
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)

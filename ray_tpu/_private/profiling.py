"""On-demand sampling profiler + JAX/XLA introspection: the cluster's
bottleneck-attribution plane.

The PR 2 flight recorder answers *where time went between processes*
(spans, RPC/task-phase metrics).  This module answers the next two
questions the perf arc needs (reference: `ray timeline` + per-worker
py-spy/memray hooks; Podracer-style work diagnoses via per-step device
and compile profiles, not RPC spans):

- **What is a hot process doing?**  A stdlib-only wall/CPU sampling
  profiler: a daemon thread walks ``sys._current_frames()`` at a
  configurable Hz and folds stacks into counts.  Any live worker /
  actor host / raylet / the GCS can be attached via the
  ``profile_start`` / ``profile_stop`` / ``profile_dump`` RPC surface
  (handlers delegate to ``handle_profile_*`` here — they never block
  the dispatch loop).  Finished captures also ship to the GCS profile
  table through the existing metrics/span report channel, so a capture
  survives its driver.
- **What is the device doing?**  ``instrument_jit`` wraps a jitted
  callable with compile-time/retrace counters; ``report_device_memory``
  publishes ``live_buffers``/``memory_stats`` gauges where the backend
  supports them (CPU-safe no-op otherwise).

Exports: ``collapse`` (collapsed-stack / flamegraph lines),
``speedscope`` (speedscope JSON), ``merge_records`` (fold per-process
captures into one cluster profile keyed by actor/tenant label).
``ray_tpu.util.profiling`` is the driver-side orchestration on top.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu._private.config import CONFIG


class ProfilerError(Exception):
    """Base error of the profiling plane."""


class ProfilerConflictError(ProfilerError):
    """A session is already running in this process.  One sampler per
    process: two concurrent captures would double the overhead and
    interleave their sample sets; the second attach gets this typed
    error (carrying the live session id) instead of silently sharing."""

    def __init__(self, message: str, session_id: str = ""):
        super().__init__(message)
        self.session_id = session_id

    def __reduce__(self):
        # Keep session_id across the RPC pickle boundary (default
        # Exception reduction only replays args[0]).
        return (type(self), (self.args[0], self.session_id))


class ProfilerSessionNotFound(ProfilerError):
    """stop/dump named a session this process doesn't have (already
    reaped, or the caller's target restarted in between)."""


# Fallback idle heuristic for CPU mode on platforms without per-thread
# CPU accounting (/proc): leaf functions that mean "this thread is
# parked, not computing".  The blocking call itself is C code (no
# Python frame), so the heuristic keys on the Python caller
# conventionally wrapping it.
_IDLE_LEAF_NAMES = frozenset(
    {
        "wait",
        "_wait_for_tstate_lock",
        "select",
        "poll",
        "epoll",
        "accept",
        "recv",
        "recv_into",
        "readexactly",
        "_recv_exact",
        "read",
        "readline",
        "get",  # queue.Queue.get parks on a condition
        "join",
        "flush_loop",
        "run_forever",
        "sleep",
    }
)


class _ThreadCpuClock:
    """Per-thread CPU-time deltas from /proc/self/task/<tid>/stat
    (Linux).  A thread whose utime+stime did not advance since the last
    sample was parked (C-level sleep/select/recv included — which the
    Python-frame leaf heuristic cannot see).  ``delta(py_tid)`` is
    None when accounting is unavailable → caller falls back to the
    leaf-name heuristic."""

    def __init__(self):
        self._available = os.path.isdir("/proc/self/task")
        self._native: Dict[int, int] = {}  # python tid -> native tid
        self._last: Dict[int, int] = {}  # native tid -> cpu jiffies

    def _refresh_native_map(self) -> None:
        for t in threading.enumerate():
            nid = getattr(t, "native_id", None)
            if nid is not None:
                self._native[t.ident] = nid

    def _cpu_jiffies(self, native_tid: int) -> Optional[int]:
        try:
            with open(f"/proc/self/task/{native_tid}/stat", "rb") as f:
                data = f.read()
            # utime, stime are fields 14, 15 (1-based), after the
            # parenthesized comm which may itself contain spaces.
            rest = data.rsplit(b")", 1)[1].split()
            return int(rest[11]) + int(rest[12])
        except (OSError, IndexError, ValueError):
            return None

    def delta(self, py_tid: int) -> Optional[int]:
        """CPU jiffies this thread burned since its previous probe;
        None = unknown (no accounting for this thread/platform).  Used
        as the sample WEIGHT: when GIL contention stretches the tick
        interval, a continuously-computing thread still accrues its
        full CPU time while a housekeeping loop's 1-jiffy blip stays a
        blip."""
        if not self._available:
            return None
        nid = self._native.get(py_tid)
        if nid is None:
            self._refresh_native_map()
            nid = self._native.get(py_tid)
            if nid is None:
                return None
        cur = self._cpu_jiffies(nid)
        if cur is None:
            # Stale mapping: the thread behind this Python ident exited
            # and a new thread reused the ident — re-resolve once so
            # churned threads don't permanently fall back to the leaf
            # heuristic (or read a recycled tid's clock).
            self._native.pop(py_tid, None)
            self._refresh_native_map()
            nid = self._native.get(py_tid)
            cur = self._cpu_jiffies(nid) if nid is not None else None
            if cur is None:
                return None
        prev = self._last.get(nid)
        self._last[nid] = cur
        if prev is None:
            return 0  # no baseline yet: treat the first probe as idle
        return max(0, cur - prev)


def _frame_label(code) -> str:
    return f"{os.path.basename(code.co_filename)}:{code.co_name}"


class SamplingProfiler:
    """One capture: a daemon thread sampling every live thread's stack.

    ``mode="wall"`` keeps every sample; ``mode="cpu"`` drops samples
    whose leaf frame is a known parked-thread idiom (see
    ``_IDLE_LEAF_NAMES``) — an approximation, but a useful one without
    OS-level thread state (stdlib-only by design).
    """

    def __init__(
        self,
        session_id: str,
        hz: float,
        duration_s: float,
        mode: str = "wall",
        label: str = "",
        on_finish=None,
    ):
        self.session_id = session_id
        self.hz = max(1.0, min(float(hz), 1000.0))
        self.duration_s = float(duration_s)
        self.mode = mode if mode in ("wall", "cpu") else "wall"
        self.label = label
        self.started_at = time.time()
        self.ended_at: Optional[float] = None
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._samples: Dict[Tuple[str, ...], int] = {}
        self._ticks = 0
        self._sample_count = 0
        self._idle_dropped = 0
        self._threads_seen: set = set()
        self._errors: List[str] = []
        self._max_depth = int(CONFIG.profile_max_stack_depth)
        self._on_finish = on_finish
        self._cpu_clock = _ThreadCpuClock() if self.mode == "cpu" else None
        self._thread = threading.Thread(
            target=self._run, daemon=True, name=f"profile-sampler-{session_id[:8]}"
        )

    # -- lifecycle ------------------------------------------------------
    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()

    @property
    def running(self) -> bool:
        return self._thread.is_alive()

    def _run(self) -> None:
        interval = 1.0 / self.hz
        deadline = time.monotonic() + self.duration_s
        own_tid = threading.get_ident()
        try:
            while not self._stop.is_set() and time.monotonic() < deadline:
                t0 = time.perf_counter()
                self._sample_once(own_tid)
                # Absorb the sampling cost into the interval so the
                # effective rate stays ~hz instead of hz + walk time.
                self._stop.wait(max(0.0005, interval - (time.perf_counter() - t0)))
        except Exception as e:  # noqa: BLE001 — a broken sampler must end cleanly
            with self._lock:
                self._errors.append(f"sampler died: {type(e).__name__}: {e}")
        finally:
            with self._lock:
                self.ended_at = time.time()
            if self._on_finish is not None:
                try:
                    self._on_finish(self)
                except Exception:  # noqa: BLE001 — best-effort ship
                    pass

    def _sample_once(self, own_tid: int) -> None:
        # Phase 1 — walk every stack WITHOUT any GIL-releasing call in
        # between: the frame objects in the snapshot stay live only
        # while the sampled threads cannot run.  (The CPU-clock probes
        # below do file I/O, which releases the GIL; probing first once
        # produced truncated single-frame stacks of frames the thread
        # had already popped.)
        frames = sys._current_frames()
        walked: List[Tuple[int, str, Tuple[str, ...]]] = []
        for tid, top in frames.items():
            if tid == own_tid:
                continue
            stack: List[str] = []
            f = top
            depth = 0
            while f is not None and depth < self._max_depth:
                stack.append(_frame_label(f.f_code))
                f = f.f_back
                depth += 1
            stack.reverse()
            walked.append((tid, top.f_code.co_name, tuple(stack)))
        # Phase 2 — filter + fold (CPU-clock probes may release the GIL
        # freely now; the stacks are already copied out as strings).
        with self._lock:
            self._ticks += 1
            for tid, leaf_name, key in walked:
                self._threads_seen.add(tid)
                weight = 1
                if self.mode == "cpu":
                    # Real per-thread CPU accounting where the OS
                    # provides it (samples weighted by jiffies burned);
                    # leaf-name heuristic otherwise.
                    delta = self._cpu_clock.delta(tid)
                    if delta == 0 or (
                        delta is None and leaf_name in _IDLE_LEAF_NAMES
                    ):
                        self._idle_dropped += 1
                        continue
                    if delta is not None:
                        weight = delta
                self._samples[key] = self._samples.get(key, 0) + weight
                self._sample_count += weight

    # -- export ---------------------------------------------------------
    def snapshot(self, partial: Optional[bool] = None) -> Dict[str, Any]:
        """The session's record — safe to call mid-capture (a dump of a
        dying worker returns whatever was sampled so far)."""
        with self._lock:
            samples = {";".join(k): v for k, v in self._samples.items()}
            errors = list(self._errors)
            ticks, count = self._ticks, self._sample_count
            idle, nthreads = self._idle_dropped, len(self._threads_seen)
            ended_at = self.ended_at
        return {
            "session_id": self.session_id,
            "label": self.label,
            "pid": os.getpid(),
            "hz": self.hz,
            "mode": self.mode,
            "duration_s": self.duration_s,
            "started_at": self.started_at,
            "ended_at": ended_at,
            "running": self.running if partial is None else partial,
            "ticks": ticks,
            "sample_count": count,
            "idle_dropped": idle,
            "threads_seen": nthreads,
            "errors": errors,
            "samples": samples,
        }


# ----------------------------------------------------------------------
# per-process session registry (one active capture per process)
# ----------------------------------------------------------------------
_registry_lock = threading.Lock()
_active: Optional[SamplingProfiler] = None
_last_record: Optional[Dict[str, Any]] = None


def _ship_finished(profiler: SamplingProfiler) -> None:
    """Natural end of a capture: cache the record locally (a late dump
    RPC still gets it) and ship it to the GCS profile table through the
    existing report channel (worker GCS client, or the raylet/GCS
    report channel — same path spans ride)."""
    global _last_record
    record = profiler.snapshot(partial=False)
    with _registry_lock:
        _last_record = record
    from ray_tpu._private import telemetry

    telemetry.count_profile_session("completed")
    try:
        from ray_tpu.util import metrics as metrics_mod
        from ray_tpu.util import tracing

        tracing.record_event_span(
            "profile.capture",
            record["started_at"],
            record["ended_at"] or time.time(),
            attributes={
                "label": record["label"],
                "hz": record["hz"],
                "mode": record["mode"],
                "sample_count": record["sample_count"],
            },
        )
        metrics_mod.report(
            "profile_report",
            {
                "profile": record,
                # per-tenant accounting in the GCS profile table (same
                # stamp the span flusher carries)
                "tenant": os.environ.get("RAY_TPU_TENANT") or "default",
            },
        )
    except Exception:  # noqa: BLE001 — shipping is best-effort
        pass


def handle_profile_start(payload: Optional[dict]) -> Dict[str, Any]:
    """RPC surface: attach a sampler to THIS process.  Non-blocking —
    spawns the daemon sampler thread and returns immediately."""
    global _active
    payload = payload or {}
    duration = min(
        max(0.05, float(payload.get("duration_s") or 10.0)),
        float(CONFIG.profile_max_duration_s),
    )
    hz = float(payload.get("hz") or CONFIG.profile_default_hz)
    mode = payload.get("mode") or "wall"
    label = str(payload.get("label") or f"pid:{os.getpid()}")
    session_id = payload.get("session_id") or _new_session_id()
    with _registry_lock:
        # Conflict gate keys on ended_at, not thread liveness: a just-
        # registered session whose thread hasn't started yet (start()
        # below, still under this lock) and a running one both have
        # ended_at None — checking Thread.is_alive() here left a window
        # where a concurrent attach could silently overwrite the
        # registry and double the sampling overhead.
        if _active is not None and _active.ended_at is None:
            from ray_tpu._private import telemetry

            telemetry.count_profile_session("conflict")
            raise ProfilerConflictError(
                f"a profile session ({_active.session_id}) is already running "
                f"in pid {os.getpid()}; stop it or wait for its deadline",
                session_id=_active.session_id,
            )
        prof = SamplingProfiler(
            session_id, hz, duration, mode=mode, label=label, on_finish=_ship_finished
        )
        _active = prof
        try:
            prof.start()
        except Exception:
            # Thread spawn failed (e.g. at the process thread limit): a
            # registered-but-never-started session would hold the
            # conflict gate (ended_at stays None with no thread to set
            # it) and brick profiling for the process — release the
            # slot and surface the error instead.
            _active = None
            raise
    return {
        "session_id": session_id,
        "pid": os.getpid(),
        "hz": prof.hz,
        "mode": prof.mode,
        "duration_s": duration,
        "started_at": prof.started_at,
        "label": label,
    }


def _find(session_id: Optional[str]) -> SamplingProfiler:
    if _active is None or (session_id and _active.session_id != session_id):
        raise ProfilerSessionNotFound(
            f"no profile session {session_id or '<any>'} in pid {os.getpid()}"
        )
    return _active


def handle_profile_stop(payload: Optional[dict]) -> Dict[str, Any]:
    """Stop the capture early; returns the final record."""
    payload = payload or {}
    with _registry_lock:
        prof = _find(payload.get("session_id"))
    prof.stop()
    # The sampler thread exits within one interval; don't join on the
    # dispatch loop — snapshot now (records through the last tick).
    return prof.snapshot(partial=False)


def handle_profile_dump(payload: Optional[dict]) -> Dict[str, Any]:
    """Dump the capture (partial if still running).  ``stop=True``
    (default) also ends it — the one-call dump-and-detach the driver
    orchestration uses."""
    global _last_record
    payload = payload or {}
    sid = payload.get("session_id")
    with _registry_lock:
        if _active is None or (sid and _active.session_id != sid):
            if _last_record is not None and (
                not sid or _last_record["session_id"] == sid
            ):
                return _last_record
            raise ProfilerSessionNotFound(
                f"no profile session {sid or '<any>'} in pid {os.getpid()}"
            )
        prof = _active
    if payload.get("stop", True):
        prof.stop()
    return prof.snapshot()


def active_session_id() -> Optional[str]:
    with _registry_lock:
        if _active is not None and _active.running:
            return _active.session_id
    return None


def _new_session_id() -> str:
    import secrets

    return secrets.token_hex(8)


# ----------------------------------------------------------------------
# export formats (pure functions; shared by util.profiling + dashboard)
# ----------------------------------------------------------------------
def collapse(record: Dict[str, Any], root: Optional[str] = None) -> str:
    """Brendan-Gregg collapsed-stack lines ("f1;f2;f3 count"), the
    input format of flamegraph.pl / speedscope / inferno.  ``root``
    (default: the record's label) prefixes every stack so merged
    cluster profiles stay attributable per process."""
    prefix = record.get("label", "") if root is None else root
    lines = []
    for stack, count in sorted(record.get("samples", {}).items()):
        line = f"{prefix};{stack}" if prefix else stack
        lines.append(f"{line} {count}")
    return "\n".join(lines) + ("\n" if lines else "")


def merge_records(records: List[Dict[str, Any]]) -> Dict[str, int]:
    """Fold per-process capture records into one cluster-wide stack
    map, each stack rooted at its process label (actor/tenant/raylet),
    so one flamegraph shows the whole cluster with per-target subtrees."""
    merged: Dict[str, int] = {}
    for rec in records:
        prefix = rec.get("label", "")
        for stack, count in rec.get("samples", {}).items():
            key = f"{prefix};{stack}" if prefix else stack
            merged[key] = merged.get(key, 0) + count
    return merged


def speedscope(records: List[Dict[str, Any]], name: str = "ray_tpu profile") -> Dict[str, Any]:
    """Speedscope JSON (sampled profiles, one per capture record) —
    https://www.speedscope.app file-format-schema.  Aggregated stacks
    become one weighted sample each; weights are sample counts."""
    frames: List[Dict[str, str]] = []
    frame_idx: Dict[str, int] = {}

    def fidx(label: str) -> int:
        i = frame_idx.get(label)
        if i is None:
            i = frame_idx[label] = len(frames)
            frames.append({"name": label})
        return i

    profiles = []
    for rec in records:
        samples: List[List[int]] = []
        weights: List[float] = []
        for stack, count in sorted(rec.get("samples", {}).items()):
            samples.append([fidx(fr) for fr in stack.split(";")])
            weights.append(float(count))
        profiles.append(
            {
                "type": "sampled",
                "name": rec.get("label") or f"pid {rec.get('pid')}",
                "unit": "none",
                "startValue": 0.0,
                "endValue": float(sum(weights)),
                "samples": samples,
                "weights": weights,
            }
        )
    return {
        "$schema": "https://www.speedscope.app/file-format-schema.json",
        "shared": {"frames": frames},
        "profiles": profiles,
        "name": name,
        "exporter": "ray_tpu.profiling",
        "activeProfileIndex": 0,
    }


def top_frames(records: List[Dict[str, Any]], n: int = 10) -> List[Tuple[str, int, float]]:
    """(leaf_frame, samples, fraction) of the hottest exclusive frames
    across the given records — the "what is it doing" one-liner."""
    counts: Dict[str, int] = {}
    total = 0
    for rec in records:
        for stack, count in rec.get("samples", {}).items():
            leaf = stack.rsplit(";", 1)[-1]
            counts[leaf] = counts.get(leaf, 0) + count
            total += count
    ranked = sorted(counts.items(), key=lambda kv: -kv[1])[:n]
    return [(fr, c, (c / total if total else 0.0)) for fr, c in ranked]


# ----------------------------------------------------------------------
# JAX/XLA introspection (CPU-safe; no-ops when jax is absent)
# ----------------------------------------------------------------------
# The compile ledger.  JAX reports each part of making a program ready,
# in the thread that made the call, as a ``jax.monitoring`` event; the
# listeners book them under the name open in that thread (the
# innermost ``instrument_jit`` call, else the set-up phase under way,
# else "other") and in the process's totals.  A call that finds its
# program in memory reports nothing, so the listeners run only where
# JAX compiles.  docs/profiling.md has the table.
_TRACED = "/jax/core/compile/jaxpr_trace_duration"
# every program JAX lowers (a first call, a new shape or dtype).  A call
# that only misses the jit's fast-path cache (the same shapes from
# another place: NumPy arrays after device arrays) finds its traced and
# compiled program in memory and lowers nothing, though that cache grows
_LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"
# brackets ``compiler.compile_or_get_cached``: a real backend compile OR
# the read and load of an executable the persistent cache held.  The hit
# event fires inside the bracket, before the duration is reported
_BACKEND = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
# reported where a compiled program is WRITTEN to the cache: one that
# compiled in under ``jax_persistent_cache_min_compile_time_secs`` is a
# backend miss that counts no cache miss
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
_SPANS = {_TRACED: ("traces", "trace_s"), _LOWERED: ("lowerings", "lower_s"),
          _BACKEND: ("backend_calls", None)}  # hit or miss: by what was last seen
LEDGER_KEYS = (
    "traces", "trace_s", "lowerings", "lower_s",
    "backend_calls", "backend_hit_s", "backend_miss_s",
    "cache_hits", "cache_misses", "cache_read_s", "cache_saved_s",
)


def _new_ledger() -> Dict[str, Any]:
    return {k: 0.0 if k.endswith("_s") else 0 for k in LEDGER_KEYS}


_jit_lock = threading.Lock()
_jit_records: Dict[str, Dict[str, Any]] = {}
_compile_totals: Dict[str, Any] = _new_ledger()
_listening = False


class _Thread:
    """What one thread has open, for the listeners."""

    __slots__ = ("jit", "booked", "split", "cache_hit", "spans")

    def __init__(self):
        self.jit: Optional[str] = None  # the innermost instrument_jit call's name
        self.booked = 0  # events booked from this thread
        self.split: Dict[str, Any] = {}  # those of the wrapped call under way
        self.cache_hit = False  # a hit reported since the last backend call's span
        # (start, count key, seconds key, seconds) of the spans no later
        # one has enclosed yet
        self.spans: List[Tuple[float, str, str, float]] = []


_threads = threading.local()


def _this_thread() -> _Thread:
    mine = getattr(_threads, "mine", None)
    if mine is None:
        mine = _threads.mine = _Thread()
    return mine


def _book(mine: _Thread, **amounts) -> None:
    setup = _setup
    name = mine.jit or (setup.open if setup is not None else None) or "other"
    with _jit_lock:
        rec = _jit_records.get(name)
        if rec is None:
            rec = _jit_records[name] = _new_record()
        for key, n in amounts.items():
            rec[key] += n
            _compile_totals[key] += n
    mine.booked += 1
    if mine.jit is not None:
        split = mine.split
        for key, n in amounts.items():
            split[key] = split.get(key, 0) + n


def _on_span(event, start, end, **_kw) -> None:
    keys = _SPANS.get(event)
    if keys is None:
        return
    mine = _this_thread()
    count, seconds = keys
    if event == _BACKEND:
        hit, mine.cache_hit = mine.cache_hit, False
        seconds = "backend_hit_s" if hit else "backend_miss_s"
    # What JAX does inside a span it reports before it: a jit traced
    # while another is traced, the jitted helpers a lowering rule calls.
    # The outermost span keeps the time and what it enclosed is taken
    # off again, so a program is one trace, one lowering and one backend
    # call whose seconds do not overlap.  (JAX reports the same seconds
    # as durations; only this form says where they lie.)
    amounts = {count: 1, seconds: end - start}
    spans = mine.spans
    while spans and spans[-1][0] >= start:
        _, in_count, in_seconds, secs = spans.pop()
        amounts[in_count] = amounts.get(in_count, 0) - 1
        amounts[in_seconds] = amounts.get(in_seconds, 0.0) - secs
    spans.append((start, count, seconds, end - start))
    if len(spans) > 4096:  # outermost spans of programs long done
        del spans[:2048]
    _book(mine, **amounts)


def _on_duration(event, secs, **_kw) -> None:
    if event == _CACHE_READ:
        _book(_this_thread(), cache_read_s=secs)
    elif event == _CACHE_SAVED:
        _book(_this_thread(), cache_saved_s=secs)


def _on_event(event, **_kw) -> None:
    if event == _CACHE_HIT:
        mine = _this_thread()
        mine.cache_hit = True
        _book(mine, cache_hits=1)
    elif event == _CACHE_MISS:
        _book(_this_thread(), cache_misses=1)


def listen_for_compiles() -> bool:
    """Install the ledger's listeners, once a process -> whether JAX
    introspection is on (``jax_introspection``).  ``instrument_jit``
    calls this; so does whoever wants what compiles before the first
    instrumented jit booked (a replica's set-up)."""
    global _listening
    try:
        if not CONFIG.jax_introspection:
            return False
    except Exception:  # noqa: BLE001 — config unavailable in exotic contexts
        pass
    with _jit_lock:
        if not _listening:
            import jax.monitoring

            jax.monitoring.register_event_time_span_listener(_on_span)
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            jax.monitoring.register_event_listener(_on_event)
            _listening = True
    return True


def _new_record() -> Dict[str, Any]:
    return {"compiles": 0, "retraces": 0, "compile_seconds": 0.0, "first_call_s": 0.0,
            **_new_ledger()}


def jit_stats(name: Optional[str] = None) -> Dict[str, Any]:
    """Per-name compile records: an instrumented function's
    ``compiles``, ``retraces`` and ``compile_seconds`` (``first_call_s``
    is the same seconds) with its share of the ledger (LEDGER_KEYS), and
    the ledger alone under the set-up phases' names and "other"."""
    with _jit_lock:
        if name is not None:
            return dict(_jit_records.get(name, {}))
        return {k: dict(v) for k, v in _jit_records.items()}


def compile_totals() -> Dict[str, Any]:
    """The ledger's sums over every name: what this process has spent
    tracing, lowering, compiling and loading from the cache."""
    with _jit_lock:
        return dict(_compile_totals)


def programs_lowered() -> int:
    """Programs this process has lowered since the listeners were
    installed: it grows where anything compiled (one int read: the
    engine asks after every slice of its loop)."""
    return _compile_totals["lowerings"]


def instrument_jit(name: str, jfn):
    """Wrap an already-jitted callable with compile-time and retrace
    counters.

    Steady-state cost per call: one thread-local read, one perf_counter
    read and the name the listeners book under set and put back (well
    under a microsecond) — far inside the telemetry budget for
    step-scale functions.  A call compiled where JAX reports that it
    lowered a program (``_LOWERED``), not where the jit's fast-path
    cache grew: that also grows when the same shapes come from another
    place, which costs no compile.  When a call triggers a (re)trace,
    its wall time is recorded as ``jax_compile_seconds``
    (trace+compile+first run — the stall the operator actually sees) and
    a ``jax.compile`` span lands in the timeline, with the call's split
    of the ledger as attributes.  Disabled via
    ``jax_introspection=False`` (returns ``jfn`` unwrapped, and nothing
    listens).
    """
    if not listen_for_compiles():
        return jfn
    state = {"compiles": 0}
    with _jit_lock:
        _jit_records.setdefault(name, _new_record())

    def wrapped(*args, **kwargs):
        mine = getattr(_threads, "mine", None) or _this_thread()
        outer, booked = mine.jit, mine.booked
        mine.jit = name
        t0 = time.perf_counter()
        try:
            out = jfn(*args, **kwargs)
        except BaseException:
            mine.jit, mine.split = outer, {}
            raise
        mine.jit = outer
        if mine.booked != booked:
            dt = time.perf_counter() - t0
            split, mine.split = mine.split, {}
            if split.get("lowerings"):
                _note_compile(name, state, split, dt)
        return out

    wrapped.__name__ = f"instrumented_{name}"
    wrapped.__wrapped__ = jfn
    return wrapped


def _note_compile(name: str, state: Dict[str, int], split: Dict[str, Any], dt: float) -> None:
    """A wrapped call that lowered a program: its counters, its metric
    and its ``jax.compile`` span."""
    from ray_tpu._private import telemetry

    state["compiles"] += 1
    first = state["compiles"] == 1
    with _jit_lock:
        rec = _jit_records[name]
        rec["compiles"] += 1
        rec["compile_seconds"] += dt
        rec["first_call_s"] += dt
        if not first:
            rec["retraces"] += 1
    telemetry.observe_jax_compile(name, dt)
    if not first:
        telemetry.count_jax_retrace(name)
    try:
        from ray_tpu.util import tracing

        end = time.time()
        tracing.record_event_span(
            "jax.compile",
            end - dt,
            end,
            attributes={
                "function": name, "retrace": not first,
                "trace_s": split.get("trace_s", 0.0), "lower_s": split.get("lower_s", 0.0),
                "backend_s": split.get("backend_hit_s", 0.0) + split.get("backend_miss_s", 0.0),
                "cache_hit": bool(split.get("cache_hits")),
            },
        )
    except Exception:  # noqa: BLE001
        pass


# ----------------------------------------------------------------------
# a replica's set-up, phase by phase
# ----------------------------------------------------------------------
class SetupPhases:
    """One replica's set-up as consecutive phases on the epoch clock
    (``time.time()``: the clock ``jax.profiler`` stamps host events on,
    and the ``jax.compile`` spans).  A phase begins where the one before
    it ended, so the phases tile the time from the first one's start and
    never overlap.  ``finish`` records each as a span, all of one trace,
    under the root ``setup.replica`` that caused them.  While a phase is
    open the ledger books under its name what compiles outside every
    instrumented jit.  docs/serving.md "What a start is made of"."""

    ROOT = "setup.replica"

    def __init__(self, at: Optional[float] = None):
        self.spans: List[Tuple[str, float, float]] = []  # (name, start, end), in order
        self.open: Optional[str] = None
        self.at = time.time() if at is None else at  # where the last phase ended
        self._recorded = False

    def enter(self, name: str) -> None:
        """End the open phase and begin ``name`` where the last one ended."""
        self.leave()
        self.open = name

    def leave(self) -> None:
        """End the open phase; what follows belongs to the next ``enter``."""
        if self.open is not None:
            now = time.time()
            self.spans.append((self.open, self.at, now))
            self.open, self.at = None, now

    def finish(self) -> None:
        """End the set-up and record its spans, once."""
        global _setup
        self.leave()
        if _setup is self:
            _setup = None
        if self._recorded or not self.spans:
            return
        self._recorded = True
        from ray_tpu.util import tracing

        trace_id, root = tracing.new_trace_id(), tracing.new_span_id()
        tracing.record_span(self.ROOT, self.spans[0][1], self.at, context=(trace_id, root, None))
        for name, start, end in self.spans:
            tracing.record_span(name, start, end, context=(trace_id, tracing.new_span_id(), root))

    def seconds(self, name: str) -> float:
        return sum((end - start for n, start, end in self.spans if n == name), 0.0)


# the set-up under way in this process: begun at the worker's entry
# (default_worker.main) or by the first engine built outside a worker
_setup: Optional[SetupPhases] = None


def begin_setup(at: Optional[float] = None) -> SetupPhases:
    global _setup
    _setup = SetupPhases(at)
    return _setup


def setup_under_way() -> Optional[SetupPhases]:
    return _setup


_dev_report_lock = threading.Lock()
_last_dev_report = 0.0


def report_device_memory(min_interval_s: float = 1.0) -> None:
    """Publish per-device memory gauges (``memory_stats``) and the live
    on-device buffer count (``live_arrays``) where the backend supports
    them.  CPU backends typically report nothing — then this is a
    cheap no-op.  Rate-limited so per-step call sites cost one clock
    read on the fast path."""
    global _last_dev_report
    from ray_tpu._private import telemetry

    if not telemetry.enabled():
        return
    now = time.monotonic()
    if now - _last_dev_report < min_interval_s:
        return  # lock-free fast path for per-step call sites
    with _dev_report_lock:
        if now - _last_dev_report < min_interval_s:
            return
        _last_dev_report = now
    try:
        import jax
    except Exception:  # noqa: BLE001 — no jax in this process
        return
    try:
        devices = jax.local_devices()
    except Exception:  # noqa: BLE001 — backend init failure
        return
    live_by_dev: Dict[str, int] = {}
    try:
        for arr in jax.live_arrays():
            for d in getattr(arr, "devices", lambda: [])():
                key = f"{d.platform}:{d.id}"
                live_by_dev[key] = live_by_dev.get(key, 0) + 1
    except Exception:  # noqa: BLE001
        pass
    for d in devices:
        dev_label = f"{d.platform}:{d.id}"
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 — unsupported backend
            stats = None
        if stats:
            in_use = stats.get("bytes_in_use")
            if in_use is not None:
                telemetry.set_device_memory(dev_label, "in_use", float(in_use))
            peak = stats.get("peak_bytes_in_use")
            if peak is not None:
                telemetry.set_device_memory(dev_label, "peak", float(peak))
            limit = stats.get("bytes_limit")
            if limit is not None:
                telemetry.set_device_memory(dev_label, "limit", float(limit))
        if dev_label in live_by_dev:
            telemetry.set_device_live_buffers(dev_label, live_by_dev[dev_label])

"""Profiling & bottleneck-attribution plane: the on-demand sampling
profiler (attach / dump / merge / export), its lifecycle edges
(conflict, dies mid-capture, raylet kill), the <5% attached-overhead
guard, JAX/XLA introspection and dataplane counters (reference:
`ray timeline` + py-spy attach workflows).
"""

import json
import os
import signal
import sys
import threading
import time

import pytest

import ray_tpu
from ray_tpu._private import profiling as profiling_mod
from ray_tpu.util import state
from ray_tpu.util.profiling import ProfilerConflictError

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def cluster():
    ctx = ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield ctx
    ray_tpu.shutdown()


@ray_tpu.remote
class Burner:
    """CPU-bound workload whose frames the profiler must attribute."""

    def burn_workload(self, seconds: float) -> int:
        deadline = time.monotonic() + seconds
        acc = 0
        while time.monotonic() < deadline:
            acc += sum(i * i for i in range(500))
        return acc

    def timed_burn(self, iters: int) -> float:
        t0 = time.perf_counter()
        acc = 0
        for _ in range(iters):
            acc += sum(i * i for i in range(2000))
        return time.perf_counter() - t0

    def getpid(self) -> int:
        return os.getpid()


def _busy_thread(seconds: float) -> threading.Thread:
    def busy():
        deadline = time.monotonic() + seconds
        while time.monotonic() < deadline:
            sum(i * i for i in range(1000))

    t = threading.Thread(target=busy, daemon=True, name="busy-probe")
    t.start()
    return t


# ----------------------------------------------------------------------
# sampler core (in-process, no cluster)
# ----------------------------------------------------------------------
def test_sampler_captures_busy_thread_and_exports():
    _busy_thread(1.2)
    rep = profiling_mod.handle_profile_start(
        {"duration_s": 0.8, "hz": 100, "label": "local"}
    )
    time.sleep(0.9)
    rec = profiling_mod.handle_profile_dump({"session_id": rep["session_id"]})
    assert rec["sample_count"] > 0 and rec["ticks"] > 0
    collapsed = profiling_mod.collapse(rec)
    assert "busy" in collapsed
    # Every line is "stack count" with the label as root frame.
    for line in collapsed.strip().splitlines():
        stack, count = line.rsplit(" ", 1)
        assert stack.startswith("local;") and int(count) > 0
    ss = profiling_mod.speedscope([rec])
    json.dumps(ss)  # serializable
    prof = ss["profiles"][0]
    assert prof["type"] == "sampled" and len(prof["samples"]) == len(prof["weights"])
    assert all(
        i < len(ss["shared"]["frames"]) for s in prof["samples"] for i in s
    )


def test_sampler_cpu_mode_filters_idle_threads():
    """mode=cpu drops parked threads (per-thread CPU clocks): a sleeping
    thread contributes ~nothing, a spinning one dominates."""
    _busy_thread(2.0)
    rep = profiling_mod.handle_profile_start(
        {"duration_s": 1.2, "hz": 80, "mode": "cpu", "label": "cpu"}
    )
    time.sleep(1.3)
    rec = profiling_mod.handle_profile_dump({"session_id": rep["session_id"]})
    assert rec["sample_count"] > 0
    # The pytest main thread is parked in time.sleep during the whole
    # capture; with CPU filtering it must not dominate.
    busy = sum(c for s, c in rec["samples"].items() if "busy" in s)
    assert busy / rec["sample_count"] >= 0.5, rec["samples"]


def test_concurrent_attach_gets_typed_conflict_error():
    rep = profiling_mod.handle_profile_start({"duration_s": 5.0, "label": "first"})
    try:
        with pytest.raises(ProfilerConflictError) as err:
            profiling_mod.handle_profile_start({"duration_s": 1.0, "label": "second"})
        assert err.value.session_id == rep["session_id"]
    finally:
        profiling_mod.handle_profile_stop({"session_id": rep["session_id"]})
    # The stopped session frees the slot: a new attach succeeds (no leak).
    time.sleep(0.1)
    rep2 = profiling_mod.handle_profile_start({"duration_s": 0.2, "label": "third"})
    assert rep2["session_id"] != rep["session_id"]
    time.sleep(0.3)


def test_dump_after_natural_end_returns_cached_record():
    rep = profiling_mod.handle_profile_start({"duration_s": 0.2, "hz": 50, "label": "x"})
    time.sleep(0.5)  # capture ended on its own
    rec = profiling_mod.handle_profile_dump({"session_id": rep["session_id"]})
    assert rec["running"] is False
    assert rec["session_id"] == rep["session_id"]


def test_merge_records_keys_cluster_profile_by_label():
    a = {"label": "actor:tenantA/Foo", "samples": {"f1;f2": 3}, "sample_count": 3}
    b = {"label": "raylet:abcd1234", "samples": {"f1;f2": 2, "g": 1}, "sample_count": 3}
    merged = profiling_mod.merge_records([a, b])
    assert merged["actor:tenantA/Foo;f1;f2"] == 3
    assert merged["raylet:abcd1234;f1;f2"] == 2
    assert merged["raylet:abcd1234;g"] == 1


# ----------------------------------------------------------------------
# orchestrated capture on a live cluster (the acceptance criterion)
# ----------------------------------------------------------------------
def test_profile_live_actor_attributes_workload(cluster):
    """util.state.profile() on a live actor under load: the merged
    profile's top frames attribute >=80% of samples to the actor's
    actual workload, exported as both collapsed-stack and speedscope."""
    actor = Burner.remote()
    ray_tpu.get(actor.burn_workload.remote(0.01), timeout=60)  # actor up
    ref = actor.burn_workload.remote(8.0)

    result = state.profile(actor, duration_s=2.0, mode="cpu")
    assert result.errors == []
    assert result.total_samples > 0
    attribution = result.attribution("burn_workload")
    assert attribution >= 0.8, (
        f"only {attribution:.0%} of samples in the workload; "
        f"top: {result.top_frames(8)}"
    )
    collapsed = result.collapsed()
    assert collapsed.startswith("actor:") and "burn_workload" in collapsed
    ss = result.speedscope()
    assert ss["profiles"] and ss["profiles"][0]["samples"]
    json.dumps(ss)
    ray_tpu.get(ref, timeout=60)


def test_profile_ships_record_to_gcs_table(cluster):
    """End-of-capture records land in the GCS profile table
    (state.profiles) via the report channel — capture outlives driver."""
    actor = Burner.remote()
    ray_tpu.get(actor.burn_workload.remote(0.01), timeout=60)
    ref = actor.burn_workload.remote(3.0)
    result = state.profile(actor, duration_s=1.0)
    assert result.profiles, result.errors
    sid = result.profiles[0]["session_id"]
    deadline = time.monotonic() + 15
    shipped = []
    while time.monotonic() < deadline and not shipped:
        shipped = state.profiles(session_id=sid)
        # graftlint: disable=retry-gate -- deadline-bounded assertion poll; 0.3 s is the scan resolution, not a retry delay
        time.sleep(0.3)
    assert shipped and shipped[0]["session_id"] == sid
    ray_tpu.get(ref, timeout=60)


@pytest.mark.slow  # ~38 s kill drill: runs under `-m chaos`
@pytest.mark.chaos
def test_profiled_worker_dies_mid_capture_partial_no_leak(cluster):
    """SIGKILL the profiled worker mid-capture: the orchestration
    returns a partial result with an errors entry (no exception), and
    the next capture works — nothing leaks client-side."""
    victim = Burner.remote()
    pid = ray_tpu.get(victim.getpid.remote(), timeout=60)
    victim.burn_workload.remote(20.0)

    from ray_tpu.util import profiling as up

    gcs_call = state._gcs().call
    targets = up.resolve_targets(victim, gcs_call)

    killer = threading.Timer(1.0, lambda: os.kill(pid, signal.SIGKILL))
    killer.start()
    result = up.run_profile(
        targets, gcs_call, state._node_call, duration_s=3.0
    )
    killer.join()
    # The dump hit a dead socket: an errors entry, not an exception
    # (unless the end-of-capture ship beat the kill, which yields a
    # recovered record instead).
    assert result.errors or result.profiles

    # The plane still works for a fresh target afterwards.
    survivor = Burner.remote()
    ray_tpu.get(survivor.burn_workload.remote(0.01), timeout=60)
    ref = survivor.burn_workload.remote(4.0)
    again = state.profile(survivor, duration_s=1.0)
    assert again.profiles and again.total_samples > 0
    ray_tpu.get(ref, timeout=60)


def test_dashboard_profile_endpoint(cluster):
    """/api/profile drives the same orchestration with the dashboard's
    own clients (no connected driver) in all three formats."""
    from urllib import request as urlrequest

    url = cluster.dashboard_url
    if not url:
        pytest.skip("no dashboard in this session")
    actor = Burner.remote()
    ray_tpu.get(actor.burn_workload.remote(0.01), timeout=60)
    ref = actor.burn_workload.remote(6.0)
    aid = actor._actor_id.hex()
    with urlrequest.urlopen(
        f"{url}/api/profile?target={aid}&duration_s=1", timeout=30
    ) as r:
        body = json.loads(r.read())
    assert body["total_samples"] > 0 and not body["errors"]
    assert body["collapsed"].startswith("actor:")
    with urlrequest.urlopen(
        f"{url}/api/profile?target={aid}&duration_s=0.5&format=collapsed", timeout=30
    ) as r:
        assert b"burn_workload" in r.read()
    with urlrequest.urlopen(f"{url}/api/profiles", timeout=10) as r:
        assert isinstance(json.loads(r.read()), list)
    ray_tpu.get(ref, timeout=60)


# ----------------------------------------------------------------------
# overhead guard (the PR 2 <5% budget, extended to the attached profiler)
# ----------------------------------------------------------------------
def test_profiler_overhead_budget(cluster):
    """An actor workload with the profiler attached at the default Hz
    must run <5% slower than detached.  Wall-clock comparisons on the
    shared CI box swing with host load, so each condition takes the
    MINIMUM of several runs (the classic noise floor estimator) and the
    workload is timed inside the actor process."""
    actor = Burner.remote()
    iters = 150
    ray_tpu.get(actor.timed_burn.remote(iters), timeout=60)  # warm

    def best_of(n):
        return min(
            ray_tpu.get(actor.timed_burn.remote(iters), timeout=60) for _ in range(n)
        )

    base = best_of(4)
    # Attach at the default Hz for the whole measured window.
    info = state._gcs().call("get_actor_info", actor._actor_id.binary())
    start = state._node_call(
        info["worker_address"], "profile_start",
        {"duration_s": 60.0, "label": "overhead"},
    )
    try:
        attached = best_of(4)
    finally:
        state._node_call(
            info["worker_address"], "profile_dump",
            {"session_id": start["session_id"], "stop": True},
        )
    overhead = (attached - base) / base
    assert overhead < 0.05, (
        f"attached profiler overhead {overhead:.1%} >= 5% "
        f"(base {base * 1e3:.1f}ms, attached {attached * 1e3:.1f}ms)"
    )


def test_profiler_detached_zero_cost():
    """Detached = zero cost: no sampler thread survives a capture, no
    interpreter-level profile/trace hook is ever installed, and the
    execution path carries no per-call hooks (attach is a pure RPC
    surface)."""
    rep = profiling_mod.handle_profile_start({"duration_s": 0.2, "hz": 50, "label": "z"})
    time.sleep(0.5)
    rec = profiling_mod.handle_profile_dump({"session_id": rep["session_id"]})
    assert rec["running"] is False
    time.sleep(0.2)
    assert profiling_mod.active_session_id() is None
    assert not any(
        t.name.startswith("profile-sampler") and t.is_alive()
        for t in threading.enumerate()
    )
    assert sys.getprofile() is None and sys.gettrace() is None


# ----------------------------------------------------------------------
# JAX/XLA introspection
# ----------------------------------------------------------------------
def test_instrument_jit_counts_compiles_and_retraces():
    jax = pytest.importorskip("jax")
    jnp = jax.numpy

    f = profiling_mod.instrument_jit("probe_fn", jax.jit(lambda x: x * 3))
    f(jnp.ones((4,)))
    f(jnp.ones((4,)))  # cached: no compile
    f(jnp.ones((16,)))  # new shape: retrace
    rec = profiling_mod.jit_stats("probe_fn")
    assert rec["compiles"] == 2
    assert rec["retraces"] == 1
    assert rec["compile_seconds"] > 0


def test_instrument_jit_kill_switch_returns_unwrapped():
    jax = pytest.importorskip("jax")
    from ray_tpu._private.config import CONFIG

    CONFIG._overrides["jax_introspection"] = False
    try:
        jfn = jax.jit(lambda x: x + 1)
        # nothing wraps the call and nothing is asked to listen: the
        # ledger costs a call nothing, compiling or not
        assert profiling_mod.instrument_jit("killed", jfn) is jfn
        assert profiling_mod.listen_for_compiles() is False
        jfn(jax.numpy.ones((3,)))
        assert profiling_mod.jit_stats("killed") == {}
    finally:
        CONFIG._overrides.pop("jax_introspection", None)


def _ledger(rec):
    return {k: rec[k] for k in profiling_mod.LEDGER_KEYS}


def test_compile_ledger_splits_a_first_call_and_books_nothing_on_the_second():
    """A first call is one trace, one lowering and one backend call with
    seconds of each, under the function's name and in the process's
    totals; a second call books nothing; a new shape is a retrace with a
    split of its own, which its ``jax.compile`` span carries."""
    jax = pytest.importorskip("jax")
    jnp = jax.numpy
    from ray_tpu.util import tracing

    # jnp.sin is a jit of its own, traced inside f's trace: one trace all the same
    f = profiling_mod.instrument_jit("ledger_probe", jax.jit(lambda x: jnp.sin(x) * 3))
    small, large = jnp.ones((4,)), jnp.ones((16,))  # made before anything is read: they compile too
    tracing.drain_spans()
    totals = profiling_mod.compile_totals()
    f(small)
    first = profiling_mod.jit_stats("ledger_probe")
    assert (first["traces"], first["lowerings"], first["backend_calls"]) == (1, 1, 1)
    assert first["trace_s"] > 0 and first["lower_s"] > 0
    assert first["backend_hit_s"] + first["backend_miss_s"] > 0
    # the old three keys read as before, and first_call_s is compile_seconds
    assert (first["compiles"], first["retraces"]) == (1, 0)
    assert first["first_call_s"] == first["compile_seconds"] > first["trace_s"] + first["lower_s"]
    after = profiling_mod.compile_totals()
    assert {k: after[k] - totals[k] for k in after} == pytest.approx(_ledger(first))

    f(small)
    assert profiling_mod.jit_stats("ledger_probe") == first
    assert profiling_mod.compile_totals() == after

    f(large)
    second = profiling_mod.jit_stats("ledger_probe")
    assert (second["compiles"], second["retraces"]) == (2, 1)
    assert (second["traces"], second["lowerings"], second["backend_calls"]) == (2, 2, 2)
    spans = [sp["attributes"] for sp in tracing.drain_spans()
             if sp["name"] == "jax.compile" and sp["attributes"]["function"] == "ledger_probe"]
    assert [a["retrace"] for a in spans] == [False, True]
    for a, before, now in zip(spans, (dict.fromkeys(first, 0), first), (first, second)):
        assert a["trace_s"] == pytest.approx(now["trace_s"] - before["trace_s"]) and a["trace_s"] > 0
        assert a["lower_s"] == pytest.approx(now["lower_s"] - before["lower_s"]) and a["lower_s"] > 0
        assert a["backend_s"] > 0 and a["cache_hit"] == (now["cache_hits"] > before["cache_hits"])


def test_compile_ledger_books_under_the_name_open_in_the_calling_thread():
    """Two instrumented jits compiling at once, each in a thread of its
    own: each program lands under its own name."""
    jax = pytest.importorskip("jax")
    jnp = jax.numpy
    tracing_a, release = threading.Event(), threading.Event()

    def held(x):  # runs while it is traced: holds thread A inside its compile
        tracing_a.set()
        assert release.wait(30)
        return x - 1

    fa = profiling_mod.instrument_jit("ledger_thread_a", jax.jit(held))
    fb = profiling_mod.instrument_jit("ledger_thread_b", jax.jit(lambda x: x * 7))
    x = jnp.ones((5,))
    a = threading.Thread(target=fa, args=(x,), daemon=True)
    a.start()
    try:
        assert tracing_a.wait(30)
        fb(x)  # compiles whole while A is in the middle of its trace
        mid = profiling_mod.jit_stats("ledger_thread_a")
        assert (mid["traces"], mid["lowerings"]) == (0, 0)
    finally:
        release.set()
    a.join(30)
    assert not a.is_alive()
    for name in ("ledger_thread_a", "ledger_thread_b"):
        rec = profiling_mod.jit_stats(name)
        assert (rec["compiles"], rec["traces"], rec["lowerings"], rec["backend_calls"]) == (1, 1, 1, 1)


_CACHE_SCRIPT = """
import json, jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from ray_tpu._private import profiling
f = profiling.instrument_jit("cached_probe", jax.jit(lambda x: jnp.cos(x) * 5))
x = jnp.ones((8,))
f(x)
first = profiling.jit_stats("cached_probe")
jax.clear_caches()
f(x)
print("LEDGER=" + json.dumps([first, profiling.jit_stats("cached_probe")]))
"""


def test_compile_ledger_tells_a_cache_load_from_a_compile(tmp_path):
    """With the persistent cache on: a process's first compile of a
    program is a miss, booked under ``backend_miss_s``; the same program
    after ``jax.clear_caches()`` is read back, booked under
    ``backend_hit_s`` with the cache's own read time."""
    pytest.importorskip("jax")
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    out = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT], env=env, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    first, second = json.loads(next(
        line for line in out.stdout.splitlines() if line.startswith("LEDGER="))[len("LEDGER="):])
    if second["cache_hits"] == 0:
        pytest.skip("the CPU backend of this JAX does not read its persistent cache back")
    assert (first["cache_hits"], first["cache_misses"], first["backend_calls"]) == (0, 1, 1)
    assert first["backend_miss_s"] > 0 and first["backend_hit_s"] == 0 and first["cache_read_s"] == 0
    assert (second["cache_hits"], second["cache_misses"], second["backend_calls"]) == (1, 1, 2)
    assert second["backend_miss_s"] == first["backend_miss_s"]
    assert second["backend_hit_s"] >= second["cache_read_s"] > 0


def test_report_device_memory_cpu_safe():
    pytest.importorskip("jax")
    # Must be a no-op (no exception) on backends without memory_stats.
    profiling_mod.report_device_memory(min_interval_s=0.0)


# ----------------------------------------------------------------------
# dataplane counters
# ----------------------------------------------------------------------
def test_channel_counters_and_occupancy(tmp_path):
    from ray_tpu.experimental.channel import Channel, ChannelTimeout

    path = str(tmp_path / "chan")
    Channel.create_file(path, 1 << 16)
    w = Channel(path)
    r = Channel(path)
    assert w.pending() is False
    w.write(b"x" * 100)
    assert w.pending() is True  # published, not yet acked
    assert r.read() == b"x" * 100
    assert w.pending() is False
    assert w.stats["writes"] == 1 and w.stats["bytes_written"] == 100
    assert r.stats["reads"] == 1 and r.stats["bytes_read"] == 100
    # A read with nothing published blocks, then times out -> counted.
    with pytest.raises(ChannelTimeout):
        r.read(timeout=0.1)
    assert r.stats["read_timeouts"] == 1
    assert r.stats["read_blocked_s"] > 0
    w.close()
    r.close()


def test_compiled_dag_stats_expose_dataplane(cluster):
    @ray_tpu.remote
    class Doubler:
        def double(self, x):
            return x * 2

    from ray_tpu.dag import InputNode

    with InputNode() as inp:
        node = Doubler.bind().double.bind(inp)
    dag = node.experimental_compile()
    try:
        for i in range(5):
            assert ray_tpu.get(dag.execute(i)) == i * 2
        s = dag.stats()
        assert s["compiled"] is True
        assert s["executions"] == 5 and s["inflight"] == 0
        assert s["input_channels"][0]["writes"] == 5
        assert s["output_channels"][0]["reads"] == 5
    finally:
        dag.teardown()


# ----------------------------------------------------------------------
# attach edges
# ----------------------------------------------------------------------
def test_profile_foreign_session_is_error_not_shared(cluster):
    """A conflict with a session some OTHER operator started must
    surface as an error (the target's samples are missing from this
    result), not as a benign co-hosted 'shared' note."""
    actor = Burner.remote()
    ray_tpu.get(actor.burn_workload.remote(0.01), timeout=60)
    info = state._gcs().call("get_actor_info", actor._actor_id.binary())
    foreign = state._node_call(
        info["worker_address"], "profile_start",
        {"duration_s": 30.0, "label": "operator-A"},
    )
    try:
        result = state.profile(actor, duration_s=0.5)
        assert result.shared == []
        assert result.errors and "busy" in result.errors[0]["error"]
        assert foreign["session_id"] in result.errors[0]["error"]
    finally:
        state._node_call(
            info["worker_address"], "profile_stop",
            {"session_id": foreign["session_id"]},
        )


def test_resolve_targets_rejects_unknown_types():
    """A wrong-typed target must raise, not silently widen to a
    cluster-wide capture."""
    from ray_tpu.util import profiling as up

    def must_not_call(method, payload, *a):
        raise AssertionError(f"gcs_call reached for bad target: {method}")

    with pytest.raises(ValueError):
        up.resolve_targets(123, must_not_call)
    with pytest.raises(ValueError):
        up.resolve_targets(b"\x01\x02", must_not_call)


# ----------------------------------------------------------------------
# chaos drill: capture survives its raylet dying
# ----------------------------------------------------------------------
@pytest.mark.slow  # ~39 s raylet-kill drill: runs under `-m chaos`
@pytest.mark.chaos
def test_profile_worker_through_raylet_kill():
    """SIGKILL the raylet of the node hosting the profiled actor while
    a capture is running.  The worker's direct RPC endpoint is
    independent of the raylet, so the attach either rides out the kill
    (dump succeeds with workload samples) or degrades to the partial
    path (errors entry) — never an exception, and the plane stays
    usable on the surviving node."""
    from ray_tpu.cluster_utils import Cluster

    ray_tpu.shutdown()  # the module fixture's single-node session
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    node = c.add_node(num_cpus=1, resources={"side": 1})
    c.wait_for_nodes()
    ray_tpu.init(address=c.address)
    try:
        @ray_tpu.remote(resources={"side": 0.5})
        class SideBurner:
            def burn_workload(self, seconds):
                deadline = time.monotonic() + seconds
                acc = 0
                while time.monotonic() < deadline:
                    acc += sum(i * i for i in range(500))
                return acc

        actor = SideBurner.remote()
        ray_tpu.get(actor.burn_workload.remote(0.01), timeout=60)
        actor.burn_workload.remote(20.0)

        from ray_tpu.util import profiling as up

        gcs_call = state._gcs().call
        targets = up.resolve_targets(actor, gcs_call)
        killer = threading.Timer(0.8, lambda: c.remove_node(node))
        killer.start()
        result = up.run_profile(
            targets, gcs_call, state._node_call, duration_s=2.5
        )
        killer.join()
        assert result.profiles or result.errors
        if result.profiles:
            # The worker outlived its raylet: samples attribute to the
            # workload as usual.
            assert result.total_samples > 0
            assert "burn_workload" in result.collapsed()

        # Plane still works on the head node afterwards.
        head_actor = Burner.remote()
        ray_tpu.get(head_actor.burn_workload.remote(0.01), timeout=60)
        ref = head_actor.burn_workload.remote(4.0)
        again = state.profile(head_actor, duration_s=1.0)
        assert again.profiles and again.total_samples > 0
        ray_tpu.get(ref, timeout=60)
    finally:
        ray_tpu.shutdown()
        c.shutdown()

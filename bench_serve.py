"""LLM serving-plane benchmark -> BENCH_serve.json.

Four phases against the tiny GPT-2 config (synthetic weights; the
numbers measure the SERVING plane — engine scheduling, streaming
transport, overload behavior — not model quality):

1. **throughput comparison** — continuous in-flight batching
   (``LLMServer``) vs the request-level ``@serve.batch`` baseline
   (``StaticBatchLLMServer``) at equal concurrency and equal decode
   width, mixed request lengths.  Continuous must win on tokens/s: the
   static batch pays the drain barrier (every batch runs to its LAST
   member while short members' lanes idle).
2. **stream drill** — 4k concurrent token streams through one
   deployment (stepping toward the 10k target): p50/p99 end-to-end
   latency, p50/p99 TTFT, aggregate tokens/s, all streams complete.
   Records stamp the stream count so bench_gate --compare refuses to
   score a resized drill against an older, smaller one.
3. **shed** — flood a small-queue deployment far past its bound: the
   overflow is shed with typed errors (engine) while every admitted
   request completes; records the shed rate.
4. **chaos** — 2 replicas under live stream load, one replica killed:
   every established stream on the survivor completes, new requests
   re-route, the controller replaces the dead replica.

Hardware caveats: same 1-core CI box as BENCH_micro — the transport
(per-token stream items through the object store) dominates over the
tiny model's decode math, and loadavg swings absolute numbers; every
record carries the loadavg annotation.

Run: python bench_serve.py [--out BENCH_serve.json] [--streams 4096]
"""

from __future__ import annotations

import argparse
import json
import os
import threading
import time

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve import llm
from ray_tpu.serve.exceptions import RequestShedError

NOTE = (
    "tiny GPT-2, synthetic weights, CPU backend on the 1-core CI box: "
    "serving-plane numbers (scheduling + streaming transport), not model "
    "math; host contention swings absolutes run-to-run"
)


def _pct(sorted_vals, p):
    if not sorted_vals:
        return None
    i = min(len(sorted_vals) - 1, int(p / 100.0 * len(sorted_vals)))
    return sorted_vals[i]


def record(out, metric, value, unit, **extra):
    rec = {
        "metric": metric,
        "value": round(value, 2) if isinstance(value, float) else value,
        "unit": unit,
        "loadavg_1m_at_capture": round(os.getloadavg()[0], 2),
        "note": NOTE,
    }
    rec.update(extra)
    out[metric] = rec
    print(json.dumps(rec))


# ----------------------------------------------------------------------
# phase 1: continuous vs static batching, equal concurrency
# ----------------------------------------------------------------------
def _drive_oneshot(handle, n_requests, concurrency, mixed_lengths):
    """n_requests one-shot completions, `concurrency` in flight, mixed
    max_tokens; returns (wall_s, total_tokens, latencies)."""
    lock = threading.Lock()
    state = {"next": 0, "tokens": 0, "lat": [], "errors": 0}

    def worker():
        while True:
            with lock:
                i = state["next"]
                if i >= n_requests:
                    return
                state["next"] = i + 1
            t0 = time.time()
            try:
                out = handle.remote(
                    {"prompt": [1, 2, 3, i % 7], "max_tokens": mixed_lengths[i]}
                ).result(timeout=300)
                dt = time.time() - t0
                with lock:
                    state["tokens"] += out["num_tokens"]
                    state["lat"].append(dt)
            except Exception:  # noqa: BLE001
                with lock:
                    state["errors"] += 1

    t0 = time.time()
    threads = [threading.Thread(target=worker) for _ in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return time.time() - t0, state["tokens"], sorted(state["lat"]), state["errors"]


def phase_throughput(out, n_requests=192, concurrency=48, width=16):
    mixed = [4 + (i * 7) % 28 for i in range(n_requests)]  # 4..31 tokens

    cont_app = llm.build_app(
        llm.LLMConfig(model="tiny", max_batch_size=width, num_blocks=512,
                      block_size=8, max_queue=4096, name="bench_cont")
    )
    handle = serve.run(cont_app, name="bench_cont_app")
    # warm the compile caches out of the measurement
    handle.remote({"prompt": [1], "max_tokens": 4}).result(timeout=120)
    wall, tokens, lat, errors = _drive_oneshot(handle, n_requests, concurrency, mixed)
    assert errors == 0, f"{errors} continuous requests failed"
    st = handle.stats.remote().result(timeout=30)
    assert st["kv_blocks_in_use"] == 0, st["kv_leak_report"]
    cont_tps = tokens / wall
    record(out, "serve_tokens_per_s_continuous", cont_tps, "tokens/s",
           requests=n_requests, concurrency=concurrency, batch_width=width,
           wall_s=round(wall, 2), engine_steps=st["steps"])
    serve.delete("bench_cont")

    static_dep = serve.deployment(
        name="bench_static", max_ongoing_requests=4096
    )(llm.StaticBatchLLMServer)
    s_handle = serve.run(
        static_dep.bind(
            llm.LLMConfig(model="tiny", max_batch_size=width,
                          name="bench_static").to_dict()
        ),
        name="bench_static_app",
    )
    s_handle.remote({"prompt": [1], "max_tokens": 4}).result(timeout=120)
    wall_s_, tokens_s_, lat_s, errors_s = _drive_oneshot(
        s_handle, n_requests, concurrency, mixed
    )
    assert errors_s == 0, f"{errors_s} static requests failed"
    static_tps = tokens_s_ / wall_s_
    record(out, "serve_tokens_per_s_static_batch", static_tps, "tokens/s",
           requests=n_requests, concurrency=concurrency, batch_width=width,
           wall_s=round(wall_s_, 2))
    record(out, "serve_continuous_vs_static_speedup", cont_tps / static_tps,
           "x", acceptance="continuous must beat static at equal concurrency")
    serve.delete("bench_static")
    return cont_tps, static_tps


# ----------------------------------------------------------------------
# phase 2: 4k concurrent stream drill (toward the 10k target)
# ----------------------------------------------------------------------
def phase_stream_drill(out, n_streams=4096, max_tokens=12, width=32):
    app = llm.build_app(
        llm.LLMConfig(model="tiny", max_batch_size=width, num_blocks=1024,
                      block_size=8, max_queue=n_streams + 64,
                      name="bench_drill"),
        max_ongoing_requests=2 * n_streams,
    )
    handle = serve.run(app, name="bench_drill_app")
    handle.remote({"prompt": [1], "max_tokens": 4}).result(timeout=120)

    t_start = time.time()
    streams = []
    stream_handle = handle.options(stream=True)
    for i in range(n_streams):
        gen = stream_handle.generate.remote(
            {"prompt": [1, 2, i % 11], "max_tokens": max_tokens}
        )
        streams.append({
            "gen": gen, "t_open": time.time(), "t_first": None,
            "t_done": None, "tokens": 0, "failed": False,
        })
    t_opened = time.time()

    open_set = list(streams)
    deadline = time.time() + 600
    while open_set and time.time() < deadline:
        for s in list(open_set):
            try:
                ev = s["gen"].try_next()
            except StopIteration:
                s["t_done"] = s["t_done"] or time.time()
                open_set.remove(s)
                continue
            except Exception:  # noqa: BLE001
                s["failed"] = True
                open_set.remove(s)
                continue
            if ev is None:
                continue
            if isinstance(ev, dict) and "token" in ev:
                s["tokens"] += 1
                if s["t_first"] is None:
                    s["t_first"] = time.time()
    t_end = time.time()

    failed = [s for s in streams if s["failed"] or s["t_done"] is None]
    done = [s for s in streams if s["t_done"] is not None and not s["failed"]]
    assert len(failed) == 0, f"{len(failed)} of {n_streams} streams failed"
    total_tokens = sum(s["tokens"] for s in done)
    lat = sorted(s["t_done"] - s["t_open"] for s in done)
    ttft = sorted(s["t_first"] - s["t_open"] for s in done if s["t_first"])
    wall = t_end - t_start
    # workload provenance: `streams` on every drill record lets
    # bench_gate --compare refuse latency comparisons across drill
    # resizes (a 4x-larger drill is a workload change, not a perf one)
    record(out, "serve_stream_drill_streams", len(done), "streams",
           requested=n_streams, open_time_s=round(t_opened - t_start, 2))
    record(out, "serve_stream_drill_tokens_per_s", total_tokens / wall,
           "tokens/s", total_tokens=total_tokens, wall_s=round(wall, 2),
           streams=n_streams)
    record(out, "serve_stream_drill_latency_p50", _pct(lat, 50), "s",
           streams=n_streams)
    record(out, "serve_stream_drill_latency_p99", _pct(lat, 99), "s",
           streams=n_streams)
    record(out, "serve_stream_drill_ttft_p50", _pct(ttft, 50), "s",
           streams=n_streams)
    record(out, "serve_stream_drill_ttft_p99", _pct(ttft, 99), "s",
           streams=n_streams)
    st = handle.stats.remote().result(timeout=30)
    assert st["kv_blocks_in_use"] == 0, st["kv_leak_report"]
    record(out, "serve_stream_drill_kv_blocks_after", st["kv_blocks_in_use"],
           "blocks", acceptance="zero KV-block leak after the drill")
    serve.delete("bench_drill")


# ----------------------------------------------------------------------
# phase 2b: router→replica channel dataplane A/B (ROADMAP item 1 wiring:
# per-token stream items through the object store were the bottleneck —
# route token streaming over compiled-DAG channels, record before/after)
# ----------------------------------------------------------------------
def _run_stream_batch(handle, n_streams, max_tokens):
    stream_handle = handle.options(stream=True)
    t_start = time.time()
    streams = []
    for i in range(n_streams):
        gen = stream_handle.generate.remote(
            {"prompt": [1, 2, i % 11], "max_tokens": max_tokens}
        )
        streams.append({"gen": gen, "tokens": 0, "done": False})
    open_set = list(streams)
    deadline = time.time() + 300
    while open_set and time.time() < deadline:
        for s in list(open_set):
            try:
                ev = s["gen"].try_next()
            except StopIteration:
                s["done"] = True
                open_set.remove(s)
                continue
            except Exception:  # noqa: BLE001
                open_set.remove(s)
                continue
            if ev is not None and isinstance(ev, dict) and "token" in ev:
                s["tokens"] += 1
    wall = time.time() - t_start
    assert all(s["done"] for s in streams), "streams failed in A/B phase"
    return sum(s["tokens"] for s in streams), wall


def phase_dataplane_ab(out, n_streams=192, max_tokens=16, width=16):
    """The same token-stream workload over both transports: per-token
    object-store items (RPC path, dataplane off) vs multiplexed channel
    frames (dataplane on).  Fresh app per arm so neither inherits the
    other's attach state."""
    from ray_tpu._private.config import CONFIG
    from ray_tpu.serve._private.router import _routers

    results = {}
    for arm, enabled in (("rpc", False), ("dataplane", True)):
        CONFIG._overrides["serve_channel_dataplane"] = enabled
        app = llm.build_app(
            llm.LLMConfig(model="tiny", max_batch_size=width, num_blocks=512,
                          block_size=8, max_queue=n_streams + 64,
                          name=f"bench_ab_{arm}"),
            max_ongoing_requests=2 * n_streams,
        )
        handle = serve.run(app, name=f"bench_ab_{arm}_app")
        handle.remote({"prompt": [1], "max_tokens": 4}).result(timeout=120)
        tokens, wall = _run_stream_batch(handle, n_streams, max_tokens)
        if enabled:
            router = _routers.get(handle.deployment_name)
            engaged = bool(
                router
                and any(
                    getattr(v, "replica_id", None) is not None
                    for v in router._dataplanes.values()
                )
            )
            assert engaged, "dataplane arm did not attach channel clients"
        results[arm] = tokens / wall
        record(out, f"serve_stream_tokens_per_s_{arm}", tokens / wall,
               "tokens/s", streams=n_streams, max_tokens=max_tokens)
        serve.delete(f"bench_ab_{arm}")
    CONFIG._overrides["serve_channel_dataplane"] = True
    record(out, "serve_stream_dataplane_speedup",
           results["dataplane"] / results["rpc"], "x",
           acceptance="token streaming over compiled channels vs object-store hops")
    return results


# ----------------------------------------------------------------------
# phase 3: shed rate far past the bound
# ----------------------------------------------------------------------
def phase_shed(out, n_requests=256, max_queue=48):
    app = llm.build_app(
        llm.LLMConfig(model="tiny", max_batch_size=8, num_blocks=256,
                      block_size=8, max_queue=max_queue, name="bench_shed"),
        max_ongoing_requests=2 * n_requests,
    )
    handle = serve.run(app, name="bench_shed_app")
    handle.remote({"prompt": [1], "max_tokens": 4}).result(timeout=120)
    responses = [
        handle.remote({"prompt": [i % 5], "max_tokens": 12})
        for i in range(n_requests)
    ]
    shed = completed = 0
    for r in responses:
        try:
            r.result(timeout=300)
            completed += 1
        except RequestShedError:
            shed += 1
    assert shed + completed == n_requests
    assert shed > 0, "flood never shed — the bound is not enforced"
    assert completed >= max_queue, "admitted requests must complete"
    record(out, "serve_shed_rate", shed / n_requests, "fraction",
           flood=n_requests, queue_bound=max_queue, shed=shed,
           completed=completed,
           acceptance="overflow sheds typed + retryable; admitted work completes")
    st = handle.stats.remote().result(timeout=30)
    assert st["kv_blocks_in_use"] == 0, st["kv_leak_report"]
    serve.delete("bench_shed")


# ----------------------------------------------------------------------
# phase 4: chaos — replica kill mid-load
# ----------------------------------------------------------------------
def phase_chaos(out, n_streams=128, max_tokens=60):
    from ray_tpu.serve._private.controller import CONTROLLER_NAME

    app = llm.build_app(
        llm.LLMConfig(model="tiny", max_batch_size=8, num_blocks=512,
                      block_size=8, max_queue=4 * n_streams,
                      name="bench_chaos"),
        num_replicas=2,
        max_ongoing_requests=4 * n_streams,
    )
    handle = serve.run(app, name="bench_chaos_app")
    controller = ray_tpu.get_actor(CONTROLLER_NAME, "serve")
    reps = ray_tpu.get(controller.get_replicas.remote("bench_chaos"))
    assert len(reps) == 2
    actors = {r["replica_id"]: ray_tpu.get_actor(r["actor_name"], "serve")
              for r in reps}

    streams = []
    stream_handle = handle.options(stream=True)
    for i in range(n_streams):
        gen = stream_handle.generate.remote(
            {"prompt": [2, 3, i % 5], "max_tokens": max_tokens}
        )
        streams.append({"gen": gen, "established": False, "tokens": 0,
                        "failed": False, "done": False})
    # establish: every stream has a first token
    open_set = list(streams)
    deadline = time.time() + 120
    while time.time() < deadline and any(not s["established"] for s in streams):
        for s in streams:
            if s["established"] or s["failed"]:
                continue
            try:
                ev = s["gen"].try_next()
            except StopIteration:
                s["done"] = s["established"] = True
                continue
            except Exception:  # noqa: BLE001
                s["failed"] = True
                continue
            if isinstance(ev, dict) and "token" in ev:
                s["tokens"] += 1
                s["established"] = True
    established = [s for s in streams if s["established"] and not s["done"]]
    counts = {rid: ray_tpu.get(a.stats.remote()).get("total", 0)
              for rid, a in actors.items()}
    victim = max(counts, key=counts.get)
    t_kill = time.time()
    ray_tpu.kill(actors[victim])

    open_set = [s for s in established if not s["done"]]
    deadline = time.time() + 300
    while open_set and time.time() < deadline:
        for s in list(open_set):
            try:
                ev = s["gen"].try_next()
            except StopIteration:
                s["done"] = True
                open_set.remove(s)
                continue
            except Exception:  # noqa: BLE001
                s["failed"] = True
                open_set.remove(s)
                continue
            if isinstance(ev, dict) and "token" in ev:
                s["tokens"] += 1
    survivors_done = sum(1 for s in established if s["done"])
    victim_failed = sum(1 for s in established if s["failed"])
    stuck = sum(1 for s in established if not s["done"] and not s["failed"])
    assert stuck == 0, f"{stuck} streams neither finished nor failed"
    # acceptance: zero failed established streams on SURVIVING replicas —
    # every failure must be attributable to the killed replica's share
    assert victim_failed < len(established), "every stream failed — survivor hit too"
    assert survivors_done > 0, "no established stream survived the kill"

    # new requests re-route (router evicts on observed death)
    t0 = time.time()
    ok = False
    while time.time() - t0 < 60:
        try:
            handle.remote({"prompt": [9], "max_tokens": 4}).result(timeout=60)
            ok = True
            break
        except Exception:  # noqa: BLE001
            time.sleep(0.2)
    assert ok, "re-route never converged after the kill"
    reroute_s = time.time() - t_kill

    # controller replaces the dead replica
    deadline = time.time() + 120
    while time.time() < deadline:
        reps = ray_tpu.get(controller.get_replicas.remote("bench_chaos"))
        if len(reps) == 2 and all(r["replica_id"] != victim for r in reps):
            break
        time.sleep(0.5)
    assert len(reps) == 2, "dead replica never replaced"
    record(out, "serve_chaos_survivor_streams_completed", survivors_done,
           "streams", established=len(established),
           failed_on_victim=victim_failed,
           recovery_s=round(time.time() - t_kill, 2),
           reroute_s=round(reroute_s, 2),
           acceptance="zero failed established streams on surviving replicas")
    serve.delete("bench_chaos")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--streams", type=int, default=4096)
    ap.add_argument("--skip-chaos", action="store_true")
    args = ap.parse_args()

    ray_tpu.init(num_cpus=4)
    out = {}
    try:
        cont, static = phase_throughput(out)
        phase_stream_drill(out, n_streams=args.streams)
        phase_dataplane_ab(out)
        phase_shed(out)
        if not args.skip_chaos:
            phase_chaos(out)
        assert cont > static, (
            f"continuous batching ({cont:.0f} tok/s) did not beat the static "
            f"@serve.batch baseline ({static:.0f} tok/s)"
        )
    finally:
        try:
            serve.shutdown()
        except Exception:  # noqa: BLE001
            pass
        ray_tpu.shutdown()
    # Platform provenance is stamped here, once the replicas that needed
    # the chip are gone: asking JAX earlier would take the chip from
    # them.  bench_gate refuses cross-platform comparisons keyed on it.
    from bench_common import provenance

    for rec in out.values():
        rec.update(provenance())
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {args.out} ({len(out)} records)")


if __name__ == "__main__":
    main()

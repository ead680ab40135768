#!/usr/bin/env python
"""benchmark/run.py: one cell, once, in a new process.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Load, warm up, measure for ``--seconds``, shut the cluster down, print
one JSON object as the last line of stdout: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, in a traced run, ``breakdown``.
With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics.  Everything else worth keeping is
on earlier lines.

This process never touches the TPU: it imports no JAX, and device facts
come back from the process that holds the lease.  A run that is not on
``tpu`` with a ``device_kind`` of ``benchmark/peaks.json`` and as many
chips as the cell asks for exits non-zero and prints no result; so does
a run that leaves a process of its cluster behind.
"""

from __future__ import annotations

import time

T_START = time.time()  # setup_s runs from here to the first measured instant

import argparse  # noqa: E402
import atexit  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import secrets  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _session_root() -> str:
    """A directory of this run's own for the cluster's session (sockets,
    logs, the address file), under the run's TMPDIR and removed at exit;
    never the program's fixed /tmp/ray_tpu, where two checkouts would
    meet.  AF_UNIX takes 107 bytes and the longest socket path adds 65
    to the root, so a longer TMPDIR is reached through a symlink of a
    unique short name: everything written still lands under TMPDIR."""
    real = tempfile.mkdtemp(prefix="rt_")
    atexit.register(shutil.rmtree, real, ignore_errors=True)
    if len(real) <= 42:
        return real
    while True:
        link = f"/tmp/rt_{secrets.token_hex(6)}"
        try:
            os.symlink(real, link)
        except FileExistsError:
            continue
        atexit.register(os.unlink, link)
        return link


os.environ["RAY_TPU_TMPDIR"] = _session_root()  # read by ray_tpu when it is imported

from benchmark import readers, spec, trace_reduce  # noqa: E402


class Refused(Exception):
    """No result may be printed."""


def stop_cluster(pids, keep):
    """Shut the cluster down; the processes of it that are still there
    afterwards (none may be): whatever still belongs to the session, and
    every process a runner reported, in whatever state.  A copy of
    ``chip_smoke.py``'s check."""
    import ray_tpu
    from ray_tpu._private.node import session_pids
    from ray_tpu._private.worker import get_global_worker

    if not ray_tpu.is_initialized():
        return []
    session = get_global_worker().session_info["session_dir"]
    ray_tpu.shutdown()
    if keep:
        try:
            dest = os.path.join(keep, "logs")
            shutil.rmtree(dest, ignore_errors=True)
            shutil.copytree(os.path.join(session, "logs"), dest)
        except Exception:  # noqa: BLE001 - never the reason a run fails
            traceback.print_exc()
    dying = {pid for pid in pids if os.path.exists(f"/proc/{pid}")}
    return sorted(dying | set(session_pids(session)))


def _metric_values(bench, cell_name, trace, ctx, setup_s):
    """{name: {"value", "unit"}} for the cell: end-to-end metrics by
    their name among the runner's values, per-layer metrics through the
    reader their file names."""
    out = {}
    if not trace:
        for m in spec.metrics_of_cell(bench, "end_to_end", cell_name):
            v = setup_s if m["name"] == "setup_s" else ctx["values"].get(m["name"])
            if v is not None:
                out[m["name"]] = {"value": v, "unit": m["unit"]}
        return out
    for m in spec.metrics_of_cell(bench, "per_layer", cell_name):
        how = spec.load_layer_metric(m["name"])
        v = readers.READERS[how["reader"]](how.get("args", {}), ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run_cell(cell_name, seed, seconds, trace, keep=None, rehearsal=None):
    """Run one cell; the result object, or None where none may be printed.

    `rehearsal` is for the tests only and cannot be reached from the
    command line: ``{"sizes": {...}, "cell": {...}}`` overrides sizes and
    parameters for a tiny run on the CPU, where the cluster is TOLD it
    has the chips and the checks on platform and device kind are off."""
    bench = spec.load_benchmark()
    wl = spec.entry(bench, "workloads", cell_name)
    cell = spec.load_cell(cell_name)
    config = spec.load_config(cell["config"])
    if (cell["config"], cell["chips"]) != (wl["config"], wl["chips"]):
        raise Refused(f"{cell_name}: BENCHMARK.json and the cell's file disagree")
    sizes = spec.sizes(config)
    if rehearsal:
        sizes.update(rehearsal.get("sizes", {}))
        config = dict(config, **rehearsal.get("config", {}))
        for key, value in rehearsal.get("cell", {}).items():
            cell[key] = dict(cell[key], **value) if isinstance(value, dict) else value
    runner = importlib.import_module(f"benchmark.runners.{cell['runner']}")

    import ray_tpu
    from ray_tpu.util.compile_cache import count_cache_entries, place_compile_cache

    cache = place_compile_cache(REPO)
    print(f"[cache] dir={cache} entries_before={count_cache_entries(cache)}", flush=True)
    result, left, pids = None, [], set()
    t_init = time.time()
    try:
        ray_tpu.init(**({"num_cpus": 4, "num_tpus": cell["chips"]} if rehearsal else {}))
        have = ray_tpu.cluster_resources().get("TPU", 0)
        if have < cell["chips"]:
            raise Refused(f"the cluster has {have} TPU chips, the cell asks for {cell['chips']}")
        job = {
            "cell": cell, "job": cell.get("job"), "config": config, "sizes": sizes,
            "seed": seed, "seconds": seconds, "trace": bool(trace), "t_init": t_init,
            "keep": keep, "keep_trace": os.path.join(keep, "trace") if keep else None,
        }
        result = runner.run(job)
        pids.add(result["device"].get("pid"))
    except Exception:  # noqa: BLE001 - the boundary: report, exit non-zero
        traceback.print_exc()
    finally:
        t_stop = time.time()
        try:
            if hasattr(runner, "stop") and ray_tpu.is_initialized():
                runner.stop()
        except Exception:  # noqa: BLE001
            traceback.print_exc()
        left = stop_cluster(pids - {None}, keep)
        print(f"[shutdown] left_running={left} shutdown_s={time.time() - t_stop:.2f} "
              f"cache_entries_after={count_cache_entries(cache)} "
              f"wall_s={time.time() - T_START:.1f}", flush=True)
    if result is None or left:
        return None

    dev = result["device"]
    peak = spec.load_peaks().get(dev["kind"])
    if not rehearsal:
        if dev["platform"] != "tpu" or peak is None or dev["count"] != cell["chips"]:
            print(f"[refused] ran on {dev}", flush=True)
            return None
    ctx = {
        "values": result["values"], "stats": result.get("stats"), "trace": result.get("trace"),
        "sizes": sizes, "job": cell.get("job") or cell.get("traffic"), "chips": cell["chips"],
        "peak": peak,
    }
    setup_s = result["values"]["t_window_start"] - T_START
    print(f"[checks] {json.dumps(result['checks'])}", flush=True)
    device = {k: dev[k] for k in ("platform", "kind", "count", "memory_peak_bytes")}
    out = {
        "correct": all(result["checks"].values()),
        "attempted": result["attempted"], "failed": result["failed"],
        "metrics": _metric_values(bench, cell_name, trace, ctx, setup_s),
        "device": device,
    }
    if trace:
        facts = result.get("trace") or {}
        if not facts.get("devices") and not rehearsal:
            print("[refused] the traced run saw no operation on the device", flush=True)
            return None
        device["busy_s"] = facts.get("busy_s", 0.0)
        device["window_s"] = facts.get("window_s", 0.0)
        out["breakdown"] = trace_reduce.breakdown(facts)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep", default=None,
                    help="directory for worker logs and, in a traced run, the raw trace")
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds, args.trace, keep=args.keep)
    except Exception:  # noqa: BLE001 - the boundary: report, exit non-zero
        traceback.print_exc()
        out = None
    if out is None:
        print("[benchmark] FAILED: no result", flush=True)
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

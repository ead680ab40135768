#!/usr/bin/env python
"""benchmark/sweep.py: find the knee of an open-loop serving cell, once.

    python benchmark/sweep.py --workload gpt2-large.serve.chat-steady \
        --rates 3,4,5,6,7,8 --seconds 30 --seed 1

One deployment, warmed up as the cell's run would; then one window of
the cell's own mix at each rate, the engine drained between windows.
One JSON line a rate: tokens asked for and delivered over the window and
over its second half (both ends in steady state), the engine's `waiting`
at the middle and the end, and the tails.  The knee is the highest rate
at which delivery keeps up with demand within 3% over the second half
and `waiting` is no deeper at the end than at the middle; the cell runs
at four fifths of it, rounded to 0.1 request/s, written into its file.
Not part of a check: the driver never runs this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import run as run_mod, spec, traffic as traffic_mod  # noqa: E402
from benchmark.runners import serve as serve_runner  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import ray_tpu
    from ray_tpu.util.compile_cache import place_compile_cache

    cell = spec.load_cell(args.workload)
    config = spec.load_config(cell["config"])
    sizes = spec.sizes(config)
    place_compile_cache(REPO)
    ray_tpu.init()
    pids = set()
    try:
        job = {"cell": cell, "config": config, "sizes": sizes, "seed": args.seed}
        handle, actor = serve_runner.deploy(job)

        def call(fn, *a):
            return actor.__ray_call__.remote(fn, *a)

        ray_tpu.get(call(serve_runner._rep_install), timeout=600)
        pids.add(ray_tpu.get(call(serve_runner._rep_device), timeout=120)["pid"])
        stream_handle = handle.options(stream=True)
        serve_runner.setup_checks(job, stream_handle)
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            tr = dict(cell["traffic"], rate_per_s=rate)
            reqs = traffic_mod.open_loop(tr, args.seconds, sizes["vocab_size"], args.seed + i)
            probes = {}
            at = [(args.seconds / 2, lambda: probes.setdefault("middle", call(serve_runner._rep_stats))),
                  (args.seconds, lambda: probes.setdefault("after", call(serve_runner._rep_stats)))]
            t0 = time.time() + 0.05
            streams = serve_runner.drive(
                stream_handle, {"mode": "open", "requests": reqs, "drain_s": tr["drain_s"]},
                t0, args.seconds, at)
            stats = {k: ray_tpu.get(v, timeout=120) for k, v in probes.items()}
            t_mid, t_end = t0 + args.seconds / 2, t0 + args.seconds
            half = [s for s in streams if s.due >= t_mid]
            gaps = [g for s in streams for g in s.gaps]
            ttft = [s.t_first - s.due for s in streams if s.t_first]
            row = {
                "rate_per_s": rate, "requests": len(streams),
                "unfinished_after_drain": sum(1 for s in streams if not s.done or s.failed),
                "asked_tokens": sum(s.req["max_tokens"] for s in streams),
                "delivered_in_window": sum(1 for s in streams for t in s.token_t if t < t_end),
                "asked_second_half": sum(s.req["max_tokens"] for s in half),
                "delivered_second_half": sum(1 for s in streams for t in s.token_t if t_mid <= t < t_end),
                "waiting_middle": stats["middle"]["waiting"], "waiting_end": stats["after"]["waiting"],
                "running_end": stats["after"]["running"],
                "ttft_p50_ms": 1000 * serve_runner.percentile(ttft, 50), "ttft_p90_ms": 1000 * serve_runner.percentile(ttft, 90),
                "itl_p50_ms": 1000 * serve_runner.percentile(gaps, 50), "itl_p95_ms": 1000 * serve_runner.percentile(gaps, 95),
            }
            row["keeps_up_second_half"] = row["delivered_second_half"] / row["asked_second_half"]
            print("[sweep] " + json.dumps(row), flush=True)
            deadline = time.time() + 120
            while time.time() < deadline:
                if ray_tpu.get(call(serve_runner._rep_stats), timeout=120)["kv_blocks_in_use"] == 0:
                    break
                time.sleep(0.5)
    finally:
        serve_runner.stop()
        left = run_mod.stop_cluster(pids, None)
        print(f"[shutdown] left_running={left}", flush=True)
    return 1 if left else 0


if __name__ == "__main__":
    sys.exit(main())

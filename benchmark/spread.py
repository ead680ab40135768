#!/usr/bin/env python
"""benchmark/spread.py: how far runs of ONE tree lie apart in a cell, and why.

    python benchmark/spread.py --workload gpt2-large.serve.batch-backlog \
        --sets 2 --runs 6 --seconds 30

What the driver's check does to a cell, by hand: `--sets` sets of
`--runs` runs, every run a new process of ``run.py --trace 0 --keep``,
run i of every set on the same seed (`--seed` + i: every seed takes the
same requests from another starting point, README "Adding a cell").
One line a run: the end-to-end metrics; from ``bursts.json`` the tokens
that reached the client in each whole second of the window (lowest,
median, highest, and the second half's mean over the first's) and every
gap over 50 ms between two bursts; from the runner's ``[serve] stats=``
line the cell's own ``stats_delta`` metrics and the replica's
collections.  One line a set and metric: the spread as the check takes
it, beside the metric's bound times the median.

With `--checkout` given more than once (each a checkout of the repo, as
``git archive`` unpacks one) set k runs in each of them in turn before
set k+1 runs in any: two trees on one machine, minutes apart.

Imports no JAX and holds no chip; not part of a check: the driver never
runs this.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import readers, spec  # noqa: E402
from benchmark.spec import _read  # noqa: E402 - a JSON file by the parts of its path

GAP_S = 0.05  # a step's tokens come every 6 ms in the fastest cell


def _say(**fields):
    print("[spread] " + json.dumps(fields), flush=True)


def without_farthest(values):
    """`values` less the one farthest from their median."""
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return [v for i, v in enumerate(values) if i != far]


def quartile_spread(values):
    """Third quartile minus first, as ``statistics.quantiles(n=4)`` has
    them (the contract's spread; numpy's quartiles lie closer together)."""
    q = statistics.quantiles(values, n=4)
    return q[2] - q[0]


def set_rule(values):
    """What the check reads of one set of runs of one metric.  `range`:
    highest minus lowest, with the run farthest from the median left out
    only where that narrows it.  `spread`: the quartiles' distance of the
    runs less that one (what `too tight` is judged by, as the mean over
    the sets against HALF the bound), `spread_all` of all of them (what
    `too loose` is judged by)."""
    kept = without_farthest(values) if len(values) > 2 else list(values)
    return {
        "median": statistics.median(values),
        "range": min(max(values) - min(values), max(kept) - min(kept)),
        "spread": quartile_spread(kept) if len(kept) > 1 else 0.0,
        "spread_all": quartile_spread(values) if len(values) > 1 else 0.0,
    }


def window_profile(bursts):
    """Tokens in each whole second of the window and the gaps over GAP_S
    between two bursts inside it, from a run's ``bursts.json``."""
    t0, t_end = bursts["t0"], bursts["t_end"]
    inside = [(t, n) for t, n in bursts["bursts"] if t0 <= t < t_end]
    seconds = int(t_end - t0)
    per_s = [0] * seconds
    for t, n in inside:
        if t - t0 < seconds:
            per_s[int(t - t0)] += n
    times = [t for t, _ in inside]
    gaps = [[round(a - t0, 3), round(1000 * (b - a), 1)] for a, b in zip(times, times[1:]) if b - a > GAP_S]
    out = {"gaps_over_50ms": gaps, "per_s": per_s}
    if seconds >= 2:
        half = seconds // 2
        first, second = per_s[:half], per_s[seconds - half:]
        out.update({"per_s_min": min(per_s), "per_s_median": statistics.median(per_s), "per_s_max": max(per_s),
                    "per_s_iqr": quartile_spread(per_s),
                    "half_slope_pct": 100 * (statistics.mean(second) / statistics.mean(first) - 1)})
    return out


def counters_of(log, checkout, cell):
    """The cell's ``stats_delta`` metrics (as `checkout` names them) and
    the collections of a run, from the lines the serve runner prints
    (``[serve] k=v, ...`` and ``[serve] stats=``); nothing where the
    runner prints none."""
    values, stats = {}, None
    for line in log.splitlines():
        if line.startswith("[serve] stats="):
            stats = json.loads(line[len("[serve] stats="):])
        elif line.startswith("[serve] ") and "=" in line and not line.startswith("[serve] checks="):
            for part in line[len("[serve] "):].split(", "):
                key, _, value = part.partition("=")
                try:
                    values[key] = float(value)
                except ValueError:
                    pass
    if stats is None:
        return {}
    ctx = {"values": values, "stats": stats}
    out = {}
    for m in spec.metrics_of_cell(_read(checkout, "BENCHMARK.json"), "per_layer", cell):
        how = _read(checkout, "benchmark", "layer_metrics", m["name"] + ".json")
        if how["reader"] == "stats_delta":
            v = readers.stats_delta(how["args"], ctx)
            if v is not None:
                out[m["name"]] = v
    for key in ("gc_collections", "gc_full_collections", "gc_pause_s", "stalls"):
        if key in stats["after"] and key in stats["before"]:
            out[key] = stats["after"][key] - stats["before"][key]
    return out


def one_run(checkout, cell, seed, seconds, keep, timeout_s):
    """One process of `checkout`'s ``run.py``; the row of the table."""
    shutil.rmtree(keep, ignore_errors=True)
    cmd = [sys.executable, os.path.join(checkout, "benchmark", "run.py"), "--workload", cell,
           "--seed", str(seed), "--seconds", str(seconds), "--trace", "0", "--keep", keep]
    t = time.time()
    try:
        proc = subprocess.run(cmd, cwd=checkout, capture_output=True, text=True, timeout=timeout_s)
        rc, log, err = proc.returncode, proc.stdout, proc.stderr
    except subprocess.TimeoutExpired as e:
        rc, log, err = 124, (e.stdout or b"").decode(errors="replace"), (e.stderr or b"").decode(errors="replace")
    row = {"seed": seed, "rc": rc, "wall_s": round(time.time() - t, 1)}
    last = log.strip().splitlines()[-1] if log.strip() else ""
    try:
        result = json.loads(last)
        row.update({"correct": result["correct"], "attempted": result["attempted"], "failed": result["failed"],
                    "metrics": {k: v["value"] for k, v in result["metrics"].items()}})
    except (ValueError, KeyError, TypeError):
        row["error"] = (err or log)[-600:]
        return row
    try:
        row["window"] = window_profile(_read(keep, "bursts.json"))
    except (OSError, ValueError):
        pass  # a train cell keeps none
    row["counters"] = counters_of(log, checkout, cell)
    return row


def summarize(bench, cell, sets):
    """{metric: [set_rule of each set, with the bound]} over the runs that
    gave a result."""
    out = {}
    for m in spec.metrics_of_cell(bench, "end_to_end", cell):
        per_set = []
        for rows in sets:
            values = [r["metrics"][m["name"]] for r in rows if m["name"] in r.get("metrics", {})]
            if m["name"] == "setup_s" and rows is sets[0]:
                values = values[1:]  # the run that compiles stands apart
            if len(values) < 2:
                continue
            rule = set_rule(values)
            rule.update({"runs": len(values), "bound": m["bound"], "bound_x_median": m["bound"] * rule["median"]})
            per_set.append(rule)
        if per_set:
            out[m["name"]] = per_set
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", type=int, default=2_149_000_001, help="run i of every set takes seed + i")
    ap.add_argument("--checkout", action="append", default=None,
                    help="a checkout to measure (default: this one); repeat to take turns set by set")
    ap.add_argument("--out", default=None, help="write every row and the summary here as JSON")
    ap.add_argument("--stop-after-s", type=float, default=None,
                    help="start no further run once this many seconds have passed")
    args = ap.parse_args(argv)

    # {the checkout as the table names it: its path}
    checkouts = {os.path.relpath(c, REPO): os.path.abspath(c) for c in (args.checkout or [REPO])}
    keep = os.path.join(REPO, ".spread_keep")
    t_start = time.time()
    table = {label: [[] for _ in range(args.sets)] for label in checkouts}
    for k in range(args.sets):
        for label, path in checkouts.items():
            for i in range(args.runs):
                if args.stop_after_s is not None and time.time() - t_start > args.stop_after_s:
                    _say(checkout=label, set=k + 1, out_of_time_after_runs=i)
                    break
                row = one_run(path, args.workload, args.seed + i, args.seconds, keep, args.seconds + 1260)
                table[label][k].append(row)
                _say(checkout=label, set=k + 1, **row)
    shutil.rmtree(keep, ignore_errors=True)

    report = {"workload": args.workload, "seconds": args.seconds, "checkouts": {}}
    bad = 0
    for label, sets in table.items():
        summary = summarize(_read(checkouts[label], "BENCHMARK.json"), args.workload, sets)
        report["checkouts"][label] = {"sets": sets, "summary": summary}
        bad += sum(1 for rows in sets for r in rows if not r.get("correct") or r.get("failed"))
        for name, per_set in summary.items():
            for k, rule in enumerate(per_set):
                _say(checkout=label, metric=name, set=k + 1, **rule)
            if name == "setup_s":  # judged by its median alone: the last set's against the first's
                _say(checkout=label, metric=name, last_median_over_first=per_set[-1]["median"] / per_set[0]["median"])
                continue
            mean_spread, allowed = statistics.mean(r["spread"] for r in per_set), per_set[0]["bound_x_median"] / 2
            _say(checkout=label, metric=name, mean_spread=mean_spread, half_bound_x_median=allowed,
                 too_tight=mean_spread > allowed,
                 every_range_under_bound=all(r["range"] <= r["bound_x_median"] for r in per_set))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

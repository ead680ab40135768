"""An addition rehearsed: what a later PR does to the benchmark, done to
a copy, so that the tests which read the data files show that they
take it without an edit.

A ``model_config`` PR adds a configuration, a cell and per-layer entries
with their files, and edits no file that is there.  ``plant`` makes that
addition under ``tmp_path`` (a copy of ``BENCHMARK.json``, ``peaks.json``
and ``configs/``, ``workloads/``, ``layer_metrics/``; ZAYA1's files
under other names) and points ``spec`` at the copy.  A test that reads the
data files takes ``tree`` from ``TREES`` and calls ``plant`` first: it
has to pass on the tree as committed and on the tree with the addition.
"""

import json
import os
import shutil

from benchmark import spec

TREES = ("as_committed", "with_an_addition")

LIKE_CONFIG, LIKE_CELL = "zaya1-8b", "zaya1-8b.serve.longthink-backlog"
CONFIG, TRAFFIC = "rehearsed-model", "serve.rehearsed-backlog"
CELL = CONFIG + "." + TRAFFIC
# the addition's own entries, each with its file: a counter of the new
# family alone, a kernel's share of busy time, and a span that the
# accepted backlog cells report too (SHARED)
ENTRIES = [
    ({"name": "rehearsed_kept_pct", "unit": "%", "better": "lower", "source": "program_counter", "layer": "models"},
     {"reader": "stats_delta", "args": {"expr": "100 * d.rehearsed_kept / d.rehearsed_cached"}}),
    ({"name": "rehearsed_kernel_busy_pct", "unit": "%", "better": "lower", "source": "device_trace",
      "layer": "kernels"},
     {"reader": "trace_ops", "args": {"pattern": "^rehearsed_kernel", "mode": "pct_of_busy"}}),
    ({"name": "rehearsed_emit_ms", "unit": "ms", "better": "lower", "source": "program_span", "layer": "serve plane"},
     {"reader": "stats_delta", "args": {"expr": "1000 * d.emit_s / d.rehearsed_steps"}}),
]
SHARED = "rehearsed_emit_ms"


def _dump(data, *parts):
    with open(os.path.join(*parts), "w") as f:
        json.dump(data, f, indent=2)


def plant(tree, tmp_path, monkeypatch):
    """On ``with_an_addition``: the copy, the addition, and ``spec``'s
    two roots on the copy.  On ``as_committed``: nothing."""
    if tree == "as_committed":
        return
    root, here = str(tmp_path), str(tmp_path / "benchmark")
    for sub in ("configs", "workloads", "layer_metrics"):
        shutil.copytree(os.path.join(spec.HERE, sub), os.path.join(here, sub))
    shutil.copy(os.path.join(spec.HERE, "peaks.json"), here)
    bench = spec.load_benchmark()

    config = dict(spec.entry(bench, "configs", LIKE_CONFIG), name=CONFIG, file=f"benchmark/configs/{CONFIG}.json")
    bench["configs"].append(config)
    _dump(spec.load_config(LIKE_CONFIG), here, "configs", CONFIG + ".json")

    like = spec.entry(bench, "workloads", LIKE_CELL)
    bench["workloads"].append(dict(like, name=CELL, config=CONFIG, traffic=TRAFFIC))
    _dump(dict(spec.load_cell(LIKE_CELL), config=CONFIG), here, "workloads", CELL + ".json")

    # the new cell joins the entries its like reports, as a family's cell does ...
    backlog_cells = list(spec.entry(bench, "per_layer", "engine_step_ms.backlog")["workloads"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        if LIKE_CELL in m.get("workloads", []):
            m["workloads"].append(CELL)
    # ... and brings entries of its own, one of which the accepted backlog cells report too
    for entry, how in ENTRIES:
        cells = (backlog_cells if entry["name"] == SHARED else []) + [CELL]
        bench["per_layer"].append(dict(entry, moves="serve_out_tokens_per_s", workloads=cells))
        _dump(how, here, "layer_metrics", entry["name"] + ".json")
    _dump(bench, root, "BENCHMARK.json")
    monkeypatch.setattr(spec, "REPO", root)
    monkeypatch.setattr(spec, "HERE", here)

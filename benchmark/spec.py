"""The benchmark's data files, found by the names in BENCHMARK.json.

``BENCHMARK.json`` (root of the checkout) names cells, configurations and
metrics; everything that belongs to one of them sits in a file of its
own under ``benchmark/``: ``workloads/<cell>.json``,
``configs/<config>.json``, ``layer_metrics/<metric>.json``.  A later PR
adds files and entries and edits none.  Nothing here imports JAX or the
program.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def _read(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_benchmark() -> dict:
    return _read(REPO, "BENCHMARK.json")


def entry(bench: dict, section: str, name: str) -> dict:
    for e in bench[section]:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {section} entry named {name!r}")


def load_cell(name: str) -> dict:
    return _read(HERE, "workloads", name + ".json")


def load_config(name: str) -> dict:
    return _read(HERE, "configs", name + ".json")


def load_layer_metric(name: str) -> dict:
    return _read(HERE, "layer_metrics", name + ".json")


def load_peaks() -> dict:
    return _read(HERE, "peaks.json")


def sizes(config: dict) -> dict:
    """The sizes the arithmetic and the runners use, under the source's
    own key names, plus the rows of vocabulary actually held."""
    out = {k: config[k] for k in ("n_layer", "n_embd", "n_head", "n_positions", "vocab_size")}
    out["vocab_rows"] = config["assumed"]["vocab_rows"]
    out["dtype"] = config["dtype"]
    return out


def metrics_of_cell(bench: dict, section: str, cell: str) -> list:
    """Entries of `section` (end_to_end or per_layer) that `cell` reports:
    those with no ``workloads`` key, and those that list it."""
    return [m for m in bench[section] if "workloads" not in m or cell in m["workloads"]]

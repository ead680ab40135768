"""The benchmark's own checks, on the CPU:

    python -m pytest benchmark/tests -q

The yardstick's arithmetic (trace reduction, FLOPs, traffic) against
hand-worked numbers, the data files against the contract, the command's
refusal to print a result without a chip, and each runner end to end at
the `tiny` preset behind `run_cell`'s rehearsal argument, which the
command line cannot reach.
"""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import flops, readers, spec, traffic, trace_reduce  # noqa: E402
from benchmark.tests import rehearsal  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# ----------------------------------------------------------------------
# trace reduction
# ----------------------------------------------------------------------
def _planes():
    ms = 1_000_000
    dev0 = [["fusion.1", 0, 4 * ms], ["flash_fwd", 3 * ms, 3 * ms],  # overlap: busy 0-6
            ["all-reduce.2", 10 * ms, 2 * ms], ["fusion.1", 14 * ms, 6 * ms]]
    dev1 = [["fusion.1", 0, 10 * ms], ["fusion.1", 12 * ms, 8 * ms]]
    host = [["step", 0, 9 * ms], ["fetch", 9 * ms, 2 * ms], ["step", 11 * ms, 9 * ms],
            ["other", 0, 20 * ms]]
    return [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": dev0},
                                            {"name": "XLA Modules", "events": [["jit_step", 0, 20 * ms]]}]},
        {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": dev1}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]},
    ]


def test_trace_reduction_busy_union_time_by_name_and_gaps():
    facts = trace_reduce.reduce(_planes(), annotations=("step", "fetch"))
    assert facts["devices"] == 2
    assert facts["window_s"] == pytest.approx(0.020)
    assert facts["busy_s_device0"] == pytest.approx(0.014)  # 6 + 2 + 6 ms
    assert facts["busy_s"] == pytest.approx((0.014 + 0.018) / 2)
    # by family: the name less its instruction number
    assert facts["op_seconds"]["fusion"] == pytest.approx(0.010)
    assert list(facts["op_seconds"])[0] == "fusion"  # longest first
    assert facts["op_counts"]["fusion"] == 2 and "all-reduce" in facts["op_seconds"]
    # gaps of device 0: 6-10 ms (midpoint 8 ms: inside the first step) and
    # 12-14 ms (midpoint 13 ms: inside the second step)
    assert facts["idle_gaps"] == [["step", pytest.approx(0.004)], ["step", pytest.approx(0.002)]]
    assert facts["span_counts"] == {"step": 2, "fetch": 1}
    assert facts["span_seconds"]["step"] == pytest.approx(0.018)
    assert trace_reduce.union_seconds([(0, 4), (3, 6), (10, 12)]) == 8
    # the first 11 ms only: device 0 busy 0-6 and 10-11, device 1 busy 0-10
    cut = trace_reduce.reduce(_planes(), annotations=("step", "fetch"), first_s=0.011)
    assert cut["window_s"] == pytest.approx(0.011) and cut["busy_s_device0"] == pytest.approx(0.007)
    assert cut["busy_s"] == pytest.approx((0.007 + 0.010) / 2)
    assert cut["op_seconds"]["all-reduce"] == pytest.approx(0.001) and cut["span_counts"]["step"] == 1
    assert trace_reduce.reduce([{"name": "/host:CPU", "lines": []}]) == {"devices": 0}


def test_operation_names_are_cut_to_instruction_and_target():
    raw = ('%attn.103 = (bf16[128,1024,64]{2,1,0:T(8,128)(2,1)}, bf16[128,1024,64]{2,1,0:T(8,128)(2,1)}) '
           'custom-call(bf16[128,1024,64]{2,1,0:T(8,128)(2,1)S(1)} %bitcast.2547, f32[128,1,1024]{2,1,0:T(1,128)} '
           '%pallas_call.230), custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert trace_reduce.short_name(raw) == "attn.103 tpu_custom_call"
    assert trace_reduce.family("attn.103 tpu_custom_call") == "attn tpu_custom_call"
    raw = ('%fusion.26 = (f32[1024,50304]{1,0:T(8,128)}, f32[1024,50304]{1,0:T(8,128)}) fusion(f32[1024,50304]'
           '{1,0:T(8,128)} %params__lm_head____kernel__.1, f32[]{:T(128)S(6)} %sub.551), kind=kOutput, calls=%fused_computation.33')
    assert trace_reduce.short_name(raw) == "fusion.26" and trace_reduce.family("fusion.26") == "fusion"
    raw = "%all-reduce.7 = f32[1280]{0:T(1024)} all-reduce(f32[1280]{0:T(1024)} %x), replica_groups={{0,1}}"
    assert trace_reduce.family(trace_reduce.short_name(raw)) == "all-reduce"
    assert trace_reduce.family("copy.1610.remat") == "copy.remat"
    assert trace_reduce.short_name("step") == "step"
    assert trace_reduce.short_name("jit_train_step(123)") == "jit_train_step(123)"


def test_trace_readers_on_the_reduced_trace():
    facts = trace_reduce.reduce(_planes(), annotations=("step", "fetch"))
    sizes = spec.sizes(spec.load_config("gpt2-medium"))
    ctx = {"trace": facts, "values": {}, "stats": None, "sizes": sizes, "chips": 1,
           "job": {"batch": 8, "seq": 1024}, "peak": spec.load_peaks()["TPU v5 lite"]}
    assert readers.trace_idle({}, ctx) == pytest.approx(30.0)
    assert readers.trace_ops({"pattern": "all-reduce", "mode": "pct_of_busy"}, ctx) == pytest.approx(100 * 2 / 14)
    assert readers.trace_ops({"pattern": "^flash", "mode": "ms_per_step"}, ctx) == pytest.approx(1.5)
    roof = readers.trace_ops({"pattern": "^flash", "mode": "roofline_pct", "work": "flash_step_work"}, ctx)
    least = flops.least_seconds(flops.flash_step_work(sizes, 8, 1024), ctx["peak"])["seconds"]
    assert roof == pytest.approx(100 * least / 0.0015)
    assert readers.trace_span({"pattern": "^fetch$"}, ctx) == pytest.approx(10.0)  # 2 of 20 ms
    # a reader that finds nothing to read returns nothing
    assert readers.trace_ops({"pattern": "nothing", "mode": "roofline_pct", "work": "flash_step_work"}, ctx) is None
    assert readers.trace_idle({}, {"trace": None}) is None
    assert readers.stats_delta({"expr": "d.steps"}, {"stats": None, "values": {}}) is None


def test_recorded_chip_trace_reduces():
    """A cut of a trace taken on the v5e chip (one train cell, a few
    steps), kept beside this file: the reduction finds the device, its
    operations and the benchmark's annotations in it."""
    path = os.path.join(HERE, "data", "trace_sample.json")
    with open(path) as f:
        planes = json.load(f)
    facts = trace_reduce.reduce(planes, annotations=("step", "fetch"))
    assert facts["devices"] >= 1
    assert 0 < facts["busy_s_device0"] <= facts["window_s"]
    assert facts["op_seconds"] and facts["span_counts"].get("step", 0) >= 1
    expected = os.path.join(HERE, "data", "trace_sample.expected.json")
    with open(expected) as f:
        want = json.load(f)
    assert facts["busy_s_device0"] == pytest.approx(want["busy_s_device0"])
    assert facts["window_s"] == pytest.approx(want["window_s"])
    top = list(facts["op_seconds"].items())[0]
    assert [top[0], pytest.approx(top[1])] == want["top_op"]


def test_stats_delta_arithmetic():
    ctx = {"values": {"first": 10},
           "stats": {"before": {"steps": 100, "total_tokens": 1000, "max_batch_size": 16, "platform": "tpu"},
                     "after": {"steps": 300, "total_tokens": 4210, "max_batch_size": 16, "platform": "tpu"},
                     "window_s": 10.0}}
    assert readers.stats_delta({"expr": "1000 * window_s / d.steps"}, ctx) == pytest.approx(50.0)
    expr = "100 * (d.total_tokens - v.first) / d.steps / s.max_batch_size"
    assert readers.stats_delta({"expr": expr}, ctx) == pytest.approx(100.0)
    with pytest.raises(ValueError):
        readers.stats_delta({"expr": "__import__('os')"}, ctx)


# ----------------------------------------------------------------------
# FLOPs and bytes from shapes
# ----------------------------------------------------------------------
def test_flops_against_hand_worked_numbers():
    medium = spec.sizes(spec.load_config("gpt2-medium"))
    large = spec.sizes(spec.load_config("gpt2-large"))
    # 24 * 12 * 1024^2 + 50304 * 1024; 36 * 12 * 1280^2 + 50304 * 1280
    assert flops.matmul_params(medium) == 301_989_888 + 51_511_296 == 353_501_184
    assert flops.matmul_params(large) == 707_788_800 + 64_389_120 == 772_177_920
    # 6 a parameter + 24 layers * 6 * T * d of causal attention
    assert flops.train_flops_per_token(medium, 1024) == 6 * 353_501_184 + 24 * 6 * 1024 * 1024
    assert flops.train_flops_per_token(medium, 1024) == pytest.approx(2.272e9, rel=1e-3)
    assert flops.train_flops_per_token(large, 1024) == pytest.approx(4.916e9, rel=1e-3)
    # one layer of medium at B=8: 8*16 heads, T=1024, Dh=64
    fwd = flops.flash_forward(8, 16, 1024, 64)
    assert fwd["flops"] == 2 * 8 * 16 * 1024 * 1024 * 64  # half of 4*B*H*T^2*Dh
    assert fwd["bytes"] == 4 * 8 * 16 * 1024 * 64 * 2 + 8 * 16 * 1024 * 4
    bwd = flops.flash_backward(8, 16, 1024, 64)
    assert bwd["flops"] == 2.5 * fwd["flops"]
    peak = spec.load_peaks()["TPU v5 lite"]
    least = flops.least_seconds(fwd, peak)
    assert least["bound"] == "compute"  # 17.2 GFLOP / 197 T = 87 us; 34 MB / 819 G = 41 us
    assert least["seconds"] == pytest.approx(17_179_869_184 / 197e12)
    step = flops.flash_step_work(medium, 8, 1024)
    assert step["flops"] == 24 * 3.5 * fwd["flops"]


# ----------------------------------------------------------------------
# traffic
# ----------------------------------------------------------------------
def test_traffic_from_the_seed():
    tr = spec.load_cell("gpt2-large.serve.chat-steady")["traffic"]
    a = traffic.open_loop(tr, 30, 50257, seed=3_000_000_019)
    b = traffic.open_loop(tr, 30, 50257, seed=3_000_000_019)
    c = traffic.open_loop(tr, 30, 50257, seed=7)
    assert a == b and a != c
    assert len(a) == round(tr["rate_per_s"] * 30)
    for reqs in (a, c):
        assert all(0 <= r["due_s"] < 30 for r in reqs)
        assert [r["due_s"] for r in reqs] == sorted(r["due_s"] for r in reqs)
        for r in reqs:
            n = len(r["prompt"])
            assert tr["prompt_len"]["lo"] <= n <= tr["prompt_len"]["hi"]
            assert tr["max_tokens"]["lo"] <= r["max_tokens"] <= tr["max_tokens"]["hi"]
            assert n + r["max_tokens"] <= tr["max_total_tokens"]
            assert all(0 <= t < 50257 for t in r["prompt"])
    # every seed: the same work in another order
    assert sorted(len(r["prompt"]) for r in a) == sorted(len(r["prompt"]) for r in c)
    assert sorted(r["max_tokens"] for r in a) == sorted(r["max_tokens"] for r in c)
    lens = sorted(len(r["prompt"]) for r in a)
    assert 110 <= lens[len(lens) // 2] <= 146  # median 128
    assert traffic.warmup_prompt_lengths(tr) == [16, 32, 64, 128, 256, 512, 768]
    gaps = traffic.exponential_gaps(150, 30.0)
    assert sum(gaps) == pytest.approx(30.0)


def test_closed_loop_rate_is_cut_at_bursts():
    """Steps of 16 tokens every 0.124 s, and after every fourth a prefill's
    first token alone: a window cut at fixed instants counts a step more
    or less by its phase, the cut at bursts does not, whichever kind of
    burst its edges fall on."""
    import types

    from benchmark.runners import serve

    def streams(phase):
        steps = [phase + 0.124 * i for i in range(-8, 260)]
        lanes = [types.SimpleNamespace(token_t=[t + lane * 1e-5 for t in steps]) for lane in range(16)]
        return lanes + [types.SimpleNamespace(token_t=[t + 0.03 for t in steps[::4]])]

    true_rate = (4 * 16 + 1) / (4 * 0.124)
    rates, fixed = [], []
    for phase in (0.001, 0.030, 0.060, 0.095, 0.123):
        tokens, span = serve.edge_rate(streams(phase), 0.0, 30.0)
        assert abs(span - 30.0) < 0.124
        rates.append(tokens / span)
        fixed.append(sum(1 for s in streams(phase) for t in s.token_t if 0.0 <= t < 30.0) / 30.0)
    assert all(r == pytest.approx(true_rate, rel=1.5e-3) for r in rates)  # one prefill in 30 s
    assert max(fixed) - min(fixed) > 0.5  # 16 tokens in 30 s
    assert serve.bursts(streams(0.0))[10:13] == [[0.0, 16], [0.03, 1], [0.124, 16]]
    # a stalled engine: no burst at an edge leaves it at its instant
    quiet = [types.SimpleNamespace(token_t=[1.0, 2.0])]
    assert serve.edge_rate(quiet, 0.0, 30.0) == (1, 29.0)


# ----------------------------------------------------------------------
# the data files and the contract
# ----------------------------------------------------------------------
@pytest.mark.parametrize("tree", rehearsal.TREES)
def test_data_files_load_and_agree_with_benchmark_json(tree, tmp_path, monkeypatch):
    rehearsal.plant(tree, tmp_path, monkeypatch)
    bench = spec.load_benchmark()
    assert bench["command"][1].startswith(bench["paths"][0] + "/")
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(1, len(cells) // 4)
    for name, c in configs.items():
        assert NAME.match(name) and len(c["source"]) <= 200
        data = spec.load_config(name)
        assert os.path.join(spec.REPO, c["file"]) == os.path.join(spec.HERE, "configs", name + ".json")
        assert data["source"] == c["source"] and data["reduced"] == c["reduced"]
        assert any(w["config"] == name for w in cells.values())
        spec.sizes(data)
    for name, w in cells.items():
        assert NAME.match(name) and NAME.match(w["traffic"]) and len(w["why"]) <= 200
        assert name == w["config"] + "." + w["traffic"]
        cell = spec.load_cell(name)
        assert (cell["config"], cell["chips"], cell["why"]) == (w["config"], w["chips"], w["why"])
        assert os.path.exists(os.path.join(BENCH, "runners", cell["runner"] + ".py"))
        reported = {m["name"] for m in spec.metrics_of_cell(bench, "end_to_end", name)}
        assert "setup_s" in reported and len(reported) >= 2
        assert spec.metrics_of_cell(bench, "per_layer", name)
    for m in bench["end_to_end"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert all(c in cells for c in m.get("workloads", []))
    layers = set()
    for m in bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        how = spec.load_layer_metric(m["name"])
        assert set(how) == {"reader", "args"} and how["reader"] in readers.READERS
        assert m["workloads"] and all(c in cells for c in m["workloads"])  # some cell reports it
        layers.add(m["layer"])
        # the metric it moves is reported in every cell that reports it
        for cell in m.get("workloads", list(cells)):
            assert m["moves"] in {e["name"] for e in spec.metrics_of_cell(bench, "end_to_end", cell)}
        if m["name"].split(".")[0].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    # every file under the three directories is named in BENCHMARK.json
    for sub, names in (("configs", configs), ("workloads", cells),
                       ("layer_metrics", {m["name"] for m in bench["per_layer"]})):
        on_disk = {f[:-5] for f in os.listdir(os.path.join(spec.HERE, sub)) if f.endswith(".json")}
        assert on_disk == set(names), sub
    assert set(spec.load_peaks()) == {"TPU v5 lite"}


def _what_a_cell_reads(bench, cell):
    """{(reader, args, moves)} of the cell's per-layer metrics, each as JSON."""
    rows = set()
    for m in spec.metrics_of_cell(bench, "per_layer", cell):
        how = spec.load_layer_metric(m["name"])
        rows.add(json.dumps([how["reader"], how.get("args", {}), m["moves"]], sort_keys=True))
    return rows


def test_the_fold_dropped_nothing_a_cell_read():
    """PR 49 folded the families' twins (128 entries to 57).  Every
    reader, with its arguments and the end-to-end metric it moves, that a
    cell resolved on the parent it resolves still, under whatever name.
    ``data/per_layer_before_fold.json`` is the parent's table, made in a
    checkout of 71c9b1a by::

        table = {w["name"]: sorted(_what_a_cell_reads(bench, w["name"])) for w in bench["workloads"]}
        json.dump({c: [json.loads(r) for r in rows] for c, rows in table.items()}, f, indent=1)
    """
    bench = spec.load_benchmark()
    with open(os.path.join(HERE, "data", "per_layer_before_fold.json")) as f:
        before = json.load(f)
    assert set(before) <= {w["name"] for w in bench["workloads"]}
    for cell, rows in before.items():
        missing = {json.dumps(r, sort_keys=True) for r in rows} - _what_a_cell_reads(bench, cell)
        assert not missing, (cell, missing)


@pytest.mark.parametrize("tree", rehearsal.TREES)
def test_one_entry_for_each_thing_measured_and_room_for_the_next_cells(tree, tmp_path, monkeypatch):
    """No two entries share reader, arguments, unit, direction, source,
    layer and `moves`, but the six that ``tests/test_serve_engine_phases.py``
    (tier-1, which a ``benchmark`` PR may not edit) looks up by name
    beside a bare twin that lists the other cells (PERF.md section 7:
    folding them waits for that file).

    The ONE assertion on the table's length in ``benchmark/tests``: the
    contract allows 128 entries; 70 stand for 12 cells after PR 56 (64
    once the six twins fold); a new family has brought up to seven and
    the next one queued owes about six.  At 96 the next fold is asked
    for while 32 places are still free."""
    rehearsal.plant(tree, tmp_path, monkeypatch)
    bench = spec.load_benchmark()
    looked_up_by_name = {"prefill_share_pct.backlog", "host_ms_per_step.backlog", "kv_gather_useful_pct.backlog",
                         "prefill_pad_ratio.backlog", "decode_overlap_pct.backlog", "decode_overlap_pct.moe"}
    seen = {}
    for m in bench["per_layer"]:
        if m["name"] in looked_up_by_name:
            assert len(m["workloads"]) == 1
            continue
        how = spec.load_layer_metric(m["name"])
        key = json.dumps([how, m["unit"], m["better"], m["source"], m["layer"], m["moves"]], sort_keys=True)
        assert key not in seen, (m["name"], seen[key])
        seen[key] = m["name"]
    assert len(bench["per_layer"]) <= 96


def test_spread_reads_a_set_as_the_check_does():
    """``spread.set_rule`` on six runs by hand: the run farthest from the
    median is left out of the range only where that narrows it, and of
    the quartiles' distance always."""
    from benchmark import spread

    runs = [2596.0, 2601.0, 2590.0, 2610.0, 2540.0, 2599.0]  # median 2597.5, 2540 the farthest
    rule = spread.set_rule(runs)
    assert rule["median"] == 2597.5 and rule["range"] == 2610.0 - 2590.0
    # statistics.quantiles of the five kept: 2593 and 2605.5; of all six: 2577.5 and 2603.25
    assert rule["spread"] == pytest.approx(12.5) and rule["spread_all"] == pytest.approx(25.75)
    # the farthest run is no end of the range where two runs tie for the other end
    tied = [10.0, 10.0, 11.0, 12.0, 13.0, 20.0]
    assert spread.without_farthest(tied) == [10.0, 10.0, 11.0, 12.0, 13.0] and spread.set_rule(tied)["range"] == 3.0
    flat = [5.0, 5.0, 5.0, 5.0, 5.0, 5.0]
    assert spread.set_rule(flat)["range"] == 0.0 and spread.set_rule(flat)["spread"] == 0.0
    # a run's window second by second, and a gap between two bursts
    bursts = {"t0": 100.0, "t_end": 104.0,
              "bursts": [[99.99, 16]] + [[100.0 + 0.01 * i, 16] for i in range(180)]
              + [[102.0 + 0.01 * i, 16] for i in range(200)] + [[104.0, 16]]}
    prof = spread.window_profile(bursts)
    assert prof["per_s"] == [1600, 1280, 1600, 1600] and prof["half_slope_pct"] == pytest.approx(100 * (3200 / 2880 - 1))
    assert prof["gaps_over_50ms"] == [[1.79, 210.0]]


# ----------------------------------------------------------------------
# the command refuses to print a result without a chip
# ----------------------------------------------------------------------
def _run(cwd, env, cell="gpt2-medium.train.b8-t1024"):
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", "3000000019",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def test_run_refuses_without_a_chip():
    out = _run(REPO, {**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
    assert "TPU chips" in out.stderr


def test_run_fails_alone_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = _run(tmp_path, env)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


# ----------------------------------------------------------------------
# each runner end to end at the tiny preset
# ----------------------------------------------------------------------
TINY = {"n_layer": 2, "n_embd": 128, "n_head": 4, "n_positions": 128, "vocab_size": 500,
        "vocab_rows": 512, "dtype": "float32"}
TINY_TRAIN = {"job": {"batch": 4, "seq": 64, "ref_tol": 1e-3, "trace_from_s": 0.3, "trace_seconds": 0.5,
                      "token_check": {"sequences": 2, "tol": 1e-3}}}
TINY_SERVE = {
    "engine": {"max_batch_size": 4, "block_size": 8, "pool_tokens": 512, "max_queue": 256},
    "traffic": {"prompt_len": {"median": 16, "sigma": 0.8, "lo": 4, "hi": 60},
                "max_tokens": {"median": 8, "sigma": 0.5, "lo": 2, "hi": 24},
                "max_total_tokens": 128, "rate_per_s": 8.0, "trace_seconds": 0.5, "drain_s": 5,
                "clients": 8, "pool_requests": 64, "lead_in_s": 0.5},
    "checks": {"prompt_len": 12, "max_tokens": 6, "logit_margin": 1e-3},
}


@pytest.mark.parametrize("cell, devices, trace, over, metric", [
    ("gpt2-medium.train.b8-t1024", 1, 0, TINY_TRAIN, "train_tokens_per_s_chip"),
    ("gpt2-large.train.mesh2x2", 4, 1, TINY_TRAIN, "step_ms.train"),
    ("gpt2-large.serve.chat-steady", 1, 0, TINY_SERVE, "itl_p95_ms"),
    ("gpt2-large.serve.batch-backlog", 1, 1, TINY_SERVE, "lanes_busy_pct.backlog"),
])
def test_runner_end_to_end_at_tiny_size(monkeypatch, cell, devices, trace, over, metric):
    from benchmark import run

    if cell not in {w["name"] for w in spec.load_benchmark()["workloads"]}:
        pytest.skip(f"{cell} is not in BENCHMARK.json (PERF.md, Open questions)")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", f"--xla_force_host_platform_device_count={devices}")
    out = run.run_cell(
        cell, seed=3_000_000_019, seconds=2, trace=trace,
        rehearsal={"sizes": TINY, "config": {"preset": "tiny"}, "cell": over},
    )
    assert out is not None
    assert set(out) == {"correct", "attempted", "failed", "metrics", "device"} | (
        {"breakdown"} if trace else set())
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"][metric]["value"] > 0
    assert out["device"]["count"] == devices
    if not trace:
        assert out["metrics"]["setup_s"]["value"] > 0

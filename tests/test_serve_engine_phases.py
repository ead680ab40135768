"""The LLM engine's step loop timed by phase: the counters in
``stats()``, the spans in a profiler trace, the slow-iteration line, and
the benchmark's per-layer metrics that read them.

Engine-level tests, no cluster: asyncio and the ``tiny`` preset on the
CPU.  Counts are exact; seconds are only ordered and bounded here, a
speed comes from the chip alone.
"""

import asyncio
import json
import logging
import os
import time

import pytest

from ray_tpu.serve.llm import LLMConfig, LLMEngine
from ray_tpu.serve.llm import engine as engine_mod
from ray_tpu.serve.llm.engine import ENGINE_SPANS, FINISHED

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# what the loop thread does, phase after phase: these tile an iteration
# (the two run spans lie inside their awaits, on an executor thread)
LOOP_PHASES = [n for n in ENGINE_SPANS + engine_mod._LOOP_WAITS if not n.endswith(".run")]


def _key(name: str) -> str:
    return name[len("engine."):].replace(".", "_")


def _tiny(**kw) -> LLMConfig:
    base = dict(model="tiny", max_batch_size=4, num_blocks=64, block_size=8,
                default_max_tokens=8, temperature=0.0)
    base.update(kw)
    return LLMConfig(**base)


async def _drain(req):
    toks = []
    while True:
        ev = await req.out.get()
        if ev is FINISHED:
            return toks
        toks.append(ev["token"])


async def _generate(eng, batch):
    reqs = [await eng.add_request(list(range(1, n + 1)), max_tokens=m) for n, m in batch]
    return await asyncio.gather(*[_drain(r) for r in reqs])


# (prompt tokens, output tokens): more requests than the four lanes, one
# answered by its prefill alone, buckets of 8, 16 and 32
BATCH = [(3, 5), (8, 1), (9, 7), (17, 4), (5, 6), (12, 3), (30, 9)]


@pytest.fixture(scope="module")
def batch_run():
    """One engine, BATCH sent at once and drained: its stats and the
    wall time from before the loop started to after it stopped."""

    async def main():
        eng = LLMEngine(_tiny())
        t0 = time.perf_counter()
        outs = await _generate(eng, BATCH)
        stats = eng.stats()
        await eng.stop()
        return eng, outs, stats, time.perf_counter() - t0

    return asyncio.run(main())


def test_counts_are_conserved_over_a_fixed_batch(batch_run):
    eng, outs, st, _ = batch_run
    assert [len(o) for o in outs] == [m for _, m in BATCH]
    assert st["joined"] == len(BATCH)
    assert st["prompt_tokens"] == sum(n for n, _ in BATCH)
    assert st["prefill_bucket_tokens"] == sum(
        LLMEngine._prefill_bucket(n, eng.max_ctx) for n, _ in BATCH)
    assert st["steps"] > 0
    # a request's decode step j (of m - 1) attends to its n prompt
    # positions and the j tokens written before it
    assert st["kv_positions_attended"] == sum(
        (m - 1) * n + (m - 1) * (m - 2) // 2 for n, m in BATCH)
    # and reads the whole pages that hold them
    bs = eng.bm.block_size
    assert st["kv_positions_gathered"] == sum(
        -(-(n + j) // bs) * bs for n, m in BATCH for j in range(m - 1))
    assert st["total_tokens"] == sum(m for _, m in BATCH)
    assert st["queue_wait_s"] > 0  # three of seven waited for a lane at least
    assert st["kv_blocks_in_use"] == 0


def test_decode_steps_are_chained_on_a_full_batch_and_never_on_single_steps(batch_run):
    """``decodes_chained`` counts the decode steps dispatched while the
    step before them was unfetched: most of a batch's, and none where
    every request takes one decode step and the engine drains between
    two of them."""
    _, _, st, _ = batch_run
    assert st["steps"] / 2 < st["decodes_chained"] < st["steps"]  # the first step follows none
    assert st["lane_steps_discarded"] == 0

    async def main():
        eng = LLMEngine(_tiny())
        for n in (3, 9, 17):
            await _generate(eng, [(n, 2)])  # a prefill's token, and one decode step's
        await _generate(eng, [(5, 1)])  # answered by its prefill alone
        st = eng.stats()
        await eng.stop()
        return st

    st = asyncio.run(main())
    assert (st["steps"], st["decodes_chained"], st["total_tokens"]) == (3, 0, 7)


@pytest.mark.parametrize("depth", [1, 2])
def test_decodes_ahead_counts_the_steps_dispatched_behind_two(depth, hold_depth, batch_run):
    """``decodes_ahead`` counts the decode steps dispatched while the two
    before them were unfetched: none where every fetch blocks (the loop
    is then never the one waited for), nearly all where none does; the
    tokens are the same at either depth, and ``decodes_chained`` keeps
    its meaning."""
    _, outs, natural, _ = batch_run

    async def main():
        eng = LLMEngine(_tiny())
        hold_depth(eng, depth)
        got = await _generate(eng, BATCH)
        st = eng.stats()
        await eng.stop()
        return got, st

    got, st = asyncio.run(main())
    assert got == outs
    assert st["steps"] / 2 < st["decodes_chained"] < st["steps"]
    if depth == 1:
        assert st["decodes_ahead"] == 0
    else:
        assert st["steps"] / 2 < st["decodes_ahead"] < st["decodes_chained"]
    # left to itself the loop lies between the two, whatever this host's pace
    assert 0 <= natural["decodes_ahead"] < natural["decodes_chained"]
    assert st["lane_steps_discarded"] == 0 and st["kv_blocks_in_use"] == 0


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_a_dispatched_program_has_its_copy_to_the_host_asked_for_at_once(kind):
    """Both dispatch sites end in ``_enqueue``: the program's output is
    asked to start for the host exactly once, before the program joins
    those in flight (so before any fetch of it), and ``_InFlight`` stays
    a plain record: building one starts nothing."""

    class Spy:
        def __init__(self, out, log):
            self.out, self.log = out, log

        def copy_to_host_async(self):
            self.log.append("copy")
            self.out.copy_to_host_async()

        def is_ready(self):
            return self.out.is_ready()

        def __array__(self, *args, **kwargs):
            import numpy as np

            self.log.append("fetch")
            return np.asarray(self.out)

    engine_mod._InFlight(out=object(), lanes=[], counts={})  # no transfer, no attribute of ``out`` touched

    async def main():
        eng = LLMEngine(_tiny())
        enqueue, logs = eng._enqueue, []

        def spied(prog):
            if prog.decode == (kind == "decode"):
                logs.append(["queued" if any(p is prog for p in eng._inflight) else "new"])
                prog.out = Spy(prog.out, logs[-1])
            return enqueue(prog)

        eng._enqueue = spied
        outs = await _generate(eng, BATCH)
        await eng.stop()
        return outs, logs

    outs, logs = asyncio.run(main())
    assert [len(o) for o in outs] == [m for _, m in BATCH]
    assert len(logs) >= len(BATCH) - (kind == "decode") and all(log == ["new", "copy", "fetch"] for log in logs), logs


def test_the_loop_runs_ahead_only_while_the_device_waits_for_it(monkeypatch):
    """The observation itself, on the engine's clock: of the last
    AHEAD_WINDOW iterations' time outside engine.idle, the share blocked
    in the two fetch phases, against AHEAD_BLOCKED_SHARE; nothing is
    known before an iteration has been clocked, and idle time is no
    time."""
    import types

    eng = LLMEngine(_tiny())
    clock = {"t": 100.0}
    monkeypatch.setattr(engine_mod, "time", types.SimpleNamespace(perf_counter=lambda: clock["t"]))

    def iteration(took_s, blocked_s, idle_s=0.0):
        clock["t"] += took_s + idle_s
        eng._phase_s["engine.idle"] += idle_s
        eng._phase_s["engine.decode.fetch"] += blocked_s / 2
        eng._phase_s["engine.prefill.fetch"] += blocked_s / 2
        eng._note_pace()

    eng._note_pace()
    assert not eng._device_waits()  # nothing clocked yet
    iteration(0.006, 0.0013)  # a fifth blocked: the device waits for the loop
    assert eng._device_waits()
    for _ in range(engine_mod.AHEAD_WINDOW):
        iteration(0.024, 0.017, idle_s=1.0)  # 71% blocked: the device sets the pace
    assert not eng._device_waits()
    for _ in range(engine_mod.AHEAD_WINDOW // 2 - 4):
        iteration(0.006, 0.0004)
    assert not eng._device_waits()  # the window still holds more blocked time than half
    for _ in range(engine_mod.AHEAD_WINDOW // 2 + 4):
        iteration(0.006, 0.0004)
    assert eng._device_waits()
    assert len(eng._pace) == engine_mod.AHEAD_WINDOW + 1


def test_phase_seconds_fit_the_wall_time(batch_run):
    _, _, st, wall_s = batch_run
    for name in ENGINE_SPANS:
        assert st[_key(name) + "_s"] > 0
    assert st["decode_await_s"] >= st["decode_run_s"] > 0
    assert st["prefill_await_s"] >= st["prefill_run_s"] > 0
    in_loop = sum(st[_key(n) + "_s"] for n in LOOP_PHASES)
    assert 0 < in_loop <= wall_s
    # and they leave little of the loop's time unnamed: the run began
    # with the loop's start and ended with the last token
    assert in_loop >= 0.8 * wall_s


def test_phases_are_spans_in_a_profiler_trace(tmp_path):
    """A jax.profiler trace taken around a few steps holds the engine's
    phases on the host plane, where the benchmark's reduction finds them."""
    import jax

    from benchmark import trace_reduce

    async def main():
        eng = LLMEngine(_tiny())
        await _generate(eng, [(5, 3)])  # compile outside the trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        before = eng.stats()
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            await _generate(eng, [(5, 4), (6, 6)])
        finally:
            jax.profiler.stop_trace()
        after = eng.stats()
        await eng.stop()
        return before, after

    before, after = asyncio.run(main())
    planes = trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)))
    spans = trace_reduce.host_spans(planes, ENGINE_SPANS)
    count = {}
    for name, _start, dur in spans:
        assert dur >= 0
        count[name] = count.get(name, 0) + 1
    assert set(count) == set(ENGINE_SPANS)
    steps = after["steps"] - before["steps"]
    assert steps >= 5
    assert (count["engine.decode.run"] == count["engine.decode.build"]
            == count["engine.decode.fetch"] == steps)
    assert count["engine.prefill.run"] == count["engine.prefill.fetch"] == 2


def test_slow_iteration_is_counted_and_logged_by_phase(caplog):
    async def main():
        eng = LLMEngine(_tiny())
        await _generate(eng, [(5, 3)])
        before = eng.stats()
        push, calls = eng._push_metrics, []

        def slow_push(force=False):
            if not calls:
                time.sleep(1.1)
            calls.append(force)
            return push(force)

        eng._push_metrics = slow_push
        with caplog.at_level(logging.WARNING, logger="ray_tpu.serve.llm.engine"):
            caplog.clear()
            await _generate(eng, [(5, 3)])
        after = eng.stats()
        await eng.stop()
        return before, after

    before, after = asyncio.run(main())
    lines = [r.getMessage() for r in caplog.records if "slow iteration" in r.getMessage()]
    assert len(lines) == 1, lines
    by_phase = lines[0].split("ms by phase: ")[1].split()
    assert by_phase[0].startswith("engine.metrics=")  # the largest comes first
    assert float(by_phase[0].split("=")[1]) >= 1100
    assert "waiting=" in lines[0] and "running=" in lines[0] and "at step" in lines[0]
    assert after["stalls"] - before["stalls"] == 1
    assert after["stall_s"] - before["stall_s"] >= 1.1


def test_a_slice_that_compiles_is_no_stall(caplog):
    """A deployment's first calls compile for seconds: an INFO line with
    the phases, and nothing counted."""

    async def main():
        eng = LLMEngine(_tiny())
        await _generate(eng, [(5, 3)])
        jit = eng._prefill_jit

        def slow_jit(*args):
            time.sleep(1.1)
            return jit(*args)

        eng._prefill_jit = slow_jit
        with caplog.at_level(logging.INFO, logger="ray_tpu.serve.llm.engine"):
            caplog.clear()
            await _generate(eng, [(40, 2)])  # a bucket not seen yet: a real compile
        st = eng.stats()
        await eng.stop()
        return st

    st = asyncio.run(main())
    assert st["stalls"] == 0 and st["stall_s"] == 0
    assert not [r for r in caplog.records if "slow iteration" in r.getMessage()]
    lines = [r for r in caplog.records if "llm engine compiled" in r.getMessage()]
    assert len(lines) == 1 and lines[0].levelno == logging.INFO
    assert "engine.prefill.run=" in lines[0].getMessage()
    # and which program compiled, with its split of the compile ledger
    programs = lines[0].getMessage().split("programs, ms: ")[1]
    assert programs.startswith("serve_prefill x1 trace=") and "serve_decode" not in programs
    assert all(f" {part}=" in programs for part in ("lower", "backend_miss", "backend_hit"))


def test_a_full_refill_of_fast_prefills_is_no_stall(monkeypatch):
    """The threshold holds each prefill and each decode step by itself:
    an iteration that admits many prompts is long and no stall."""
    monkeypatch.setattr(engine_mod, "STALL_S", 0.25)

    async def main():
        eng = LLMEngine(_tiny())
        await _generate(eng, [(5, 2)])
        jit = eng._prefill_jit

        def slow_jit(*args):
            time.sleep(0.1)
            return jit(*args)

        eng._prefill_jit = slow_jit
        await _generate(eng, [(5, 2)] * 4)  # four lanes: 0.4 s of prefill in one iteration
        st = eng.stats()
        await eng.stop()
        return st

    st = asyncio.run(main())
    assert st["prefill_await_s"] >= 0.4 and st["stalls"] == 0


def test_an_idle_engine_is_no_stall():
    """The loop waits up to a second for work; that is idle time."""

    async def main():
        eng = LLMEngine(_tiny())
        eng.ensure_started()
        await asyncio.sleep(1.3)
        st = eng.stats()
        await eng.stop()
        return st

    st = asyncio.run(main())
    assert st["stalls"] == 0 and st["idle_s"] >= 1.0 and st["steps"] == 0


# ----------------------------------------------------------------------
# the start, phase by phase, and the compile ledger in stats()
# ----------------------------------------------------------------------
_SETUP_KEYS = [f"{name.replace('.', '_')}_s" for name in engine_mod.SETUP_PHASES]
_COMPILE_KEYS = ("compile_trace_s", "compile_lower_s", "compile_backend_hit_s",
                 "compile_backend_miss_s", "compile_cache_read_s", "compile_cache_hits",
                 "compile_cache_misses", "programs_lowered",
                 "serve_prefill_first_call_s", "serve_decode_first_call_s")


@pytest.mark.parametrize("in_a_worker", [False, True])
def test_setup_phases_tile_the_start_as_spans_of_one_trace(in_a_worker):
    """The phases of a start lie in order, each beginning where the last
    ended, as seconds in stats() and as spans of one trace under
    ``setup.replica``; ``setup.worker`` is there where a worker began
    the set-up (default_worker.main) and a replica's __init__ ended it."""
    from ray_tpu._private import profiling
    from ray_tpu.util import tracing

    tracing.drain_spans()
    t0 = time.time()
    if in_a_worker:
        setup = profiling.begin_setup(at=t0 - 0.25)  # as default_worker.main does, at its entry
        setup.enter("setup.worker")
        setup.leave()  # as Replica.__init__ does, entering
    eng = LLMEngine(_tiny())
    t1 = time.time()
    assert profiling.setup_under_way() is None
    st = eng.stats()
    assert all(isinstance(st[k], float) for k in _SETUP_KEYS + ["engine_ready_at"]), st
    assert (st["setup_worker_s"] >= 0.25) if in_a_worker else (st["setup_worker_s"] == 0.0)
    assert all(st[k] > 0 for k in _SETUP_KEYS[1:])
    assert t0 <= st["engine_ready_at"] <= t1
    spans = [sp for sp in tracing.drain_spans() if sp["name"].startswith("setup.")]
    root = next(sp for sp in spans if sp["name"] == "setup.replica")
    phases = [sp for sp in spans if sp is not root]
    assert [sp["name"] for sp in phases] == list(engine_mod.SETUP_PHASES[0 if in_a_worker else 1:])
    assert {sp["trace_id"] for sp in spans} == {root["trace_id"]}
    assert root["parent_span_id"] is None
    assert {sp["parent_span_id"] for sp in phases} == {root["span_id"]}
    assert len({sp["span_id"] for sp in spans}) == len(spans)
    # in order, no gap and no overlap, from the root's start to its end
    assert [sp["start_time"] for sp in phases[1:]] == [sp["end_time"] for sp in phases[:-1]]
    assert all(sp["end_time"] >= sp["start_time"] for sp in phases)
    assert (root["start_time"], root["end_time"]) == (phases[0]["start_time"], phases[-1]["end_time"])
    assert root["end_time"] == st["engine_ready_at"]
    for sp in phases:
        assert st[sp["name"].replace(".", "_") + "_s"] == pytest.approx(sp["end_time"] - sp["start_time"])


def test_a_start_that_fails_leaves_no_setup_under_way():
    from ray_tpu._private import profiling

    with pytest.raises(ValueError, match="KV pool smaller"):
        LLMEngine(_tiny(num_blocks=4))
    assert profiling.setup_under_way() is None
    assert LLMEngine(_tiny()).stats()["setup_worker_s"] == 0.0


def test_compile_totals_rise_with_a_new_shape_and_rest_on_shapes_seen():
    from ray_tpu._private import profiling

    async def main():
        eng = LLMEngine(_tiny())
        built = eng.stats()
        await _generate(eng, [(5, 3)])
        first = eng.stats()
        await _generate(eng, [(6, 3), (5, 2)])  # the bucket of 8 and the decode step again
        again = eng.stats()
        await eng.stop()
        return built, first, again

    built, first, again = asyncio.run(main())
    assert all(isinstance(built[k], (int, float)) for k in _COMPILE_KEYS)
    # the set-up's own programs (the init's, the pools') are in the ledger already
    assert built["programs_lowered"] > 0 and built["compile_lower_s"] > 0
    assert first["programs_lowered"] >= built["programs_lowered"] + 2
    assert first["serve_prefill_first_call_s"] > 0 and first["serve_decode_first_call_s"] > 0
    assert {k: again[k] for k in _COMPILE_KEYS} == {k: first[k] for k in _COMPILE_KEYS}
    assert again["programs_lowered"] == profiling.compile_totals()["lowerings"]


# the three that move setup_s: the counters AFTER the window (``s.``),
# for the process's totals do not move inside one
_SETUP_BY_HAND = {
    "setup_trace_lower_s": (9.5 + 3.25, "serve plane"),
    "setup_cache_load_s": (11.0, "entry points and runtime"),
    "setup_compile_miss_s": (4.5, "entry points and runtime"),
}


@pytest.mark.parametrize("metric", sorted(_SETUP_BY_HAND))
def test_layer_metric_reads_the_compile_ledger(metric, batch_run):
    import re

    from benchmark import readers, spec

    value, layer = _SETUP_BY_HAND[metric]
    how = spec.load_layer_metric(metric)
    assert how["reader"] == "stats_delta"
    ledger = {"compile_trace_s": 9.5, "compile_lower_s": 3.25, "compile_backend_hit_s": 11.0,
              "compile_backend_miss_s": 4.5}
    ctx = {"values": {}, "stats": {"before": dict(_BEFORE, **ledger), "after": dict(_AFTER, **ledger),
                                   "window_s": 30.0}}
    assert readers.stats_delta(how["args"], ctx) == pytest.approx(value)
    # on a program that lacks the counters (the parent) the metric is left out, not raised
    old = {"before": _BEFORE, "after": _AFTER, "window_s": 30.0}
    assert readers.stats_delta(how["args"], {"values": {}, "stats": old}) is None
    # every name the expression uses is a number of stats()
    for key in re.findall(r"\bs\.(\w+)", how["args"]["expr"]):
        assert isinstance(batch_run[2][key], (int, float)), (metric, key)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == metric)
    assert set(_CELL["steady"] + _CELL["backlog"]) <= set(entry["workloads"])
    assert (entry["layer"], entry["moves"], entry["source"], entry["better"], entry["unit"]) == (
        layer, "setup_s", "program_counter", "lower", "s")


# ----------------------------------------------------------------------
# the benchmark's per-layer metrics over these counters
# ----------------------------------------------------------------------
# stats() at the ends of a 30 s window of 250 steps, as the benchmark's
# replica-side probe passes them on (numbers and strings only)
_BEFORE = {
    "steps": 1000, "max_batch_size": 16, "platform": "tpu", "total_tokens": 9000,
    "joined": 100, "queue_wait_s": 5.0, "prompt_tokens": 20_000, "prefill_bucket_tokens": 28_000,
    "kv_positions_attended": 2_000_000, "kv_positions_gathered": 16_384_000,
    "admit_s": 0.10, "prefill_build_s": 0.05, "prefill_run_s": 0.20, "prefill_await_s": 0.25,
    "prefill_fetch_s": 4.0, "decode_build_s": 2.0, "decode_run_s": 2.5, "decode_await_s": 3.0,
    "decode_fetch_s": 108.0, "emit_s": 0.30, "metrics_s": 0.20, "yield_s": 0.40, "idle_s": 50.0,
    "decodes_chained": 700, "decodes_ahead": 300,
}
_AFTER = {
    "steps": 1250, "max_batch_size": 16, "platform": "tpu", "total_tokens": 12000,
    "joined": 140, "queue_wait_s": 8.0, "prompt_tokens": 28_000, "prefill_bucket_tokens": 39_200,
    "kv_positions_attended": 2_614_400, "kv_positions_gathered": 20_480_000,
    "admit_s": 0.15, "prefill_build_s": 0.07, "prefill_run_s": 0.28, "prefill_await_s": 0.35,
    "prefill_fetch_s": 6.08, "decode_build_s": 2.5, "decode_run_s": 3.125, "decode_await_s": 3.75,
    "decode_fetch_s": 134.0, "emit_s": 0.45, "metrics_s": 0.25, "yield_s": 0.55, "idle_s": 50.5,
    "decodes_chained": 900, "decodes_ahead": 425,
}
# host time a step: admit 0.05 + build 0.5 + await 0.75 (dispatch and
# hop) + emit 0.15 + metrics 0.05 + yield 0.15 = 1.65 s over 250 steps;
# prefill: build 0.02 + await 0.10 + fetch 2.08 = 2.2 s of 30; 200 of
# the 250 decode steps were dispatched while the one before was
# unfetched, 125 while the two before were
_CELL = {"steady": ["gpt2-large.serve.chat-steady"], "backlog": ["gpt2-large.serve.batch-backlog"],
         "moe": ["olmoe-1b-7b.serve.backlog-wide"],
         # the seven other backlog cells' own tests pin their per-layer sets
         # (benchmark/tests/test_*_cell.py; PERF.md section 7)
         "decode_ahead_pct": ["gpt2-large.serve.batch-backlog"]}
_BY_HAND = {
    "queue_wait_ms.steady": 1000 * 3.0 / 40,
    "prefill_share_pct.steady": 100 * 2.2 / 30,
    "prefill_share_pct.backlog": 100 * 2.2 / 30,
    "host_ms_per_step.steady": 6.6,
    "host_ms_per_step.backlog": 6.6,
    "kv_gather_useful_pct.steady": 15.0,
    "kv_gather_useful_pct.backlog": 15.0,
    "prefill_pad_ratio.steady": 1.4,
    "prefill_pad_ratio.backlog": 1.4,
    "decode_overlap_pct.steady": 80.0,
    "decode_overlap_pct.backlog": 80.0,
    "decode_overlap_pct.moe": 80.0,
    "decode_ahead_pct": 50.0,
    "decode_ahead_pct.steady": 50.0,
}


@pytest.mark.parametrize("metric", sorted(_BY_HAND))
def test_layer_metric_reads_the_engine_counters(metric):
    from benchmark import readers, spec

    how = spec.load_layer_metric(metric)
    assert how["reader"] == "stats_delta"
    ctx = {"values": {}, "stats": {"before": _BEFORE, "after": _AFTER, "window_s": 30.0}}
    assert readers.stats_delta(how["args"], ctx) == pytest.approx(_BY_HAND[metric])
    # on a program that lacks the counters the metric is left out, not raised
    old = {"before": {"steps": 1000}, "after": {"steps": 1250}, "window_s": 30.0}
    assert readers.stats_delta(how["args"], {"values": {}, "stats": old}) is None
    # and BENCHMARK.json reports it in its cells, under the layer's name
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == metric)
    assert set(_CELL[metric.rsplit(".", 1)[-1]]) <= set(entry["workloads"])
    assert (entry["layer"], entry["source"]) == ("serve plane", "program_counter")


def test_layer_metric_counters_are_keys_of_stats(batch_run):
    """The names the expressions use are names stats() has."""
    import re

    from benchmark import spec

    _, _, st, _ = batch_run
    for metric in _BY_HAND:
        expr = spec.load_layer_metric(metric)["args"]["expr"]
        for key in re.findall(r"\bd\.(\w+)", expr):
            assert isinstance(st[key], (int, float)), (metric, key)

"""The runtime's share of a token: the stamps a request carries from the
handle to the engine (``sent_at``, ``rx_at``, ``t_submit``, ``t_join``,
``t_first_token``), the emit time a token carries to the coroutine that
takes it, the replica's two dataplane threads, and the interpreter's
collections, each as a sum in ``stats()``; and the benchmark's per-layer
metrics that read them.

CPU and the ``tiny`` preset.  Counts are exact and sums are held to the
stamps they were taken from; how long anything took is the chip's to say.
"""

import asyncio
import gc
import json
import os
import re
import time

import pytest

from ray_tpu import serve
from ray_tpu.serve._private.request_context import _set_request_meta, get_request_meta
from ray_tpu.serve.llm import LLMConfig, LLMEngine
from ray_tpu.serve.llm.deployment import LLMServer
from ray_tpu.serve.llm.engine import FINISHED

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
INGRESS = ("submitted", "ingress_wire_s", "ingress_loop_s")
# the seven sums of seconds this file is about
SUMS = ("ingress_wire_s", "ingress_loop_s", "join_to_first_token_s", "egress_loop_s",
        "egress_tx_s", "tx_busy_s", "rx_busy_s")
# (prompt tokens, output tokens): more requests than the four lanes
BATCH = [(3, 5), (8, 1), (9, 7), (17, 4), (5, 6), (12, 3)]


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


# ----------------------------------------------------------------------
# the engine and LLMServer in one process, the request context set by hand
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def handled_run():
    """BATCH through ``LLMServer.generate`` (the last one through
    ``__call__``), each under a request context that carries what a
    handle and a replica would have stamped; every ``_emit`` and every
    ``token_taken`` recorded beside the engine's own sums."""

    async def main():
        srv = LLMServer(_tiny())
        eng = srv.engine
        reqs, emitted, taken, waits = [], {}, {}, []
        add, emit, take = eng.add_request, eng._emit, eng.token_taken

        async def add_request(*a, **kw):
            reqs.append(await add(*a, **kw))
            return reqs[-1]

        def _emit(req, token, now):
            emitted.setdefault(req.request_id, []).append(now)
            return emit(req, token, now)

        def token_taken(req):
            emitted_at = req.emit_times[0]
            taken.setdefault(req.request_id, []).append(emitted_at)
            before, t0 = eng._counts["egress_loop_s"], time.time()
            take(req)
            # what the sum grew by, and the two instants it was taken between
            waits.append((eng._counts["egress_loop_s"] - before, t0 - emitted_at, time.time() - emitted_at))

        eng.add_request, eng._emit, eng.token_taken = add_request, _emit, token_taken

        async def client(i, n, m):
            sent = time.time()
            await asyncio.sleep(0.002 * (i + 1))  # the wire
            _set_request_meta({"tenant": "default", "sent_at": sent, "rx_at": time.time()})
            await asyncio.sleep(0.001)  # the hand-over to the loop
            payload = {"prompt": list(range(1, n + 1)), "max_tokens": m}
            if i == len(BATCH) - 1:
                return (await srv(payload))["tokens"]
            return [ev["token"] async for ev in srv.generate(payload) if "token" in ev]

        outs = await asyncio.gather(*[client(i, n, m) for i, (n, m) in enumerate(BATCH)])
        st = srv.stats()
        await eng.stop()
        return outs, reqs, st, emitted, taken, waits

    return asyncio.run(main())


def test_stamps_are_in_order_for_every_finished_request(handled_run):
    outs, reqs, st, *_ = handled_run
    assert [len(o) for o in outs] == [m for _, m in BATCH] and len(reqs) == len(BATCH)
    for r in reqs:
        assert 0 < r.t_sent <= r.t_rx <= r.t_submit <= r.t_join <= r.t_first_token <= r.t_done
    assert st["kv_blocks_in_use"] == 0


def test_each_sum_is_the_sum_of_its_differences(handled_run):
    """Conservation, to a microsecond: a counter is what its stamps say."""
    _, reqs, st, emitted, taken, waits = handled_run
    assert st["submitted"] == st["first_tokens"] == len(BATCH)
    assert st["ingress_wire_s"] == pytest.approx(sum(r.t_rx - r.t_sent for r in reqs), abs=1e-6)
    assert st["ingress_loop_s"] == pytest.approx(sum(r.t_submit - r.t_rx for r in reqs), abs=1e-6)
    assert st["join_to_first_token_s"] == pytest.approx(
        sum(r.t_first_token - r.t_join for r in reqs), abs=1e-6)
    # the wire and the hand-over are at least the sleeps the clients took
    assert st["ingress_wire_s"] >= sum(0.002 * (i + 1) for i in range(len(BATCH)))
    assert st["ingress_loop_s"] >= 0.001 * len(BATCH)
    # every token was taken once, with ITS emit time, in order
    assert st["tokens_out"] == st["total_tokens"] == sum(m for _, m in BATCH) == len(waits)
    assert taken == emitted
    assert all(not r.emit_times for r in reqs)
    for grew_by, at_least, at_most in waits:  # now minus ITS emit time, to a sum's own rounding
        assert 0 <= at_least - 1e-9 <= grew_by <= at_most + 1e-9
    assert st["egress_loop_s"] == pytest.approx(sum(w[0] for w in waits), abs=1e-6)
    # no channel endpoint in this process: the dataplane's five read zero
    for key in ("frames_rx", "frames_tx", "rx_busy_s", "tx_busy_s", "egress_tx_s"):
        assert st[key] == 0
    for key in SUMS:
        assert st[key] >= 0


def test_skewed_clocks_count_as_zero_not_as_negative():
    """A sender whose clock runs ahead (two hosts) moves the sums by 0."""

    async def main():
        eng = LLMEngine(_tiny())
        _set_request_meta({"sent_at": time.time() + 60.0, "rx_at": time.time() + 30.0})
        req = await eng.add_request([1, 2, 3], max_tokens=2)
        await _drain(req)
        st = eng.stats()
        await eng.stop()
        return st

    st = asyncio.run(main())
    assert (st["submitted"], st["ingress_wire_s"], st["ingress_loop_s"]) == (1, 0.0, 0.0)


def test_a_request_without_sent_at_moves_no_ingress_counter(monkeypatch):
    """A direct ``engine.add_request`` has no way in to measure: no
    counter of it moves and no ``serve.ingress`` is recorded; a request
    with the stamps gets the span under its root, which starts with it."""
    from ray_tpu.util import tracing

    spans = []
    monkeypatch.setattr(
        tracing, "record_span",
        lambda name, start, end, attrs=None, context=None: spans.append((name, start, end, attrs, context)))

    async def main():
        eng = LLMEngine(_tiny())
        direct = await eng.add_request([1, 2, 3], max_tokens=3)
        await _drain(direct)
        before = eng.stats()
        sent = time.time() - 0.25
        _set_request_meta({"sent_at": sent, "rx_at": sent + 0.2})
        handled = await eng.add_request([4, 5, 6], max_tokens=3)
        await _drain(handled)
        after = eng.stats()
        await eng.stop()
        return direct, handled, before, after

    direct, handled, before, after = asyncio.run(main())
    assert (direct.t_sent, direct.t_rx) == (0.0, 0.0)
    assert [before[k] for k in INGRESS] == [0, 0.0, 0.0]
    assert before["first_tokens"] == 1 and before["join_to_first_token_s"] > 0
    assert before["tokens_out"] == 0  # nobody took its tokens on a client's behalf
    assert after["submitted"] == 1 and after["ingress_wire_s"] == pytest.approx(0.2)

    def of(req):
        return {s[0]: s for s in spans if s[4][0] == req.trace[0]}

    assert set(of(direct)) == {"serve.request", "serve.queue", "serve.prefill", "serve.decode"}
    assert of(direct)["serve.request"][1] == direct.t_submit
    mine = of(handled)
    assert set(mine) == {"serve.request", "serve.ingress", "serve.queue", "serve.prefill", "serve.decode"}
    root = mine["serve.request"]
    assert root[1] == handled.t_sent and root[4][1] == handled.trace[1]
    name, start, end, attrs, (trace_id, span_id, parent) = mine["serve.ingress"]
    assert (start, end, parent) == (handled.t_sent, handled.t_submit, handled.trace[1])
    assert attrs["wire_s"] == pytest.approx(0.2)
    assert attrs["wire_s"] + attrs["loop_s"] == pytest.approx(end - start)
    # the chain has no hole: ingress ends where the queue begins
    assert mine["serve.queue"][1] == end


def test_the_handle_stamps_a_copy_and_the_replica_stamps_what_lacks_rx_at():
    """``sent_at`` goes into a copy a call, never into the handle's own
    identity; on the RPC path the replica stamps ``rx_at`` at the top of
    its handlers and leaves one the dataplane set alone."""
    from ray_tpu.serve._private.replica import Replica
    from ray_tpu.serve.handle import DeploymentHandle

    class Router:
        def route(self, method, args, kwargs, model_id, request_meta=None):
            self.meta = request_meta
            return None, "r1"

        route_stream = route

    h = DeploymentHandle("dep", request_meta={"tenant": "acme"})
    h._router = Router()
    t0 = time.time()
    h.remote({})
    assert h._request_meta == {"tenant": "acme"}
    assert h._router.meta["tenant"] == "acme" and t0 <= h._router.meta["sent_at"] <= time.time()
    anonymous = DeploymentHandle("dep", stream=True)
    anonymous._router = first = Router()
    anonymous.remote({})
    assert set(first.meta) == {"sent_at"} and anonymous._request_meta is None

    def whoami(payload):
        return get_request_meta()

    async def main():
        rep = Replica("r1", "dep", (whoami, (), {}), None, 10)
        sent = {"sent_at": time.time()}
        over_rpc = await rep.handle_request("__call__", ({},), {}, "", sent)
        by_rx_thread = await rep.handle_request("__call__", ({},), {}, "", dict(sent, rx_at=1.5))
        streamed = [m async for m in rep.handle_request_stream("__call__", ({},), {}, "", sent)]
        nobody = await rep.handle_request("__call__", ({},), {})
        return sent, over_rpc, by_rx_thread, streamed[0], nobody

    sent, over_rpc, by_rx_thread, streamed, nobody = asyncio.run(main())
    assert set(sent) == {"sent_at"}  # the caller's dict is not written to
    assert sent["sent_at"] <= over_rpc["rx_at"] <= time.time()
    assert sent["sent_at"] <= streamed["rx_at"] <= time.time()
    assert by_rx_thread["rx_at"] == 1.5
    assert nobody is None


# ----------------------------------------------------------------------
# the interpreter's pauses
# ----------------------------------------------------------------------
def test_collections_are_counted_while_the_loop_runs_and_the_hook_goes_with_it():
    hooks = list(gc.callbacks)

    async def main():
        eng = LLMEngine(_tiny())
        assert gc.callbacks == hooks  # building an engine registers nothing
        req = await eng.add_request([1, 2, 3], max_tokens=6)
        await req.out.get()  # the loop runs
        assert len(gc.callbacks) == len(hooks) + 1
        before = eng.stats()
        gc.collect()
        gc.collect(0)
        after = eng.stats()
        await _drain(req)
        await eng.stop()
        stopped = list(gc.callbacks)
        # a second life of the same engine watches again, once
        req = await eng.add_request([1, 2, 3], max_tokens=2)
        await _drain(req)
        again = len(gc.callbacks)
        await eng.stop()
        return before, after, stopped, again

    before, after, stopped, again = asyncio.run(main())
    assert after["gc_collections"] - before["gc_collections"] >= 2
    assert after["gc_full_collections"] - before["gc_full_collections"] == 1
    assert after["gc_pause_s"] > before["gc_pause_s"] >= 0
    assert stopped == hooks and again == len(hooks) + 1
    assert gc.callbacks == hooks


def test_an_engine_nobody_stopped_leaves_no_hook_behind():
    """The loop task ends with its event loop (``asyncio.run`` cancels
    what is pending), and the hook with the task."""
    hooks = list(gc.callbacks)

    async def main():
        eng = LLMEngine(_tiny())
        await _drain(await eng.add_request([1, 2, 3], max_tokens=2))
        assert len(gc.callbacks) == len(hooks) + 1

    asyncio.run(main())
    assert gc.callbacks == hooks


def test_a_collection_is_a_span_and_a_stamp_lies_on_the_trace_s_axis(tmp_path):
    """``engine.gc`` is a TraceAnnotation of a traced run, and a
    ``time.time()`` stamp can be laid on the trace's axis: the profiler
    stamps host events on the epoch clock and stores them less the
    ``profile_start_time`` of its ``Task Environment`` plane."""
    import jax
    from jax.profiler import ProfileData

    from benchmark import trace_reduce

    async def main():
        eng = LLMEngine(_tiny())
        await _drain(await eng.add_request([1, 2, 3], max_tokens=3))  # compile outside the trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            req = await eng.add_request([1, 2, 3], max_tokens=4)
            await req.out.get()
            before = time.time()
            gc.collect()
            after = time.time()
            with jax.profiler.TraceAnnotation("test.stamp"):
                pass
            await _drain(req)
        finally:
            jax.profiler.stop_trace()
        await eng.stop()
        return before, after

    before, after = asyncio.run(main())
    path = trace_reduce.find_xplane(str(tmp_path))
    planes = trace_reduce.load(path)
    env = next(p for p in ProfileData.from_file(path).planes if p.name == "Task Environment")
    origin_ns = dict(env.stats)["profile_start_time"]
    # one annotation and one time.time() taken right before it, under a millisecond apart
    (_, stamped_ns, _), = trace_reduce.host_spans(planes, ("test.stamp",))
    assert 0 <= (origin_ns + stamped_ns) / 1e9 - after < 1e-3
    full = [(start, dur) for name, start, dur in trace_reduce.host_spans(planes, ("engine.gc",)) if dur > 0]
    assert full
    # the one full collection asked for between the two stamps is among them
    inside = [(origin_ns + start) / 1e9 for start, dur in full
              if before - 1e-3 <= (origin_ns + start) / 1e9 <= after + 1e-3]
    assert inside, (before, after, [(origin_ns + s) / 1e9 for s, _ in full])


# ----------------------------------------------------------------------
# the benchmark's per-layer metrics over these counters
# ----------------------------------------------------------------------
_BEFORE = {
    "steps": 1000, "submitted": 100, "ingress_wire_s": 0.10, "ingress_loop_s": 0.05,
    "first_tokens": 98, "join_to_first_token_s": 1.0, "tokens_out": 9000, "egress_loop_s": 0.9,
    "frames_tx": 9200, "egress_tx_s": 1.84, "tx_busy_s": 0.5, "gc_pause_s": 0.2,
}
_AFTER = {
    "steps": 1250, "submitted": 140, "ingress_wire_s": 0.14, "ingress_loop_s": 0.07,
    "first_tokens": 138, "join_to_first_token_s": 1.4, "tokens_out": 12000, "egress_loop_s": 1.2,
    "frames_tx": 12300, "egress_tx_s": 2.46, "tx_busy_s": 1.1, "gc_pause_s": 0.35,
}
# 40 requests: 0.04 s of wire, 0.02 s of loop, 0.4 s from join to first
# token; 3,000 tokens waited 0.3 s for their coroutines, 3,100 frames
# 0.62 s for their commit; the tx thread worked 0.6 s of 30, and 0.15 s
# went to collections
_BY_HAND = {
    "ingress_wire_ms.steady": (1.0, "entry points and runtime", "ttft_p90_ms"),
    "ingress_loop_ms.steady": (0.5, "entry points and runtime", "ttft_p90_ms"),
    "join_to_first_token_ms.steady": (10.0, "serve plane", "ttft_p90_ms"),
    "egress_ms.steady": (0.1 + 0.2, "entry points and runtime", "itl_p95_ms"),
    "tx_busy_pct.backlog": (2.0, "entry points and runtime", "serve_out_tokens_per_s"),
    "gc_pause_pct.backlog": (0.5, "entry points and runtime", "serve_out_tokens_per_s"),
}
_CELL = {"steady": "gpt2-large.serve.chat-steady", "backlog": "gpt2-large.serve.batch-backlog"}


@pytest.mark.parametrize("metric", sorted(_BY_HAND))
def test_layer_metric_reads_the_request_path_counters(metric, handled_run):
    from benchmark import readers, spec

    value, layer, moves = _BY_HAND[metric]
    how = spec.load_layer_metric(metric)
    assert how["reader"] == "stats_delta"
    ctx = {"values": {}, "stats": {"before": _BEFORE, "after": _AFTER, "window_s": 30.0}}
    assert readers.stats_delta(how["args"], ctx) == pytest.approx(value)
    # on a program that lacks the counters (the parent) the metric is
    # left out of the line, not raised
    old = {"before": {"steps": 1000}, "after": {"steps": 1250}, "window_s": 30.0}
    assert readers.stats_delta(how["args"], {"values": {}, "stats": old}) is None
    # every name the expression uses is a number of LLMServer.stats()
    st = handled_run[2]
    for key in re.findall(r"\bd\.(\w+)", how["args"]["expr"]):
        assert isinstance(st[key], (int, float)), (metric, key)
    # and BENCHMARK.json reports it in its cell, under its layer's name
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == metric)
    assert _CELL[metric.rsplit(".", 1)[1]] in entry["workloads"]
    assert (entry["layer"], entry["moves"], entry["source"]) == (layer, moves, "program_counter")


# ----------------------------------------------------------------------
# through serve.run: the handle, the router, the replica, both ways in
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def serve_cluster(ray_cluster):
    yield ray_cluster
    try:
        serve.shutdown()
    except Exception:  # noqa: BLE001
        pass


def _stream_all(handle, batch, limit_s=120.0):
    """Every request of ``batch`` as an open stream at once, polled from
    this thread until each has ended or the time limit is over."""
    from ray_tpu._private import retry

    deadline = time.time() + limit_s
    idle = retry.STREAM_POLL.start()
    gens = [handle.options(stream=True).generate.remote(
        {"prompt": list(range(1, n + 1)), "max_tokens": m}) for n, m in batch]
    events = [[] for _ in gens]
    live = set(range(len(gens)))
    while live:
        assert time.time() < deadline, f"streams {sorted(live)} still open after {limit_s} s"
        got = False
        for i in sorted(live):
            try:
                ev = gens[i].try_next()
            except StopIteration:
                live.discard(i)
                continue
            if ev is not None:
                events[i].append(ev)
                got = True
        if not got:
            time.sleep(idle.next_delay())
    return events


@pytest.mark.parametrize("dataplane", [True, False], ids=["dataplane", "rpc"])
def test_counters_through_serve_run(serve_cluster, monkeypatch, dataplane):
    """``submitted`` is the requests sent, ``tokens_out`` the tokens the
    client received, ``frames_tx`` those and each stream's summary and
    end; over the RPC path the same but for the dataplane's five, which
    stay at zero."""
    from ray_tpu.serve import llm

    # the router lives in this process and reads the switch a call
    monkeypatch.setenv("RAY_TPU_serve_channel_dataplane", "1" if dataplane else "0")
    name = "llm_path_dp" if dataplane else "llm_path_rpc"
    handle = serve.run(llm.build_app(_tiny(name=name)), name=name + "_app")
    try:
        events = _stream_all(handle, BATCH)
        tokens = [[e["token"] for e in evs if "token" in e] for evs in events]
        assert [len(t) for t in tokens] == [m for _, m in BATCH]
        assert all(evs[-1].get("done") for evs in events)
        st = handle.stats.remote().result(timeout=60)
        received = sum(len(t) for t in tokens)
        assert st["submitted"] == st["first_tokens"] == len(BATCH)
        assert st["tokens_out"] == st["total_tokens"] == received
        for key in SUMS:
            assert st[key] >= 0, key
        assert st["ingress_wire_s"] > 0 and st["join_to_first_token_s"] > 0
        if dataplane:
            # a stream is its tokens, its summary and its end; the stats
            # call's own frame was read before it ran, its answer not yet written
            assert st["frames_tx"] == received + 2 * len(BATCH)
            assert st["frames_rx"] == len(BATCH) + 1
            assert st["egress_tx_s"] >= st["tx_busy_s"] > 0 and st["rx_busy_s"] > 0
        else:
            assert [st[k] for k in ("frames_tx", "frames_rx", "egress_tx_s", "tx_busy_s", "rx_busy_s")] == [0] * 5
        # a one-shot call is a request like any other, its tokens taken server-side
        out = handle.remote({"prompt": [1, 2, 3], "max_tokens": 5}).result(timeout=60)
        assert out["num_tokens"] == 5
        st2 = handle.stats.remote().result(timeout=60)
        assert st2["submitted"] == len(BATCH) + 1 and st2["tokens_out"] == received + 5
        if dataplane:
            assert st2["frames_tx"] == st["frames_tx"] + 2  # the first stats answer, the one-shot's
    finally:
        serve.delete(name)

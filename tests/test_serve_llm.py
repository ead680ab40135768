"""LLM serving plane: paged KV cache accounting, continuous batching,
decode parity, autoscaling, load shedding, chaos replica-kill.

Reference test model: vLLM engine tests + ray serve autoscaling tests,
scaled to CI size.  Engine-level tests run without a cluster (asyncio
only); the cluster tests ride the shared module fixture.
"""

import asyncio
import json
import threading
import time
import urllib.error
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve.exceptions import RequestShedError
from ray_tpu.serve.llm import BlockManager, LLMConfig, LLMEngine
from ray_tpu.serve.llm.engine import FINISHED
from ray_tpu.serve.llm.kv_cache import NoFreeBlocksError


@pytest.fixture(scope="module")
def serve_cluster(ray_cluster):
    yield ray_cluster
    serve.shutdown()


def _tiny(**kw) -> LLMConfig:
    base = dict(model="tiny", max_batch_size=4, num_blocks=64, block_size=8,
                default_max_tokens=8)
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
# block manager: pure accounting
# ----------------------------------------------------------------------
def test_block_manager_accounting():
    bm = BlockManager(num_blocks=8, block_size=4)
    assert bm.free_blocks == 7  # block 0 reserved
    bm.allocate("a", 10)  # 3 blocks
    bm.allocate("b", 4)  # 1 block
    assert bm.blocks_in_use == 4
    # scratch block 0 is never handed out
    bm.advance("a", 10)
    assert all(bm.phys_index("a", p) >= bm.block_size for p in range(10))
    # growth beyond the reservation is refused, not silently corrupting
    with pytest.raises(NoFreeBlocksError):
        bm.advance("a", 3)
    # the pool bound is enforced
    with pytest.raises(NoFreeBlocksError):
        bm.allocate("c", 100)
    assert bm.free("a") == 3
    assert bm.free("a") == 0  # idempotent
    bm.free("b")
    assert bm.blocks_in_use == 0
    assert bm.leak_report()["total_allocs"] == bm.leak_report()["total_frees"]


def test_block_manager_phys_indices_padding():
    bm = BlockManager(num_blocks=8, block_size=4)
    bm.allocate("s", 6)
    bm.advance("s", 6)
    idx = bm.phys_indices("s", 6, 12)
    assert list(idx[6:]) == [0] * 6  # padded with the scratch slot
    # positions within one block are contiguous
    assert idx[1] == idx[0] + 1


def test_block_manager_block_table_padding():
    bm = BlockManager(num_blocks=8, block_size=4)
    bm.allocate("a", 3)
    bm.allocate("s", 10)  # 3 blocks, reserved whole at admission
    bm.advance("s", 6)
    table = bm.block_table("s", 5)
    assert table.dtype.name == "int32" and table.shape == (5,)
    assert list(table[3:]) == [0, 0]  # padded with the scratch block
    assert 0 not in table[:3] and len(set(table[:3])) == 3
    # the table and the position -> slot mapping agree on every position
    for pos in range(6):
        assert bm.phys_index("s", pos) == table[pos // 4] * 4 + pos % 4
    assert not set(table[:3]) & set(bm.block_table("a", 5)[:1])


# ----------------------------------------------------------------------
# engine: generation, parity, continuous batching, cancel, shed
# ----------------------------------------------------------------------
def test_engine_greedy_matches_full_forward():
    """The paged prefill/decode path must produce the SAME greedy tokens
    as re-running the full model over the growing sequence."""
    import jax

    from ray_tpu.models import gpt2

    async def main():
        eng = LLMEngine(_tiny(temperature=0.0))
        prompt = [3, 1, 4, 1, 5]
        req = await eng.add_request(prompt, max_tokens=6)
        toks = await _drain(req)
        await eng.stop()
        return eng, toks

    eng, toks = asyncio.run(main())
    cfg = eng.model_cfg
    params = gpt2.init_params(cfg, rng=jax.random.PRNGKey(eng.config.seed))
    import jax.numpy as jnp

    oracle = gpt2.generate_greedy(params, cfg, jnp.asarray([[3, 1, 4, 1, 5]]), 6)
    assert toks == [int(t) for t in oracle[0]], (toks, oracle)


def test_engine_no_leak_after_mixed_requests():
    async def main():
        eng = LLMEngine(_tiny())
        reqs = [
            await eng.add_request([1 + i, 2, 3], max_tokens=3 + (i % 5))
            for i in range(12)
        ]
        outs = await asyncio.gather(*[_drain(r) for r in reqs])
        for r, out in zip(reqs, outs):
            assert len(out) == r.max_tokens
            assert r.finish_reason == "length"
        report = eng.bm.leak_report()
        await eng.stop()
        return report

    report = asyncio.run(main())
    assert report["blocks_in_use"] == 0
    assert report["live_sequences"] == 0
    assert report["total_allocs"] == 12
    assert report["total_frees"] == 12


def test_engine_continuous_batch_join_at_step_boundary():
    """A late request must join the RUNNING batch at a step boundary and
    decode concurrently — not wait for the batch to drain."""

    async def main():
        eng = LLMEngine(_tiny(max_batch_size=2))
        long_req = await eng.add_request([1, 2], max_tokens=60)
        # let the long request get well into decode
        while long_req.generated < 5:
            await asyncio.sleep(0.01)
        late = await eng.add_request([3, 4], max_tokens=5)
        await asyncio.gather(_drain(long_req), _drain(late))
        report = eng.bm.leak_report()
        await eng.stop()
        return long_req, late, report

    long_req, late, report = asyncio.run(main())
    assert late.join_step < long_req.finish_step, (
        f"late joined at step {late.join_step}, long finished at "
        f"{long_req.finish_step} — no in-flight join happened"
    )
    assert late.finish_step <= long_req.finish_step
    assert report["blocks_in_use"] == 0


def test_engine_cancel_frees_blocks():
    async def main():
        eng = LLMEngine(_tiny())
        # cancel while WAITING (tiny batch keeps it queued)
        eng2 = LLMEngine(_tiny(max_batch_size=1))
        a = await eng2.add_request([1], max_tokens=200)
        b = await eng2.add_request([2], max_tokens=200)
        while a.generated < 1:
            await asyncio.sleep(0.01)
        assert b.slot < 0  # still waiting behind a
        eng2.cancel(b.request_id)
        ev = await b.out.get()
        assert ev is FINISHED
        assert b.finish_reason == "cancelled"
        # cancel while RUNNING (disconnect path: generator finally)
        while a.generated < 3:
            await asyncio.sleep(0.01)
        eng2.cancel(a.request_id)
        await _drain(a)
        # cancel settles at the next step boundary
        deadline = time.monotonic() + 5
        while eng2.bm.blocks_in_use and time.monotonic() < deadline:
            await asyncio.sleep(0.02)
        r2 = eng2.bm.leak_report()
        await eng2.stop()
        await eng.stop()
        return r2

    report = asyncio.run(main())
    assert report["blocks_in_use"] == 0
    assert report["live_sequences"] == 0


def test_engine_sheds_past_queue_bound():
    async def main():
        eng = LLMEngine(_tiny(max_batch_size=1, max_queue=2))
        first = await eng.add_request([0], max_tokens=120)
        while first.generated < 1:  # occupies the single lane
            await asyncio.sleep(0.01)
        held = [first] + [await eng.add_request([i], max_tokens=120) for i in (1, 2)]
        with pytest.raises(RequestShedError):
            await eng.add_request([9], max_tokens=4)
        for r in held:
            eng.cancel(r.request_id)
        for r in held:
            await _drain(r)
        await eng.stop()
        return eng.bm.leak_report()

    report = asyncio.run(main())
    assert report["blocks_in_use"] == 0


def test_engine_kv_pool_admission_blocks_then_completes():
    """When the pool can't hold another sequence the head-of-line waits
    (no overtaking) and is admitted once completions free blocks."""

    async def main():
        # pool: 15 usable blocks * 4 = 60 slots; each request needs
        # 2 + 30 tokens -> 8 blocks, so only one fits at a time
        eng = LLMEngine(LLMConfig(model="tiny", max_batch_size=4,
                                  num_blocks=16, block_size=4,
                                  max_model_len=32))
        a = await eng.add_request([1, 2], max_tokens=30)
        b = await eng.add_request([3, 4], max_tokens=30)
        while a.generated < 2:
            await asyncio.sleep(0.01)
        assert b.slot < 0  # parked on KV capacity, not a free lane
        out_a, out_b = await asyncio.gather(_drain(a), _drain(b))
        assert len(out_a) == 30 and len(out_b) == 30
        report = eng.bm.leak_report()
        await eng.stop()
        return report

    report = asyncio.run(main())
    assert report["blocks_in_use"] == 0


# ----------------------------------------------------------------------
# engine: one decode step in flight (docs/serving.md "What a step is
# made of"): step n+1 is dispatched on step n's tokens before they are
# fetched, so an end by eos_token or cancel() is found out a step late
# ----------------------------------------------------------------------
async def _one_at_a_time(config, batch):
    eng = LLMEngine(config)
    outs = []
    for prompt, n in batch:
        outs.append(await _drain(await eng.add_request(prompt, max_tokens=n)))
    stats = eng.stats()
    await eng.stop()
    return outs, stats


@pytest.mark.parametrize("model", ["tiny", "olmoe_tiny"])
def test_engine_mixed_batch_equals_one_request_at_a_time(model):
    """Greedy tokens do not depend on what shares the batch, on when a
    request joined, or on the step that is in flight while it does."""
    config = _tiny(model=model, max_batch_size=3, temperature=0.0)
    batch = [([3, 1, 4, 1, 5], 9), ([2, 7], 1), ([1] * 9, 14), ([8, 8, 8], 4),
             ([5, 6, 7, 8, 9, 10], 22), ([4, 2], 6), ([9] * 17, 2)]

    async def mixed():
        eng = LLMEngine(config)
        reqs = [await eng.add_request(p, max_tokens=n) for p, n in batch[:4]]  # one more than lanes
        while reqs[2].generated < 3:
            await asyncio.sleep(0.005)
        reqs += [await eng.add_request(p, max_tokens=n) for p, n in batch[4:]]  # join mid-stream
        outs = await asyncio.gather(*[_drain(r) for r in reqs])
        stats = eng.stats()
        await eng.stop()
        return outs, stats

    outs, st = asyncio.run(mixed())
    alone, st_alone = asyncio.run(_one_at_a_time(config, batch))
    assert outs == alone
    assert [len(o) for o in outs] == [n for _, n in batch]
    for stats in (st, st_alone):
        assert stats["lane_steps_discarded"] == 0  # an end by length is known a step ahead
        assert stats["kv_leak_report"]["blocks_in_use"] == 0
    assert 0 < st["decodes_chained"] <= st["steps"] < st_alone["steps"]


@pytest.mark.parametrize("depth", [1, 2])
def test_engine_successor_joins_before_the_last_token_is_fetched(depth, hold_depth):
    """A lane that ends by length is known a step ahead: the next
    request takes it while the step with the last token is in flight
    (the last two, where the loop runs a step ahead), so the lane stands
    empty for no step; FINISHED still follows the last token, and a lane
    never decodes for two requests at once."""

    async def main():
        eng = LLMEngine(_tiny(max_batch_size=1, temperature=0.0))
        hold_depth(eng, depth)
        a = await eng.add_request([1, 2, 3], max_tokens=6)
        b = await eng.add_request([4, 5], max_tokens=4)
        outs = await asyncio.gather(_drain(a), _drain(b))
        stats = eng.stats()
        await eng.stop()
        return a, b, outs, stats

    a, b, outs, st = asyncio.run(main())
    alone, _ = asyncio.run(_one_at_a_time(_tiny(max_batch_size=1, temperature=0.0),
                                          [([1, 2, 3], 6), ([4, 5], 4)]))
    assert outs == alone
    assert b.join_step == a.finish_step - depth  # joined with a's last steps dispatched, not fetched
    assert st["steps"] == 5 + 3 and st["lane_steps_discarded"] == 0
    assert st["decodes_chained"] == st["steps"] - 1  # b's prefill did not break the chain
    assert st["decodes_ahead"] == (st["steps"] - 2) * (depth - 1)  # nor the second step behind it
    assert st["kv_leak_report"]["blocks_in_use"] == 0


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("at", [0, 4])
def test_engine_eos_ends_the_request_a_lane_step_late(at, depth, hold_depth):
    """The request ends at eos_token and nothing after it is emitted,
    though the next step (the next two, where the loop runs a step
    ahead) was dispatched with its lane: those tokens are dropped and
    counted.  ``at`` 0: the prefill's own token ends it."""
    prompt, n = [3, 1, 4, 1, 5], 12

    async def main():
        (free,), _ = await _one_at_a_time(_tiny(temperature=0.0), [(prompt, n)])
        eos = free[at]
        eng = LLMEngine(_tiny(temperature=0.0, eos_token=eos))
        hold_depth(eng, depth)
        req = await eng.add_request(prompt, max_tokens=n)
        other = await eng.add_request([2, 7, 1], max_tokens=n)
        toks, others = await asyncio.gather(_drain(req), _drain(other))
        stats = eng.stats()
        await eng.stop()
        return free, eos, req, toks, others, stats

    free, eos, req, toks, others, st = asyncio.run(main())
    assert toks == free[:free.index(eos) + 1] and len(toks) < n
    assert req.finish_reason == "eos" and req.generated == len(toks)
    # a lane-step for each step in flight behind the one that brought eos
    assert depth <= st["lane_steps_discarded"] <= depth * (1 + (eos in others))
    assert st["total_tokens"] == len(toks) + len(others)  # a dropped token is not counted
    assert st["kv_leak_report"]["blocks_in_use"] == 0
    assert st["kv_leak_report"]["live_sequences"] == 0


@pytest.mark.parametrize("depth", [1, 2])
def test_engine_cancel_and_stop_with_a_step_in_flight(depth, hold_depth):
    """cancel() of a running request and stop() while a jit call that
    donates the pool is under way, with one decode step in flight or
    two: blocks balance to zero, the engine stays bound to live buffers,
    and it serves again after a restart."""
    prompt, n = [3, 1, 4], 6

    async def main():
        eng = LLMEngine(_tiny(temperature=0.0))
        want = await _drain(await eng.add_request(prompt, max_tokens=n))
        hold_depth(eng, depth)
        a = await eng.add_request([1, 2], max_tokens=200)
        b = await eng.add_request([3], max_tokens=200)
        while a.generated < 3:
            await asyncio.sleep(0.005)
        assert [p.decode for p in eng._inflight] == [True] * depth, "the steps in flight between two iterations"
        eng.cancel(a.request_id)
        sent = await _drain(a)
        assert a.finish_reason == "cancelled" and sent == a.tokens
        before = b.generated
        while b.generated < before + 3:  # the loop goes on for the other lane
            await asyncio.sleep(0.005)
        jit = eng._decode_jit

        def slow_jit(*args):  # so that stop() finds the loop inside the call
            time.sleep(0.05)
            return jit(*args)

        eng._decode_jit = slow_jit
        await asyncio.sleep(0.12)
        await eng.stop()
        eng._decode_jit = jit
        assert b.finish_reason == "engine_stopped" and not eng._inflight
        assert eng._dispatching is None
        report = eng.bm.leak_report()
        assert report["blocks_in_use"] == 0 and report["live_sequences"] == 0
        assert not eng.k_pages.is_deleted() and not eng.v_pages.is_deleted()
        discarded = eng.stats()["lane_steps_discarded"]
        again = await _drain(await eng.add_request(prompt, max_tokens=n))  # ensure_started()
        await eng.stop()
        return want, again, discarded

    want, again, discarded = asyncio.run(main())
    assert again == want
    assert discarded == depth  # a's lane-steps that were in flight when it was cancelled


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("what", ["jit_call", "executor_shut_down"])
def test_engine_failed_step_ends_no_stream_short(what, depth, hold_depth):
    """A step that raises retires the steps in flight first (their tokens
    are real and go out in order) and never becomes a clean end: after a
    jit call that raised the loop goes on and every request still gets
    its max_tokens; with the default executor shut down (a replica on
    its way out) the loop stops and no stream is sent FINISHED, so a
    client sees a broken stream, not a short answer."""
    prompt, n = [3, 1, 4, 1, 5], 12

    async def main():
        (free,), _ = await _one_at_a_time(_tiny(temperature=0.0), [(prompt, n)])
        eng = LLMEngine(_tiny(temperature=0.0))
        hold_depth(eng, depth)
        req = await eng.add_request(prompt, max_tokens=n)
        while req.generated < 3:
            await asyncio.sleep(0.005)
        seen = []  # tokens on the host when the step raised: ``depth`` more are in flight

        def planted(*args):
            seen.append(len(req.tokens))
            raise RuntimeError("planted")

        loop = asyncio.get_running_loop()
        if what == "jit_call":
            jit = eng._decode_jit

            def once(*args):
                eng._decode_jit = jit
                planted()

            eng._decode_jit = once
            toks = await _drain(req)
        else:
            loop.run_in_executor = planted
            while not eng._loop_task.done():
                await asyncio.sleep(0.005)
            del loop.run_in_executor
            toks = list(req.tokens)
            sent = [req.out.get_nowait() for _ in range(req.out.qsize())]
            assert FINISHED not in sent and req.finish_reason == ""
        assert not eng._inflight
        await eng.stop()
        return free, seen, toks, req, eng.bm.leak_report()

    free, seen, toks, req, report = asyncio.run(main())
    assert len(seen) == 1 and len(toks) > seen[0]  # the steps in flight were fetched and emitted
    assert toks[:seen[0] + 1] == free[:seen[0] + 1]
    if what == "jit_call":
        assert len(toks) == n and req.finish_reason == "length"
    else:
        assert len(toks) == seen[0] + depth and req.finish_reason == "engine_stopped"
    assert report["blocks_in_use"] == 0 and report["live_sequences"] == 0


# ----------------------------------------------------------------------
# per-trace critical path (PR 2 carried follow-up)
# ----------------------------------------------------------------------
def test_critical_path_sequential_children():
    from ray_tpu.util.state import critical_path, group_traces

    def span(name, sid, parent, t0, t1):
        return {"name": name, "span_id": sid, "parent_span_id": parent,
                "trace_id": "t1", "start_time": t0, "end_time": t1, "pid": 1}

    group = [
        span("serve.request", "root", None, 0.0, 10.0),
        span("serve.queue", "q", "root", 0.0, 2.0),
        span("serve.prefill", "p", "root", 2.0, 3.0),
        span("serve.decode", "d", "root", 3.0, 10.0),
        # a concurrent sibling that overlaps decode: NOT on the path
        span("other", "o", "root", 4.0, 5.0),
    ]
    path = critical_path(group)
    names = [e["name"] for e in path]
    assert names == ["serve.request", "serve.queue", "serve.prefill", "serve.decode"]
    total = sum(e["duration_s"] for e in path if e["segment"])
    assert total == pytest.approx(10.0)
    traces = group_traces(group)
    assert traces[0]["critical_path_s"] == pytest.approx(10.0)
    assert [e["name"] for e in traces[0]["critical_path"]] == names


def test_engine_records_request_spans():
    """The engine's per-request spans land in the process span log and
    group into a trace whose critical path attributes queue/prefill/
    decode."""
    from ray_tpu.util import tracing
    from ray_tpu.util.state import group_traces

    tracing.drain_spans()  # isolate

    async def main():
        eng = LLMEngine(_tiny())
        req = await eng.add_request([1, 2, 3], max_tokens=4)
        await _drain(req)
        await eng.stop()

    asyncio.run(main())
    spans = tracing.drain_spans()
    mine = [s for s in spans if s["name"].startswith("serve.")]
    names = {s["name"] for s in mine}
    assert {"serve.request", "serve.queue", "serve.prefill", "serve.decode"} <= names
    traces = group_traces(mine)
    t = next(tr for tr in traces if "serve.request" in tr["root_names"])
    cp_names = [e["name"] for e in t["critical_path"]]
    assert cp_names[0] == "serve.request"
    assert "serve.decode" in cp_names


# ----------------------------------------------------------------------
# @serve.batch fixes (satellite): running-loop binding + shutdown cancel
# ----------------------------------------------------------------------
def test_batch_queue_binds_running_loop():
    """The batch worker must bind the loop the first call RUNS on — a
    non-default loop here (the old get_event_loop() bound the thread
    default and the worker never woke)."""

    @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.01)
    async def doubler(items):
        return [i * 2 for i in items]

    loop = asyncio.new_event_loop()  # NOT the thread's default loop
    try:
        out = loop.run_until_complete(asyncio.wait_for(doubler(21), timeout=5))
        for q in doubler._serve_batch_queues.values():
            q.shutdown()
        loop.run_until_complete(asyncio.sleep(0))  # let cancellation land
    finally:
        loop.close()
    assert out == 42


def test_replica_prepare_shutdown_cancels_batch_worker():
    from ray_tpu.serve._private.replica import Replica

    class Batched:
        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.01)
        async def handle(self, items):
            return [i + 1 for i in items]

        async def __call__(self, x):
            return await self.handle(x)

    async def main():
        rep = Replica("r1", "dep", (Batched, (), {}), None, 10)
        out = await rep.handle_request("__call__", (1,), {})
        assert out == 2
        queues = rep.callable.handle._serve_batch_queues
        workers = [q._worker for q in queues.values() if q._worker is not None]
        assert workers and not any(w.done() for w in workers)
        await rep.prepare_shutdown()
        await asyncio.sleep(0)  # let cancellation propagate
        return workers

    workers = asyncio.run(main())
    assert all(w.done() for w in workers), "batch worker task leaked past shutdown"


# ----------------------------------------------------------------------
# cluster: serve integration, autoscaling, shedding, chaos
# ----------------------------------------------------------------------
def test_llm_serve_stream_and_oneshot(serve_cluster):
    from ray_tpu.serve import llm

    app = llm.build_app(_tiny(name="llm_basic"))
    handle = serve.run(app, name="llm_basic_app")
    out = handle.remote({"prompt": [1, 2, 3], "max_tokens": 5}).result(timeout=60)
    assert out["num_tokens"] == 5 and len(out["tokens"]) == 5
    events = list(handle.options(stream=True).generate.remote(
        {"prompt": "hi", "max_tokens": 4}
    ))
    assert [e["token"] for e in events if "token" in e.keys()][:4]
    assert events[-1]["done"] and events[-1]["num_tokens"] == 4
    # explicit cancel mid-stream frees blocks on the replica
    gen = handle.options(stream=True).generate.remote(
        {"prompt": "xy", "max_tokens": 400}
    )
    it = iter(gen)
    first = next(it)
    handle.cancel.remote(first["request_id"]).result(timeout=30)
    list(it)  # drains to the cancelled sentinel
    deadline = time.time() + 10
    while time.time() < deadline:
        st = handle.stats.remote().result(timeout=30)
        if st["kv_blocks_in_use"] == 0:
            break
        time.sleep(0.2)
    assert st["kv_blocks_in_use"] == 0, st["kv_leak_report"]
    serve.delete("llm_basic")


def test_autoscale_up_down_from_queue_depth(serve_cluster):
    """Synthetic queue depth reported via __serve_stats__ drives real
    replica add/remove through the controller's autoscaling_config."""

    @serve.deployment(
        name="synthload",
        num_replicas=1,
        autoscaling_config={
            "min_replicas": 1,
            "max_replicas": 3,
            "target_ongoing_requests": 2.0,
            "upscale_delay_s": 0.5,
            "downscale_delay_s": 1.0,
        },
    )
    class SynthLoad:
        def __init__(self):
            self.depth = 0

        def set_depth(self, d):
            self.depth = d
            return d

        def __serve_stats__(self):
            return {"queued": self.depth}

        def __call__(self, payload):
            return "ok"

    handle = serve.run(SynthLoad.bind(), name="synthload_app")

    def running():
        return serve.status()["synthload"]["num_running"]

    # every replica reports depth 10 >> target 2 -> scale to max
    handle.set_depth.remote(10).result(timeout=30)
    deadline = time.time() + 60
    while time.time() < deadline and running() < 3:
        # new replicas start at depth 0; keep pushing load to all of them
        try:
            handle.set_depth.remote(10).result(timeout=30)
        except Exception:
            pass
        time.sleep(0.5)
    assert running() == 3, f"never scaled up: {running()} running"
    # drain: depth 0 everywhere -> scale back down to min
    for _ in range(6):
        try:
            handle.set_depth.remote(0).result(timeout=30)
        except Exception:
            pass
        time.sleep(0.3)
    deadline = time.time() + 60
    while time.time() < deadline and running() > 1:
        try:
            handle.set_depth.remote(0).result(timeout=30)
        except Exception:
            pass
        time.sleep(0.5)
    assert running() == 1, f"never scaled down: {running()} running"
    serve.delete("synthload")


def test_proxy_sheds_past_queue_bound(serve_cluster):
    """Past max_queued_requests the proxy sheds with 503 + Retry-After
    instead of queueing unboundedly; capacity returning un-sheds."""

    @serve.deployment(name="shedme", max_queued_requests=2, route_prefix="/shedme")
    class Slow:
        async def __call__(self, payload):
            await asyncio.sleep(1.0)
            return {"ok": True}

    serve.run(Slow.bind(), name="shed_app", http_port=18127)

    def call(results, i):
        req = urllib.request.Request(
            "http://127.0.0.1:18127/shedme",
            data=json.dumps({"i": i}).encode(),
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=30) as resp:
                results[i] = ("ok", resp.status, None)
        except urllib.error.HTTPError as e:
            results[i] = ("http_error", e.code, e.headers.get("Retry-After"))
        except Exception as e:  # noqa: BLE001
            results[i] = ("error", None, str(e))

    # wait until the route is live
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(
                "http://127.0.0.1:18127/-/routes", timeout=5
            ) as r:
                if "/shedme" in json.loads(r.read()):
                    break
        except Exception:  # noqa: BLE001
            pass
        time.sleep(0.3)
    results = {}
    threads = [
        threading.Thread(target=call, args=(results, i), daemon=True)
        for i in range(8)
    ]
    for t in threads:
        t.start()
        time.sleep(0.02)  # establish arrival order: first 2 admitted
    for t in threads:
        t.join(timeout=60)
    oks = [r for r in results.values() if r[0] == "ok"]
    sheds = [r for r in results.values() if r[0] == "http_error" and r[1] == 503]
    assert oks, results
    assert sheds, f"no 503s under overload: {results}"
    assert all(r[2] == "1" for r in sheds), "503 without Retry-After"
    # overload gone: requests flow again
    results2 = {}
    call(results2, 0)
    assert results2[0][0] == "ok", results2
    serve.delete("shedme")


def test_engine_shed_maps_to_503_over_http(serve_cluster):
    """A RequestShedError raised in the ENGINE (inside the replica, so
    it crosses the task boundary as a derived RayTaskError) must still
    surface as 503 + Retry-After at the proxy."""
    from ray_tpu.serve import llm

    app = llm.build_app(
        LLMConfig(model="tiny", max_batch_size=1, num_blocks=64, block_size=8,
                  max_queue=1, name="llm_eshed"),
        route_prefix="/eshed",
        max_ongoing_requests=64,
    )
    serve.run(app, name="llm_eshed_app", http_port=18127)
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(
                "http://127.0.0.1:18127/-/routes", timeout=5
            ) as r:
                if "/eshed" in json.loads(r.read()):
                    break
        except Exception:  # noqa: BLE001
            pass
        time.sleep(0.3)

    def call(results, i):
        req = urllib.request.Request(
            "http://127.0.0.1:18127/eshed",
            data=json.dumps({"prompt": [i], "max_tokens": 100}).encode(),
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=60) as resp:
                results[i] = ("ok", resp.status, None)
        except urllib.error.HTTPError as e:
            results[i] = ("http_error", e.code, e.headers.get("Retry-After"))
        except Exception as e:  # noqa: BLE001
            results[i] = ("error", None, str(e))

    results = {}
    threads = [
        threading.Thread(target=call, args=(results, i), daemon=True)
        for i in range(10)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    sheds = [r for r in results.values() if r[1] == 503]
    oks = [r for r in results.values() if r[0] == "ok"]
    others = [r for r in results.values() if r[0] == "error" or r[1] not in (200, 503)]
    assert not others, f"engine shed surfaced as non-503: {results}"
    assert sheds, f"flood never shed through the engine bound: {results}"
    assert all(r[2] == "1" for r in sheds), f"503 without Retry-After: {sheds}"
    assert oks, results
    serve.delete("llm_eshed")


def test_llm_http_token_streaming_and_disconnect(serve_cluster):
    """HTTP chunked token streaming (one NDJSON event per token, the
    transport meta item stripped by the proxy), and client disconnect
    mid-stream releasing the request's KV blocks via the proxy's
    disconnect-cancel contract."""
    import http.client

    from ray_tpu.serve import llm

    app = llm.build_app(
        LLMConfig(model="tiny", max_batch_size=4, num_blocks=64,
                  block_size=8, name="llm_http"),
        route_prefix="/llm",
    )
    # the proxy is a singleton: reuse the module's proxy port
    serve.run(app, name="llm_http_app", http_port=18127)
    # wait for the route
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(
                "http://127.0.0.1:18127/-/routes", timeout=5
            ) as r:
                if "/llm" in json.loads(r.read()):
                    break
        except Exception:  # noqa: BLE001
            pass
        time.sleep(0.3)
    req = urllib.request.Request(
        "http://127.0.0.1:18127/llm",
        data=json.dumps({"prompt": "hey", "max_tokens": 5}).encode(),
        headers={"Content-Type": "application/json", "x-serve-stream": "1"},
    )
    with urllib.request.urlopen(req, timeout=60) as resp:
        lines = [json.loads(l) for l in resp.read().decode().splitlines() if l]
    tokens = [e for e in lines if "token" in e]
    assert len(tokens) == 5, lines
    assert lines[-1].get("done"), lines
    assert not any("__serve_stream_meta__" in e for e in lines), (
        "transport meta leaked to the client"
    )

    # disconnect mid-stream: read a little, then drop the connection —
    # the proxy must cancel the request so its blocks free
    conn = http.client.HTTPConnection("127.0.0.1", 18127, timeout=30)
    body = json.dumps({"prompt": "long", "max_tokens": 120})
    conn.request("POST", "/llm", body=body,
                 headers={"Content-Type": "application/json",
                          "x-serve-stream": "1"})
    resp = conn.getresponse()
    resp.read(40)  # a few token events
    conn.close()  # abandon the stream

    handle = serve.get_deployment_handle("llm_http")
    deadline = time.time() + 30
    st = None
    while time.time() < deadline:
        st = handle.stats.remote().result(timeout=30)
        if st["kv_blocks_in_use"] == 0 and st["waiting"] == 0 and st["running"] == 0:
            break
        time.sleep(0.3)
    assert st["kv_blocks_in_use"] == 0, f"KV leak after disconnect: {st['kv_leak_report']}"
    # the proxy stays healthy and keeps serving
    out = handle.remote({"prompt": [1], "max_tokens": 3}).result(timeout=60)
    assert out["num_tokens"] == 3
    serve.delete("llm_http")


@pytest.mark.slow  # ~17 s replica-kill drill: runs under `-m chaos`
@pytest.mark.chaos
def test_chaos_replica_kill_mid_stream(serve_cluster):
    """Kill one replica mid-load: its streams fail, streams on the
    survivor are unaffected, new requests re-route, the controller
    replaces the dead replica, and KV accounting on the survivor still
    balances to zero."""
    from ray_tpu.serve import llm
    from ray_tpu.serve._private.controller import CONTROLLER_NAME

    app = llm.build_app(
        LLMConfig(model="tiny", max_batch_size=4, num_blocks=128,
                  block_size=8, name="llm_chaos"),
        num_replicas=2,
    )
    handle = serve.run(app, name="llm_chaos_app")
    controller = ray_tpu.get_actor(CONTROLLER_NAME, "serve")

    def replica_actors():
        reps = ray_tpu.get(controller.get_replicas.remote("llm_chaos"))
        return {
            r["replica_id"]: ray_tpu.get_actor(r["actor_name"], "serve")
            for r in reps
        }

    deadline = time.time() + 60
    while time.time() < deadline and len(replica_actors()) < 2:
        time.sleep(0.5)
    actors = replica_actors()
    assert len(actors) == 2

    # open LONG streams (120 tokens ~ seconds of decode runway) so they
    # are genuinely in flight at kill time; "total" is the replica's
    # monotonic stream-request counter, so spread detection can't race
    # completions
    streams = []
    counts = {rid: 0 for rid in actors}
    deadline = time.time() + 60
    while time.time() < deadline and (
        len(streams) < 8 or not all(c >= 2 for c in counts.values())
    ):
        gen = handle.options(stream=True).generate.remote(
            {"prompt": [1, 2, 3], "max_tokens": 120}
        )
        it = iter(gen)
        first = next(it)  # established: first token arrived
        streams.append({"it": it, "first": first, "tokens": [first["token"]]})
        counts = {
            rid: ray_tpu.get(a.stats.remote()).get("total", 0)
            for rid, a in actors.items()
        }
        if len(streams) >= 20:
            break
    assert all(c >= 1 for c in counts.values()), f"streams never spread: {counts}"

    victim_id = max(counts, key=counts.get)
    survivor_id = next(rid for rid in counts if rid != victim_id)
    ray_tpu.kill(actors[victim_id])

    # drain every open stream: survivors complete, victim's streams fail
    completed, failed = 0, 0
    for s in streams:
        try:
            done_ev = None
            for ev in s["it"]:
                if "token" in ev:
                    s["tokens"].append(ev["token"])
                if ev.get("done"):
                    done_ev = ev
            assert done_ev is not None and done_ev["num_tokens"] == 120
            completed += 1
        except AssertionError:
            raise
        except Exception:  # noqa: BLE001 — the killed replica's streams
            failed += 1
    assert completed >= 1, "no stream survived the kill"
    assert failed >= 1, "the killed replica's streams vanished silently?"

    # new requests re-route to live replicas: the first attempt may race
    # the stale membership, but observing the death evicts the replica
    # from the router so retries converge immediately
    deadline = time.time() + 30
    out = None
    while time.time() < deadline:
        try:
            out = handle.remote({"prompt": [9], "max_tokens": 4}).result(timeout=60)
            break
        except Exception:  # noqa: BLE001 — raced the dead replica
            time.sleep(0.2)
    assert out is not None and out["num_tokens"] == 4, "re-route never converged"

    # the controller replaces the dead replica
    deadline = time.time() + 60
    while time.time() < deadline:
        reps = ray_tpu.get(controller.get_replicas.remote("llm_chaos"))
        if len(reps) == 2 and all(r["replica_id"] != victim_id for r in reps):
            break
        time.sleep(0.5)
    assert len(reps) == 2, f"dead replica never replaced: {reps}"

    # KV accounting on the survivor balances to zero
    survivor = actors[survivor_id]
    deadline = time.time() + 30
    while time.time() < deadline:
        st = ray_tpu.get(survivor.stats.remote())
        if st.get("kv_blocks_in_use") == 0:
            break
        time.sleep(0.3)
    assert st.get("kv_blocks_in_use") == 0, st.get("kv_leak_report")
    serve.delete("llm_chaos")

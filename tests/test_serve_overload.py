"""Overload-resilient serving (PR 18 tentpole): SLO-class admission,
tenant-fair KV scheduling, decode-lane preemption-by-recompute, brownout
degradation, per-tenant token-rate quotas at the proxy, and multiplexed
model variants.

Layers under test:

- pure math: TokenBucket / TenantBuckets / DegradationController (no
  engine, no cluster);
- engine: DRF fair queue under a tenant flood, preempt-by-recompute
  token-exactness vs an uninterrupted greedy run, cancel+preempt storm
  leak accounting, brownout shed semantics (interactive never shed);
- replica: multiplexed model_id -> variant engine with LRU swap;
- cluster/HTTP: identity threading (header + handle kwarg), quota 429
  with Retry-After attributed to the over-quota tenant only;
- chaos (slow): tenant storm with a replica kill mid-storm, and a
  seeded SIGKILL exactly between KV free and requeue mid-preemption.
"""

import asyncio
import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve.exceptions import RequestShedError
from ray_tpu.serve.llm import LLMConfig, LLMEngine
from ray_tpu.serve.llm.engine import FINISHED
from ray_tpu.serve.llm.overload import (
    DegradationController,
    TenantBuckets,
    TokenBucket,
    normalize_slo,
)

PROXY_PORT = 18129


@pytest.fixture(scope="module")
def serve_cluster(ray_cluster):
    yield ray_cluster
    try:
        serve.shutdown()
    except Exception:  # noqa: BLE001 — a chaos drill may have torn down
        pass


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


def _wait_route(prefix: str, port: int = PROXY_PORT, timeout: float = 30.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/-/routes", timeout=5
            ) as r:
                if prefix in json.loads(r.read()):
                    return
        except Exception:  # noqa: BLE001
            pass
        time.sleep(0.3)
    raise AssertionError(f"route {prefix} never became live")


def _post(path: str, payload: dict, headers: dict = None,
          port: int = PROXY_PORT, timeout: float = 60.0):
    """(status, body_bytes, response_headers); HTTP errors return their
    status instead of raising."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read(), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


# ----------------------------------------------------------------------
# pure math: token buckets
# ----------------------------------------------------------------------
def test_token_bucket_charge_refund_refill():
    b = TokenBucket(rate=10, burst=20)
    assert b.charge(20, now=0.0)          # full burst goes through
    assert not b.charge(1, now=0.0)       # empty: refused, NOT deducted
    assert b.level(now=0.0) == 0.0
    b.refund(5)
    assert b.charge(5, now=0.0)           # the refund is spendable
    assert b.charge(10, now=1.0)          # 1s at rate 10 refilled 10
    assert not b.charge(1, now=1.0)
    # refill caps at burst, refund caps at burst
    assert b.level(now=100.0) == 20.0
    b.refund(10**6)
    assert b.level(now=100.0) == 20.0


def test_token_bucket_retry_after():
    b = TokenBucket(rate=10, burst=20)
    assert b.charge(20, now=0.0)
    # 10-token deficit at 10 tok/s -> 1s (and never below the 1s floor)
    assert b.retry_after(10, now=0.0) == pytest.approx(1.0)
    assert b.retry_after(2, now=0.0) == 1.0
    # a request larger than burst is bounded by the burst deficit
    assert b.retry_after(10**9, now=0.0) == pytest.approx(2.0)
    frozen = TokenBucket(rate=0, burst=5)
    assert frozen.charge(5, now=0.0)
    assert frozen.retry_after(1, now=0.0) == 60.0


def test_tenant_buckets_unregistered_unlimited():
    tb = TenantBuckets({"metered": {"rate": 5, "burst": 10}})
    assert set(tb.registered()) == {"metered"}
    # no quota entry -> always admitted, no retry hint
    for _ in range(100):
        assert tb.charge("anon", 10**6, now=0.0) == (True, 0.0)
    ok, retry = tb.charge("metered", 10, now=0.0)
    assert ok and retry == 0.0
    ok, retry = tb.charge("metered", 1, now=0.0)
    assert not ok and retry >= 1.0
    tb.refund("metered", 4)
    assert tb.charge("metered", 4, now=0.0) == (True, 0.0)
    # refunding an unregistered tenant is a no-op, not an error
    tb.refund("anon", 50)


def test_normalize_slo():
    assert normalize_slo("interactive") == "interactive"
    assert normalize_slo(" Batch ") == "batch"
    for junk in (None, "", "gold-tier", "INTERACTIVE!!", "0"):
        assert normalize_slo(junk) == "standard"


# ----------------------------------------------------------------------
# pure math: brownout ladder
# ----------------------------------------------------------------------
def test_degradation_ladder_hysteresis_and_monotonicity():
    d = DegradationController(ttft_slo_s=1.0, queue_high=10,
                              down_ticks=3, up_ticks=5)
    assert d.enabled
    levels = [d.level]
    # sustained violation: one step per down_ticks, never a jump
    for _ in range(12):
        levels.append(d.tick(5.0, 0))
    assert levels[:10] == [0, 0, 0, 1, 1, 1, 2, 2, 2, 3]
    assert d.level == 3  # clamped at LEVEL_MAX
    assert all(abs(b - a) <= 1 for a, b in zip(levels, levels[1:]))
    # the hysteresis band (between recover_margin*bound and bound)
    # HOLDS the level and resets both streaks — no flapping
    for _ in range(20):
        assert d.tick(0.9, 0) == 3
    # two healthy ticks then a violation: the healthy streak resets
    d.tick(0.1, 0), d.tick(0.1, 0)
    d.tick(5.0, 0)
    for _ in range(4):
        assert d.tick(0.1, 0) == 3
    # sustained healthy: recovers one step per up_ticks back to 0
    up = [d.tick(0.1, 0) for _ in range(16)]
    assert up[0] == 2 and up[-1] == 0
    assert all(abs(b - a) <= 1 for a, b in zip(up, up[1:]))
    # queue depth alone violates too
    d2 = DegradationController(ttft_slo_s=1.0, queue_high=10, down_ticks=1)
    d2.tick(None, 50)
    assert d2.level == 1


def test_degradation_shed_ordering_never_interactive():
    d = DegradationController(ttft_slo_s=1.0, queue_high=10, down_ticks=1)
    for expect_batch, expect_std in [(False, False), (False, False),
                                     (True, False), (True, True)]:
        assert d.should_shed("batch") is expect_batch
        assert d.should_shed("standard") is expect_std
        assert d.should_shed("interactive") is False
        d.tick(9.0, 0)
    # at the deepest level interactive STILL flows
    assert d.level == 3 and not d.should_shed("interactive")
    # level >= 1 clamps only batch generation budgets
    assert d.max_tokens_cap("batch", 500) == d.batch_max_tokens
    assert d.max_tokens_cap("standard", 500) == 500
    assert d.max_tokens_cap("interactive", 500) == 500
    # disabled controller is inert regardless of signals
    off = DegradationController(ttft_slo_s=0.0, queue_high=1, down_ticks=1)
    assert not off.enabled
    for _ in range(10):
        assert off.tick(10**6, 10**6) == 0
    assert not off.should_shed("batch")


# ----------------------------------------------------------------------
# engine: tenant-fair queue, preemption, storm accounting, brownout
# ----------------------------------------------------------------------
def test_engine_fair_queue_victim_overtakes_hog_backlog():
    """With DRF fairness a newly-arrived tenant's request is admitted
    ahead of another tenant's queued backlog (zero dominant share beats
    any positive share) — FIFO would make it wait behind all of it."""

    async def main():
        eng = LLMEngine(_tiny(max_batch_size=2, preempt_wait_s=30.0,
                              tenant_weights={"hog": 1.0, "victim": 1.0}))
        hogs = [
            await eng.add_request([1 + i, 2, 3], max_tokens=30,
                                  tenant="hog", slo="batch")
            for i in range(6)
        ]
        while not all(h.generated >= 1 for h in hogs[:2]):
            await asyncio.sleep(0.01)
        vic = await eng.add_request([9, 9], max_tokens=4,
                                    tenant="victim", slo="interactive")
        st_mid = eng.stats()
        await asyncio.gather(*[_drain(r) for r in hogs + [vic]])
        report = eng.bm.leak_report()
        await eng.stop()
        return hogs, vic, st_mid, report

    hogs, vic, st_mid, report = asyncio.run(main())
    # per-tenant usage was visible while contended
    assert "hog" in st_mid["tenants"], st_mid
    # the victim overtook the ENTIRE queued hog backlog (two lanes can
    # free at one step boundary, so a hog may join the SAME step — but
    # never an earlier one; FIFO would have made the victim wait for 4)
    queued_hogs = hogs[2:]
    assert all(vic.join_step <= h.join_step for h in queued_hogs), (
        vic.join_step, [h.join_step for h in queued_hogs]
    )
    assert len(vic.tokens) == 4
    assert report["blocks_in_use"] == 0


def test_engine_preempt_by_recompute_token_exact():
    """An interactive arrival with no free lane preempts a batch lane;
    the victim's KV is freed and its generated-so-far folds into the
    prompt, so its final token sequence is IDENTICAL to an uninterrupted
    greedy run — preemption must be invisible in the output."""
    prompts, hog_tokens = [[3, 1, 4], [2, 7, 1]], 40

    async def interrupted():
        eng = LLMEngine(_tiny(max_batch_size=2, preempt_wait_s=0.005,
                              temperature=0.0,
                              tenant_weights={"a": 1.0, "b": 1.0}))
        hogs = [
            await eng.add_request(p, max_tokens=hog_tokens,
                                  tenant="a", slo="batch")
            for p in prompts
        ]
        while not all(h.generated >= 3 for h in hogs):
            await asyncio.sleep(0.01)
        vic = await eng.add_request([5, 5], max_tokens=4,
                                    tenant="b", slo="interactive")
        await asyncio.gather(*[_drain(r) for r in hogs + [vic]])
        st = eng.stats()
        report = eng.bm.leak_report()
        await eng.stop()
        return hogs, vic, st, report

    async def uninterrupted(prompt):
        eng = LLMEngine(_tiny(max_batch_size=2, temperature=0.0))
        req = await eng.add_request(prompt, max_tokens=hog_tokens)
        toks = await _drain(req)
        await eng.stop()
        return toks

    hogs, vic, st, report = asyncio.run(interrupted())
    assert st["preemptions_total"] >= 1, "drill is vacuous: nothing preempted"
    assert any(h.preemptions >= 1 for h in hogs), (
        "a batch lane should have been the victim"
    )
    # victims are only ever strictly-lower-priority lanes
    assert vic.preemptions == 0
    assert any(e["type"] == "preemption" and e["victim_slo"] == "batch"
               for e in st["events"]), st["events"]
    # token-exactness: EVERY hog (preempted or not) parity-checks against
    # its own uninterrupted greedy run — preemption is invisible
    for hog, prompt in zip(hogs, prompts):
        assert hog.tokens == asyncio.run(uninterrupted(prompt)), (
            f"hog with {hog.preemptions} preemption(s) diverged"
        )
    # KV accounting balanced through free -> fold -> re-prefill
    assert report["blocks_in_use"] == 0
    assert report["total_allocs"] == report["total_frees"]


def test_engine_preempt_parity_exact_for_known_victim():
    """Single-lane variant pins WHICH request is preempted, so the
    parity assertion is exact: same prompt, same seed, one run preempted
    (possibly repeatedly), one not — byte-identical token streams."""
    # an answer long enough that the hog still runs when each wave has
    # starved (at 30 tokens of 0.7 ms a step it had ended first in one of
    # the driver's runs); a wave that finds it ended fails here, by name
    prompt, n = [6, 2, 8], 120

    async def run(preempt: bool):
        eng = LLMEngine(_tiny(max_batch_size=1, preempt_wait_s=0.005,
                              temperature=0.0,
                              tenant_weights={"a": 1.0, "b": 1.0}))
        hog = await eng.add_request(prompt, max_tokens=n,
                                    tenant="a", slo="batch")
        vics = []

        async def wave(token, preempted):
            """One interactive request, added while the hog holds the
            lane; returns once the hog has been preempted for it."""
            assert not hog.finish_reason, "drill is vacuous: the hog ended before this wave"
            vics.append(await eng.add_request([token], max_tokens=3,
                                              tenant="b", slo="interactive"))
            while hog.preemptions < preempted:
                assert not hog.finish_reason, f"drill is vacuous: the hog ended before preemption {preempted}"
                await asyncio.sleep(0.005)

        if preempt:
            while hog.generated < 4:
                await asyncio.sleep(0.01)
            await wave(5, 1)
            # a second wave AFTER the hog is back in the lane forces a
            # second preemption through the fold-resume path
            while hog.slot < 0:
                assert not hog.finish_reason
                await asyncio.sleep(0.005)
            await wave(7, 2)
        await asyncio.gather(*[_drain(r) for r in [hog] + vics])
        st = eng.stats()
        report = eng.bm.leak_report()
        await eng.stop()
        return hog, st, report

    hog_p, st_p, rep_p = asyncio.run(run(preempt=True))
    hog_o, _, _ = asyncio.run(run(preempt=False))
    assert hog_p.preemptions >= 2, "drill is vacuous: fewer than 2 preemptions"
    assert st_p["preemptions_total"] >= 2
    assert hog_p.tokens == hog_o.tokens, (
        "preempt-by-recompute diverged from the uninterrupted run"
    )
    assert len(hog_p.tokens) == n and hog_p.finish_reason == "length"
    assert rep_p["blocks_in_use"] == 0
    assert rep_p["total_allocs"] == rep_p["total_frees"]


def test_engine_preempt_with_a_step_in_flight_folds_what_the_client_was_sent():
    """The loop keeps one decode step in flight; a preemption fetches it
    first, so the fold holds every token the victim was dispatched for,
    each of them emitted to its stream, and nothing of it is in flight."""
    # an answer long enough that the hog still runs when the victim has
    # starved: at 30 tokens of 0.7 ms a step it had ended first in one
    # run of six on this machine, and the wait below never returned
    prompt, n = [6, 2, 8], 120

    async def main():
        eng = LLMEngine(_tiny(max_batch_size=1, preempt_wait_s=0.005,
                              temperature=0.0,
                              tenant_weights={"a": 1.0, "b": 1.0}))
        seen = []
        pick, preempt = eng._preempt_victim, eng._preempt

        def picking():
            victim, for_req = pick()
            if victim is not None:
                seen.append({"in_flight_when_picked": len(eng._inflight)})
            return victim, for_req

        def preempting(req, for_req=None):
            seen[0].update(in_flight=len(eng._inflight), tokens=list(req.tokens),
                            dispatched=req.dispatched, generated=req.generated,
                            queued=req.out.qsize(), prompt=list(req.prompt))
            return preempt(req, for_req)

        eng._preempt_victim, eng._preempt = picking, preempting
        hog = await eng.add_request(prompt, max_tokens=n, tenant="a", slo="batch")
        while hog.generated < 4:
            await asyncio.sleep(0.01)
        vic = await eng.add_request([5], max_tokens=3, tenant="b", slo="interactive")
        while not hog.preemptions:
            assert not hog.finish_reason, "drill is vacuous: the hog ended before it was preempted"
            await asyncio.sleep(0.005)
        folded_prompt = list(hog.prompt)
        toks, _ = await asyncio.gather(_drain(hog), _drain(vic))
        report = eng.bm.leak_report()
        await eng.stop()
        return seen, folded_prompt, toks, report

    seen, folded_prompt, toks, report = asyncio.run(main())
    first = seen[0]
    assert first["in_flight_when_picked"] >= 1, "drill is vacuous: nothing was in flight"
    assert first["in_flight"] == 0
    assert first["dispatched"] == first["generated"] == len(first["tokens"]) == first["queued"]
    assert folded_prompt == first["prompt"] + first["tokens"]
    assert toks[:len(first["tokens"])] == first["tokens"] and len(toks) == n
    assert report["blocks_in_use"] == 0


@pytest.mark.parametrize("depth", [1, 2])
def test_engine_no_preemption_when_the_step_in_flight_frees_a_lane(depth, hold_depth):
    """The victim is picked with a step in flight (two, where the loop
    runs a step ahead); if the fetch of those steps ends another lane
    (here by eos_token), the starved request joins there and nobody is
    evicted: the decision is made again on what is true after the fetch."""
    a_prompt, b_prompt, n, k = [6, 2, 8], [3, 1, 4, 1, 5], 24, 12

    async def main():
        cfg = dict(max_batch_size=2, temperature=0.0, tenant_weights={"a": 1.0, "b": 1.0})
        free = LLMEngine(_tiny(**cfg))
        a_free = await _drain(await free.add_request(a_prompt, max_tokens=n))
        b_free = await _drain(await free.add_request(b_prompt, max_tokens=n))
        await free.stop()
        eos = b_free[k]
        assert eos not in a_free and b_free.index(eos) == k, "pick other prompts"
        # nobody is starved until b's step k, the one that ends it, is dispatched
        eng = LLMEngine(_tiny(eos_token=eos, preempt_wait_s=1e9, **cfg))
        hold_depth(eng, depth)
        a = await eng.add_request(a_prompt, max_tokens=n, tenant="a", slo="batch")
        b = await eng.add_request(b_prompt, max_tokens=n, tenant="a", slo="batch")
        jit, picks = eng._decode_jit, []
        pick = eng._preempt_victim

        def decode(*args):
            if b.dispatched == k:
                eng.config.preempt_wait_s = 0.0
            return jit(*args)

        def picking():
            victim, for_req = pick()
            picks.append((victim is not None, len(eng._inflight), b.finish_reason))
            return victim, for_req

        eng._decode_jit, eng._preempt_victim = decode, picking
        while not b.generated:  # a and b hold the lanes
            await asyncio.sleep(0.001)
        c = await eng.add_request([5], max_tokens=3, tenant="b", slo="interactive")
        assert b.dispatched < k, "c came too late for the drill"
        outs = await asyncio.gather(_drain(a), _drain(b), _drain(c))
        stats = eng.stats()
        await eng.stop()
        return a_free, b_free, outs, picks, a, stats

    a_free, b_free, outs, picks, a, stats = asyncio.run(main())
    first = picks.index((True, depth, ""))  # a victim was picked with b's last step in flight
    assert picks[first + 1] == (False, 0, "eos")  # and given up after the fetch
    assert a.preemptions == 0 and stats["preemptions_total"] == 0
    assert outs[0] == a_free and outs[1] == b_free[:k + 1] and len(outs[2]) == 3
    assert stats["kv_leak_report"]["blocks_in_use"] == 0


def test_engine_cancel_preempt_storm_zero_leak():
    """A storm of mixed-class multi-tenant requests with cancels landing
    on waiting, running, and preempted requests must balance the KV pool
    to zero — `_finish` is the only exit and every path reaches it."""

    async def main():
        eng = LLMEngine(_tiny(max_batch_size=2, preempt_wait_s=0.02,
                              num_blocks=96,
                              tenant_weights={"a": 1.0, "b": 1.0}))
        reqs = []
        for i in range(24):
            r = await eng.add_request(
                [1 + (i % 7), 2, 3],
                max_tokens=6 + (i % 9),
                tenant="a" if i % 2 == 0 else "b",
                slo=("interactive", "standard", "batch")[i % 3],
            )
            reqs.append(r)
            if i % 3 == 0:
                await asyncio.sleep(0.005)
            if i % 4 == 3:  # cancel a recent one in whatever state it is
                eng.cancel(reqs[i - 1].request_id)
        await asyncio.sleep(0.05)
        for r in reqs[::5]:  # second wave, some mid-decode / post-preempt
            eng.cancel(r.request_id)
        await asyncio.gather(*[_drain(r) for r in reqs])
        deadline = time.monotonic() + 10
        while eng.bm.blocks_in_use and time.monotonic() < deadline:
            await asyncio.sleep(0.02)
        report = eng.bm.leak_report()
        await eng.stop()
        return report

    report = asyncio.run(main())
    assert report["blocks_in_use"] == 0, report
    assert report["live_sequences"] == 0
    assert report["total_allocs"] == report["total_frees"]


def test_engine_brownout_sheds_batch_admits_interactive():
    async def main():
        eng = LLMEngine(_tiny(slo_ttft_s=0.5, max_queue=64))
        # drive the ladder directly (the engine ticks it at its metrics
        # cadence; the ladder math itself is unit-tested above)
        for _ in range(3):
            eng._degrade.tick(10.0, 10**6)
        assert eng._degrade.level == 1
        # level 1: batch budgets clamp, nothing shed yet
        br = await eng.add_request([1, 2], max_tokens=500, slo="batch")
        assert br.max_tokens == eng._degrade.batch_max_tokens
        for _ in range(6):
            eng._degrade.tick(10.0, 10**6)
        assert eng._degrade.level == 3
        with pytest.raises(RequestShedError):
            await eng.add_request([3], max_tokens=4, slo="batch")
        with pytest.raises(RequestShedError):
            await eng.add_request([3], max_tokens=4, slo="standard")
        # interactive is NEVER shed by brownout
        ir = await eng.add_request([4, 5], max_tokens=4, slo="interactive")
        await asyncio.gather(_drain(br), _drain(ir))
        st = eng.stats()
        await eng.stop()
        return ir, st

    ir, st = asyncio.run(main())
    assert len(ir.tokens) == 4
    assert st["degradation_level"] == 3
    assert st["shed_total"] == 2


# ----------------------------------------------------------------------
# replica: multiplexed model variants with LRU swap
# ----------------------------------------------------------------------
def test_multiplex_variant_lru_swap_and_eviction_count():
    from ray_tpu.serve.llm.deployment import LLMServer

    async def main():
        srv = LLMServer(_tiny(name="mx").to_dict())
        e_a = await srv._engine_for({"model_id": "a", "prompt": [1]})
        e_b = await srv._engine_for({"model_id": "b", "prompt": [1]})
        assert e_a is not e_b is not srv.engine
        # cache hit: same id -> same engine, no reload
        assert await srv._engine_for({"model_id": "a", "prompt": [1]}) is e_a
        assert srv._mx_evictions == 0
        # third variant exceeds MAX_MODELS_PER_REPLICA=2 -> LRU (b) out
        e_c = await srv._engine_for({"model_id": "c", "prompt": [1]})
        ids = {v.model_id for v in srv._loaded_variants()}
        assert ids == {"a", "c"} and srv._mx_evictions == 1
        # the evicted id reloads as a FRESH engine (and evicts again)
        e_b2 = await srv._engine_for({"model_id": "b", "prompt": [1]})
        assert e_b2 is not e_b and srv._mx_evictions == 2
        # empty model_id means the base engine
        assert await srv._engine_for({"prompt": [1]}) is srv.engine
        # a variant engine actually serves, with its own derived weights
        req = await e_c.add_request([1, 2, 3], max_tokens=4)
        toks = await _drain(req)
        assert len(toks) == 4
        stats = srv.stats()
        assert set(stats["multiplex"]["loaded_model_ids"]) == {"c", "b"}
        assert stats["multiplex"]["evictions"] == 2
        await srv.__serve_shutdown__()

    asyncio.run(main())


# ----------------------------------------------------------------------
# cluster: identity threading + proxy quota admission (tier-1)
# ----------------------------------------------------------------------
def test_identity_threads_header_and_handle_to_replica(serve_cluster):
    """tenant + SLO class reach the replica's request context through
    BOTH front doors: the proxy's x-serve-* headers and the handle's
    options(tenant=, slo_class=) — across the compiled-channel frames."""

    @serve.deployment(name="whoami", route_prefix="/whoami")
    class WhoAmI:
        def __call__(self, payload):
            return {"tenant": serve.get_request_tenant(),
                    "slo": serve.get_request_slo()}

    handle = serve.run(WhoAmI.bind(), name="whoami_app", http_port=PROXY_PORT)
    # handle kwarg path
    out = handle.options(tenant="acme", slo_class="interactive").remote(
        {}).result(timeout=60)
    assert out == {"tenant": "acme", "slo": "interactive"}
    # no identity -> defaults (and the derived handle didn't stick)
    out = handle.remote({}).result(timeout=60)
    assert out == {"tenant": "default", "slo": "standard"}
    # unknown SLO strings clamp instead of minting labels
    out = handle.options(tenant="acme", slo_class="platinum").remote(
        {}).result(timeout=60)
    assert out["slo"] == "standard"
    # HTTP header path through the proxy
    _wait_route("/whoami")
    status, body, _ = _post("/whoami", {"x": 1},
                            headers={"x-serve-tenant": "acme",
                                     "x-serve-slo": "interactive"})
    assert status == 200 and json.loads(body) == {
        "tenant": "acme", "slo": "interactive"}
    # payload fields win over headers
    status, body, _ = _post("/whoami", {"tenant": "beta", "slo": "batch"},
                            headers={"x-serve-tenant": "acme"})
    assert status == 200 and json.loads(body) == {
        "tenant": "beta", "slo": "batch"}
    serve.delete("whoami")


def test_proxy_tenant_quota_429_attributed_to_hostile_only(serve_cluster):
    """Over-quota tenants get 429 + Retry-After at the proxy; in-quota
    tenants are untouched, and the shed counters attribute every quota
    shed to the hostile tenant only."""
    from ray_tpu.serve import llm

    cfg = _tiny(
        name="llm_quota",
        tenant_quotas={
            # hostile: one small burst, then effectively frozen
            "hostile": {"rate": 0.001, "burst": 30},
            "victim": {"rate": 1e6, "burst": 1e6},
        },
    )
    app = llm.build_app(cfg, route_prefix="/quota")
    serve.run(app, name="llm_quota_app", http_port=PROXY_PORT)
    _wait_route("/quota")

    def call(tenant):
        return _post("/quota", {"prompt": "hi", "max_tokens": 8},
                     headers={"x-serve-tenant": tenant})

    # hostile: the burst admits ~3 requests (est = 2 prompt bytes + 8),
    # then the bucket refuses — completion refunds only the unused part
    codes = [call("hostile")[0] for _ in range(8)]
    assert 200 in codes, codes
    rejected = [c for c in codes if c == 429]
    assert rejected, f"hostile was never throttled: {codes}"
    status, _, headers = call("hostile")
    assert status == 429
    assert int(headers.get("Retry-After", "0")) >= 1
    # the victim flows freely the whole time
    for _ in range(5):
        status, body, _ = call("victim")
        assert status == 200, (status, body)
        assert json.loads(body)["num_tokens"] == 8
    # shed attribution: only the hostile tenant appears
    with urllib.request.urlopen(
        f"http://127.0.0.1:{PROXY_PORT}/-/stats", timeout=10
    ) as r:
        stats = json.loads(r.read())
    per_tenant = stats.get("shed_tenant", {}).get("llm_quota", {})
    assert per_tenant.get("hostile", 0) >= len(rejected), stats
    assert "victim" not in per_tenant, stats
    serve.delete("llm_quota")


# ----------------------------------------------------------------------
# chaos drills (slow): tenant storm + replica kill, SIGKILL mid-preempt
# ----------------------------------------------------------------------
@pytest.mark.slow  # multi-replica storm with a kill: runs under `-m chaos`
@pytest.mark.chaos
def test_chaos_tenant_storm_with_replica_kill(serve_cluster):
    """A hostile tenant floods at many times its quota while a victim
    tenant streams interactively; one replica is killed mid-storm.  The
    victim's established streams all complete (retries absorb the kill),
    its TTFT stays bounded, every quota shed lands on the hostile tenant,
    and KV accounting on the survivors balances to zero."""
    from ray_tpu.serve import llm
    from ray_tpu.serve._private.controller import CONTROLLER_NAME

    cfg = _tiny(
        name="llm_storm",
        max_batch_size=4,
        num_blocks=128,
        preempt_wait_s=0.1,
        temperature=0.0,
        tenant_weights={"hostile": 1.0, "victim": 1.0},
        tenant_quotas={
            "hostile": {"rate": 20, "burst": 40},
            "victim": {"rate": 1e6, "burst": 1e6},
        },
    )
    app = llm.build_app(cfg, num_replicas=2)
    serve.run(app, name="llm_storm_app", http_port=PROXY_PORT)
    _wait_route("/llm_storm")
    controller = ray_tpu.get_actor(CONTROLLER_NAME, "serve")

    stop = threading.Event()
    hostile = {"sent": 0, "ok": 0, "throttled": 0, "other": 0}

    def hostile_flood():
        while not stop.is_set():
            hostile["sent"] += 1
            try:
                status, _, _ = _post(
                    "/llm_storm", {"prompt": "h" * 16, "max_tokens": 16},
                    headers={"x-serve-tenant": "hostile",
                             "x-serve-slo": "batch"},
                    timeout=30,
                )
                if status == 200:
                    hostile["ok"] += 1
                elif status == 429:
                    hostile["throttled"] += 1
                else:
                    hostile["other"] += 1
            except Exception:  # noqa: BLE001 — the kill may drop one
                hostile["other"] += 1

    def victim_stream_once():
        """One interactive victim stream; returns its TTFT (s)."""
        req = urllib.request.Request(
            f"http://127.0.0.1:{PROXY_PORT}/llm_storm",
            data=json.dumps({"prompt": "v", "max_tokens": 8}).encode(),
            headers={"Content-Type": "application/json",
                     "x-serve-stream": "1",
                     "x-serve-tenant": "victim",
                     "x-serve-slo": "interactive"},
        )
        t0 = time.time()
        with urllib.request.urlopen(req, timeout=60) as resp:
            first = resp.readline()  # established: first token event
            ttft = time.time() - t0
            assert first
            body = resp.read().decode()
        events = [json.loads(l) for l in body.splitlines() if l]
        assert events and events[-1].get("done"), events
        return ttft

    floods = [threading.Thread(target=hostile_flood, daemon=True)
              for _ in range(3)]
    for t in floods:
        t.start()

    ttfts, raw_failures, completed = [], 0, 0
    killed = False
    try:
        for i in range(10):
            for attempt in range(4):
                try:
                    ttfts.append(victim_stream_once())
                    completed += 1
                    break
                except Exception:  # noqa: BLE001 — kill races a stream
                    raw_failures += 1
                    time.sleep(0.5)
            else:
                raise AssertionError(
                    f"victim stream {i} failed every retry "
                    f"(raw_failures={raw_failures})"
                )
            if completed == 3 and not killed:
                reps = ray_tpu.get(controller.get_replicas.remote("llm_storm"))
                victim_rep = reps[0]
                ray_tpu.kill(
                    ray_tpu.get_actor(victim_rep["actor_name"], "serve")
                )
                killed = True
    finally:
        stop.set()
        for t in floods:
            t.join(timeout=30)

    assert killed, "the drill never killed a replica"
    assert completed == 10, "a victim stream was permanently lost"
    # TTFT bound: generous for the 1-core CI box, but it proves the
    # hostile flood and the kill never starved the interactive class
    ttfts.sort()
    p99 = ttfts[max(0, int(len(ttfts) * 0.99) - 1)]
    assert p99 < 30.0, f"victim TTFT blew out under storm: {ttfts}"
    assert hostile["throttled"] >= 5, hostile
    assert hostile["sent"] >= 3 * hostile["ok"], (
        f"flood too weak to prove throttling: {hostile}"
    )
    # shed attribution: quota sheds are the hostile tenant's alone
    with urllib.request.urlopen(
        f"http://127.0.0.1:{PROXY_PORT}/-/stats", timeout=10
    ) as r:
        stats = json.loads(r.read())
    per_tenant = stats.get("shed_tenant", {}).get("llm_storm", {})
    assert per_tenant.get("hostile", 0) >= 5, stats
    assert "victim" not in per_tenant, stats
    # the dead replica is replaced and KV balances to zero everywhere
    deadline = time.time() + 60
    reps = []
    while time.time() < deadline:
        reps = ray_tpu.get(controller.get_replicas.remote("llm_storm"))
        if len(reps) == 2:
            break
        time.sleep(0.5)
    assert len(reps) == 2, f"replica never replaced: {reps}"
    deadline = time.time() + 30
    leaks = None
    while time.time() < deadline:
        leaks = {}
        for rep in reps:
            try:
                st = ray_tpu.get(
                    ray_tpu.get_actor(rep["actor_name"], "serve").stats.remote()
                )
                leaks[rep["replica_id"]] = st.get("kv_blocks_in_use", -1)
            except Exception:  # noqa: BLE001 — replica still starting
                leaks[rep["replica_id"]] = -1
        if all(v == 0 for v in leaks.values()):
            break
        time.sleep(0.5)
    assert all(v == 0 for v in leaks.values()), f"KV leak after storm: {leaks}"
    serve.delete("llm_storm")


@pytest.mark.slow  # own cluster: the chaos spec must precede process spawn
@pytest.mark.chaos
def test_chaos_sigkill_mid_preemption_zero_leak():
    """A seeded SIGKILL lands exactly in the preemption window — after
    the victim's KV pages are freed, before the requeue.  The replica
    dies mid-preemption; the controller must replace it, the replacement
    must serve with ZERO leaked KV blocks, and the plane must not wedge."""
    saved = {
        k: os.environ.get(k)
        for k in ("RAY_TPU_testing_chaos_spec", "RAY_TPU_testing_chaos_seed")
    }
    for fn in (serve.shutdown, ray_tpu.shutdown):
        try:
            fn()
        except Exception:  # noqa: BLE001
            pass
    os.environ["RAY_TPU_testing_chaos_spec"] = "@serve.preempt.evict:kill:at=1"
    os.environ["RAY_TPU_testing_chaos_seed"] = "7"
    from ray_tpu._private.chaos import CHAOS

    CHAOS.reset()
    try:
        ray_tpu.init(num_cpus=4)
        from ray_tpu.serve import llm
        from ray_tpu.serve._private.controller import CONTROLLER_NAME

        cfg = _tiny(name="llm_psig", max_batch_size=1, preempt_wait_s=0.05,
                    temperature=0.0,
                    tenant_weights={"a": 1.0, "b": 1.0})
        handle = serve.run(llm.build_app(cfg), name="llm_psig_app")
        controller = ray_tpu.get_actor(CONTROLLER_NAME, "serve")
        reps0 = ray_tpu.get(controller.get_replicas.remote("llm_psig"))
        assert len(reps0) == 1
        rid0 = reps0[0]["replica_id"]

        # occupy the single lane with a long batch-class stream
        gen = handle.options(stream=True, tenant="a", slo_class="batch")\
            .generate.remote({"prompt": [1, 2, 3], "max_tokens": 400})
        it = iter(gen)
        next(it)  # established

        # an interactive arrival forces the preemption whose evict-side
        # chaos point kills the replica (os._exit between free + requeue)
        def poke():
            try:
                handle.options(tenant="b", slo_class="interactive").remote(
                    {"prompt": [5], "max_tokens": 3}
                ).result(timeout=20)
            except Exception:  # noqa: BLE001 — died with the replica
                pass

        threading.Thread(target=poke, daemon=True).start()

        # the kill fired iff the replica id changes
        deadline = time.time() + 90
        reps = []
        while time.time() < deadline:
            reps = ray_tpu.get(controller.get_replicas.remote("llm_psig"))
            if len(reps) == 1 and reps[0]["replica_id"] != rid0:
                break
            time.sleep(0.5)
        assert reps and reps[0]["replica_id"] != rid0, (
            "chaos kill at serve.preempt.evict never fired (no preemption?)"
        )
        # the orphaned stream dies with its replica, never wedges
        try:
            for _ in it:
                pass
        except Exception:  # noqa: BLE001 — expected: replica death
            pass

        # the replacement serves immediately and its KV pool is clean
        out = None
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                out = handle.options(tenant="b", slo_class="interactive")\
                    .remote({"prompt": [9], "max_tokens": 4}).result(timeout=30)
                break
            except Exception:  # noqa: BLE001 — raced the dead membership
                time.sleep(0.3)
        assert out is not None and out["num_tokens"] == 4, (
            "replacement replica never served"
        )
        deadline = time.time() + 30
        st = None
        while time.time() < deadline:
            st = handle.stats.remote().result(timeout=30)
            if st["kv_blocks_in_use"] == 0 and st["waiting"] == 0:
                break
            time.sleep(0.3)
        assert st["kv_blocks_in_use"] == 0, st["kv_leak_report"]
        rep = st["kv_leak_report"]
        assert rep["total_allocs"] == rep["total_frees"], rep
        serve.delete("llm_psig")
    finally:
        for fn in (serve.shutdown, ray_tpu.shutdown):
            try:
                fn()
            except Exception:  # noqa: BLE001
                pass
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        CHAOS.reset()

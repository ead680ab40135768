"""The decode step's per-lane arguments as device state (``LANE_STATE``
in ``serve/llm/engine.py``): lengths, steps left and block tables (a row
a lane), temperatures and the sampling key's counter live on the device
beside ``_lane_tok``; a join writes its lane's row, a leave the device
cannot foresee clears it, one small program a step advances the rest.

Every test holds the loop to the host's own account of the same step:
what ``_dispatch_decode`` built from ``BlockManager`` and the requests
before the arguments moved (``_host_built``), and a replay of the whole
schedule through the two programs on host arrays alone, the way the
benchmark's runners call them.  All four families' tiny presets on the
CPU; counts and tokens are exact, a speed comes from the chip alone.
"""

import asyncio
import contextlib

import jax
import numpy as np
import pytest

from ray_tpu.serve.llm import LLMConfig, LLMEngine
from ray_tpu.serve.llm.engine import FINISHED, LANE_STATE

FAMILIES = ["tiny", "olmoe_tiny", "minicpm_sala_tiny", "mistral_small_4_tiny"]
BS = 8  # positions a page


def _config(model, **kw) -> LLMConfig:
    base = dict(model=model, max_batch_size=3, num_blocks=120, block_size=BS, seed=7,
                default_max_tokens=8, temperature=0.0)
    base.update(kw)
    return LLMConfig(**base)


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(1, 200, n).tolist()


async def _drain(req):
    toks = []
    while True:
        ev = await req.out.get()
        if ev is FINISHED:
            return toks
        toks.append(ev["token"])


def _host_built(eng):
    """One decode step's arguments as the host built them before they
    moved to the device: from the block manager and the requests alone,
    at the instant the loop picks the step's lanes."""
    bm, B = eng.bm, eng.config.max_batch_size
    lengths, write_phys = np.zeros(B, np.int32), np.zeros(B, np.int32)
    tables = np.zeros((B, bm.blocks_needed(eng.max_ctx)), np.int32)
    temp = np.zeros(B, np.float32)
    for i, req in enumerate(eng.slots):
        if req is None or req.dispatched >= req.max_tokens or req.cancelled:
            continue
        cur = bm.seq_len(req.request_id)
        lengths[i], tables[i] = cur, bm.block_table(req.request_id, tables.shape[1])
        write_phys[i], temp[i] = bm.phys_index(req.request_id, cur), req.temperature
    return lengths, tables, write_phys, temp


class Recorder:
    """Wraps an engine's programs.  Every decode step's device-made
    arguments are held against ``_host_built`` and the host-derived key
    as they are dispatched; every program and lane edit is kept, in
    dispatch order, for ``replay``; after every fetch the device's
    ``lengths`` and ``left`` are held against the host's mirror."""

    def __init__(self, eng):
        self.eng, self.events, self.fetches, self.errors = eng, [], 0, []
        self.decode_steps = int(eng._lanes["step"])
        self._expected = None
        n = len(eng._spec.names)
        prefill, decode, put, dispatch, fetch = (
            eng._prefill_jit, eng._decode_jit, eng._put_lane, eng._dispatch_decode, eng._fetch)

        def prefill_jit(params, *args):
            out = prefill(params, *args)
            self.events.append(("prefill", args[n:], out[0]))
            return out

        def put_lane(lane_tok, lanes, first, lane, row, temp):
            self.events.append(("put", int(lane), int(row[1])))  # the decode steps the joined request has left
            return put(lane_tok, lanes, first, lane, row, temp)

        async def dispatch_decode(loop):
            self._expected = _host_built(eng)  # no await lies between this and the loop's own pick
            return await dispatch(loop)

        def decode_jit(params, *args):
            tok, *step = args[n:]
            *made, rng = (np.asarray(a) for a in step)
            key = np.asarray(jax.random.fold_in(eng._decode_key, self.decode_steps))
            self.decode_steps += 1
            with self.checking():
                assert not any(isinstance(a, np.ndarray) for a in (tok, *step)), "a host-made argument"
                runs = self._expected[0] > 0
                for name, want, got in zip(("lengths", "tables", "write_phys", "temp"), self._expected, made):
                    assert got.dtype == want.dtype and got.shape == want.shape, name
                    if name == "temp":  # a lane that does not run keeps its last holder's: its token is dropped
                        want, got = want[runs], got[runs]
                    np.testing.assert_array_equal(got, want, err_msg=f"{name} at decode step {self.decode_steps}")
                np.testing.assert_array_equal(rng, key)
            out = decode(params, *args)
            self.events.append(("decode", (*made, key), out[0]))
            return out

        def fetch_and_check(prog):
            fetch(prog)
            self.fetches += 1
            with self.checking():
                self.check_mirror()

        eng._prefill_jit, eng._decode_jit, eng._put_lane = prefill_jit, decode_jit, put_lane
        eng._dispatch_decode, eng._fetch = dispatch_decode, fetch_and_check

    @contextlib.contextmanager
    def checking(self):
        """The loop logs a step that raised and goes on, so a failed
        check would hang the run: the first is kept for the test to
        raise (``errors``), and no later one is made."""
        try:
            if not self.errors:
                yield
        except AssertionError as e:
            self.errors.append(e)

    def check_mirror(self):
        """The device's rows against bm.seq_len, _Request.dispatched and
        the block manager's table, lane by lane; zeros where nobody runs."""
        eng = self.eng
        rows = np.asarray(eng._lanes["rows"])
        for i, req in enumerate(eng.slots):
            want = np.zeros_like(rows[i])
            if req is not None and req.row_live and req.dispatched < req.max_tokens:
                want[0], want[1] = eng.bm.seq_len(req.request_id), req.max_tokens - req.dispatched
                want[2:] = eng.bm.block_table(req.request_id, len(want) - 2)
            np.testing.assert_array_equal(rows[i], want, err_msg=f"lane {i}")


def replay(config, events):
    """The recorded schedule through a fresh engine's two programs on
    host arrays alone (the benchmark's call forms), each step's ``tok``
    from the replay's own outputs.  -> every program's tokens in order:
    a prefill's first token, a decode step's tokens of the lanes that ran."""
    eng = LLMEngine(config)
    tok = np.zeros(eng.config.max_batch_size, np.int32)
    first, out = None, []
    for kind, args, _ in events:
        if kind == "prefill":
            host = [np.asarray(a) if i != 4 else a for i, a in enumerate(args)]  # the key as _next_rng gave it
            first = int(np.asarray(eng._run_on_cache(eng._prefill_jit, *host)).reshape(-1)[0])
            out.append([first])
        elif kind == "put":
            tok[args] = first
        else:
            lengths, tables, write_phys, temp, key = args
            nxt = np.asarray(eng._run_on_cache(eng._decode_jit, tok.copy(), lengths, tables, write_phys, temp, key))
            tok = nxt[:len(tok)].astype(np.int32)
            out.append(tok[lengths > 0].tolist())
    return out


def _loop_tokens(events):
    out = []
    for kind, args, tokens in events:
        if kind == "prefill":
            out.append([int(np.asarray(tokens).reshape(-1)[0])])
        elif kind == "decode":
            out.append(np.asarray(tokens)[:len(args[0])][args[0] > 0].tolist())
    return out


@contextlib.contextmanager
def _jax_events():
    """Every event JAX reports while the block runs (a trace, a
    lowering, a compile, a look-up of either), by name."""
    events = []

    def listener(name, *_a, **_k):
        events.append(name)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield events
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


def _balanced(eng, st):
    assert st["kv_blocks_in_use"] == 0 and st["state_slots_in_use"] == 0
    assert st["kv_leak_report"]["live_sequences"] == 0
    assert not np.asarray(eng._lanes["rows"]).any()


# (prompt tokens, output tokens): more requests than the three lanes; one
# answered by its prefill alone (a row with no step left); one prompt of
# several chunks where the family states a chunk of 64; the tail leaves
# lanes standing empty while the longest answer runs on
BATCH = [(5, 9), (2, 1), (9, 14), (70, 4), (6, 22), (2, 6), (17, 2)]


@pytest.mark.parametrize("temperature", [0.0, 0.9], ids=["greedy", "sampled"])
@pytest.mark.parametrize("model", FAMILIES)
def test_the_loop_decodes_on_what_the_host_would_have_built(model, temperature):
    """Joins into a running batch, a successor's early join, leaves by
    length, lanes that stand empty: every step's arguments equal the
    host-built ones, the device's lengths and counts equal the mirror
    after every fetch, and a replay on host arrays alone gives the
    loop's tokens, greedy and sampled from the same key stream."""
    config = _config(model, temperature=temperature, top_k=20)

    async def main():
        eng = LLMEngine(config)
        rec = Recorder(eng)
        reqs = [await eng.add_request(_prompt(n, i), max_tokens=m) for i, (n, m) in enumerate(BATCH[:4])]
        while reqs[2].generated < 3:
            await asyncio.sleep(0.002)
        reqs += [await eng.add_request(_prompt(n, 10 + i), max_tokens=m)  # join mid-stream
                 for i, (n, m) in enumerate(BATCH[4:])]
        outs = await asyncio.gather(*[_drain(r) for r in reqs])
        st = eng.stats()
        await eng.stop()
        return eng, rec, reqs, outs, st

    eng, rec, reqs, outs, st = asyncio.run(main())
    assert not rec.errors, rec.errors[0]
    assert [len(o) for o in outs] == [m for _, m in BATCH]
    assert rec.decode_steps == st["steps"] > 0 and rec.fetches >= st["steps"]
    assert any(b.join_step < a.finish_step for a in reqs for b in reqs
               if a is not b and a.slot == b.slot and a.t_join < b.t_join), "no successor joined early"
    # all left by length: a row a join, no row cleared, nothing sent a step
    assert st["lane_edits"] == st["joined"] == len(BATCH)
    assert st["decode_host_bytes"] == 0 and st["lane_steps_discarded"] == 0
    _balanced(eng, st)
    assert int(eng._lanes["step"]) == st["steps"]
    assert sorted(eng._lanes) == sorted(LANE_STATE)
    # the schedule again, on host arrays alone
    assert replay(config, rec.events) == _loop_tokens(rec.events)
    # and the tokens the clients got are those programs' tokens
    by_lane = {}
    for kind, args, tokens in rec.events:
        if kind == "decode":
            for i in np.flatnonzero(args[0] > 0):
                by_lane.setdefault(int(i), []).append(int(np.asarray(tokens)[i]))
    for lane, got in by_lane.items():
        want = [t for r in sorted((r for r in reqs if r.slot == lane), key=lambda r: r.t_join) for t in r.tokens[1:]]
        assert got == want, lane


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("model", FAMILIES)
def test_a_leave_the_device_cannot_foresee_clears_the_row(model, depth, hold_depth):
    """eos_token, cancel() and a preemption, with one decode step in
    flight or two: the host finds out, the lane's row is cleared by one
    edit each, every step before and after runs on what the host would
    have built, the resumed victim's row is written again, and blocks,
    state slots and rows go back to zero.  A preemption fetches every
    program in flight before it folds: what is folded was emitted."""
    config = _config(model, max_batch_size=2, preempt_wait_s=1e9, tenant_weights={"a": 1.0, "b": 1.0})
    n = 24

    async def main():
        eng = LLMEngine(config)
        rec = Recorder(eng)
        hold_depth(eng, depth)
        pick, fold, folds = eng._preempt_victim, eng._preempt, []

        def preempt_victim():
            victim, for_req = pick()
            if victim is not None and eng._inflight:  # the first of an iteration's two decisions
                folds.append([len(eng._inflight)])
            return victim, for_req

        def preempt(req, for_req=None):
            folds[-1] += [len(eng._inflight), req.dispatched - req.generated]
            return fold(req, for_req)

        eng._preempt_victim, eng._preempt = preempt_victim, preempt
        free, other = await asyncio.gather(*[_drain(await eng.add_request(_prompt(*p), max_tokens=n))
                                             for p in ((5, 1), (7, 2))])
        # eos_token: a token of the free run past the prefill's own that came
        # nowhere before it, in neither answer: it ends one request, once
        k = next((i for i in range(2, n) if free.index(free[i]) == i and free[i] not in other), None)
        assert k is not None, "pick other prompts"
        eng.config.eos_token = free[k]
        ended = await eng.add_request(_prompt(5, 1), max_tokens=n)
        beside = await eng.add_request(_prompt(7, 2), max_tokens=n)
        outs = await asyncio.gather(_drain(ended), _drain(beside))
        assert ended.finish_reason == "eos" and outs[0] == free[:k + 1]
        eng.config.eos_token = -1
        edits = {"eos": eng.stats()["lane_edits"] - eng.stats()["joined"]}
        # cancel(): a running lane, with a step in flight
        gone = await eng.add_request(_prompt(9, 3), max_tokens=60)
        stays = await eng.add_request(_prompt(4, 4), max_tokens=30)
        while gone.generated < 3:
            await asyncio.sleep(0.002)
        eng.cancel(gone.request_id)
        sent = await _drain(gone)
        assert gone.finish_reason == "cancelled" and sent == gone.tokens and len(sent) < 60
        edits["cancel"] = eng.stats()["lane_edits"] - eng.stats()["joined"]
        # _preempt: an interactive request finds both lanes held by batch-class work
        victim = await eng.add_request(_prompt(6, 5), max_tokens=40, tenant="a", slo="batch")
        while victim.generated < 2:
            await asyncio.sleep(0.002)
        eng.config.preempt_wait_s = 0.0
        urgent = await eng.add_request(_prompt(3, 6), max_tokens=5, tenant="b", slo="interactive")
        outs = await asyncio.gather(_drain(stays), _drain(victim), _drain(urgent))
        st = eng.stats()
        await eng.stop()
        return eng, rec, st, edits, outs, (stays, victim, urgent), folds

    eng, rec, st, edits, outs, (stays, victim, urgent), folds = asyncio.run(main())
    assert not rec.errors, rec.errors[0]
    assert [len(o) for o in outs] == [30, 40, 5]
    # decided with ``depth`` decode steps in flight, folded with none, nothing of the victim's unfetched
    assert folds and all(f[0] >= depth and f[1:] == [0, 0] for f in folds), folds
    assert depth - 1 <= st["lane_steps_discarded"] // 2 <= depth  # eos and cancel(): a lane-step a step in flight
    assert st["preemptions_total"] >= 1 and stays.preemptions + victim.preemptions == st["preemptions_total"]
    assert edits == {"eos": 1, "cancel": 2}
    # a row a join (a resumed victim joins again), and one cleared for each leave by eos, cancel and preemption
    assert st["lane_edits"] == st["joined"] + 2 + st["preemptions_total"]
    assert st["lane_steps_discarded"] >= 1 and st["decode_host_bytes"] == 0
    assert rec.decode_steps == st["steps"]
    _balanced(eng, st)


@pytest.mark.parametrize("model", FAMILIES)
def test_the_same_requests_give_the_same_tokens_at_either_depth(model, hold_depth):
    """One decode step queued behind the running one or two: the same
    requests, all there from the start, get the same SAMPLED tokens from
    the same programs in the same order (a step's key follows from how
    many steps were dispatched, its lanes from who was dispatched for),
    every step runs on what the host would have built, and a join's
    prefill goes in front of the iteration's decode step, never behind
    it: the step dispatched next runs the joined lane."""
    config = _config(model, temperature=0.9, top_k=20)

    async def run(depth):
        eng = LLMEngine(config)
        rec = Recorder(eng)
        hold_depth(eng, depth)
        prefill, behind = eng._prefill, []

        async def prefill_behind(loop, req):
            behind.append(sum(p.decode for p in eng._inflight))  # decode steps a join's prefill is queued behind
            return await prefill(loop, req)

        eng._prefill = prefill_behind
        reqs = [await eng.add_request(_prompt(n, i), max_tokens=m) for i, (n, m) in enumerate(BATCH)]
        outs = await asyncio.gather(*[_drain(r) for r in reqs])
        st = eng.stats()
        await eng.stop()
        assert not rec.errors, rec.errors[0]
        _balanced(eng, st)
        return rec, outs, st, behind

    (one, outs_one, st_one, behind_one), (two, outs_two, st_two, behind_two) = (
        asyncio.run(run(depth)) for depth in (1, 2))
    assert outs_one == outs_two and [len(o) for o in outs_one] == [m for _, m in BATCH]
    assert [kind for kind, *_ in one.events] == [kind for kind, *_ in two.events]
    assert _loop_tokens(one.events) == _loop_tokens(two.events)
    assert st_one["steps"] == st_two["steps"] and st_one["decodes_chained"] == st_two["decodes_chained"]
    assert st_one["decodes_ahead"] == 0 and max(behind_one) == 1
    assert st_two["decodes_ahead"] > st_two["steps"] / 2 and max(behind_two) == 2
    assert st_one["lane_steps_discarded"] == st_two["lane_steps_discarded"] == 0
    for rec in (one, two):
        for k, (kind, lane, left) in enumerate(rec.events):
            if kind == "put" and left:  # (a request answered by its prefill alone runs no step)
                lengths = next(args[0] for what, args, _ in rec.events[k:] if what == "decode")
                assert lengths[lane] > 0, (k, lane)


@pytest.mark.parametrize("depth", [1, 2])
def test_a_step_that_raises_leaves_device_and_mirror_where_they_were(depth, hold_depth):
    """A decode call that raises with one or two steps in flight behind
    it: those are fetched and emitted, the lanes' state on the device is
    what the mirror says after every fetch, the step is dispatched again
    on the same arguments, and every request gets the tokens of a run
    nothing happened to."""
    config = _config("tiny")

    async def run(plant):
        eng = LLMEngine(config)
        rec = Recorder(eng)
        hold_depth(eng, depth)
        reqs = [await eng.add_request(_prompt(n, i), max_tokens=m) for i, (n, m) in enumerate(BATCH)]
        raised = []
        if plant:
            while reqs[2].generated < 4:
                await asyncio.sleep(0.002)
            jit = eng._decode_jit

            def once(*args):
                eng._decode_jit = jit
                raised.append(sum(p.decode for p in eng._inflight))
                raise RuntimeError("planted")

            eng._decode_jit = once
        outs = await asyncio.gather(*[_drain(r) for r in reqs])
        st = eng.stats()
        await eng.stop()
        assert not rec.errors, rec.errors[0]
        assert rec.decode_steps == st["steps"] == int(eng._lanes["step"])
        _balanced(eng, st)
        return outs, raised

    (outs, raised), (want, _) = asyncio.run(run(True)), asyncio.run(run(False))
    assert outs == want
    assert raised == [depth]  # the decode steps in flight when it raised


def test_stop_clears_every_row_and_a_restart_starts_clean():
    """stop() with lanes running: their rows are cleared (no stale lane
    writes into blocks a later owner holds), and the engine serves the
    same tokens after a restart."""
    config = _config("tiny")

    async def main():
        eng = LLMEngine(config)
        want = await _drain(await eng.add_request(_prompt(5, 1), max_tokens=6))
        running = [await eng.add_request(_prompt(4, i), max_tokens=100) for i in range(3)]
        while min(r.generated for r in running) < 2:
            await asyncio.sleep(0.002)
        await eng.stop()
        rows = np.asarray(eng._lanes["rows"])
        rec = Recorder(eng)
        again = await _drain(await eng.add_request(_prompt(5, 1), max_tokens=6))  # ensure_started()
        st = eng.stats()
        await eng.stop()
        return eng, rec, want, again, rows, st, running

    eng, rec, want, again, rows, st, running = asyncio.run(main())
    assert not rec.errors, rec.errors[0]
    assert all(r.finish_reason == "engine_stopped" for r in running)
    assert not rows.any() and again == want
    assert st["lane_edits"] == st["joined"] + 3
    _balanced(eng, st)


@pytest.mark.parametrize("model", FAMILIES)
def test_steady_decode_steps_send_nothing_and_edit_no_row(model):
    """Over N decode steps with no join and no leave, decode_host_bytes
    and lane_edits do not move, no program is traced, lowered or looked
    up again (JAX reports no compile event of any kind), and both
    counters are plain numbers in stats()."""
    from ray_tpu._private import profiling

    async def main():
        eng = LLMEngine(_config(model))
        await _drain(await eng.add_request(_prompt(5, 1), max_tokens=4))  # warm-up: every program of the run below
        reqs = [await eng.add_request(_prompt(5, i), max_tokens=40) for i in range(3)]
        while min(r.generated for r in reqs) < 2:
            await asyncio.sleep(0.002)
        before, compiles = eng.stats(), profiling.jit_stats("serve_decode")["compiles"]
        with _jax_events() as seen:
            while min(r.generated for r in reqs) < 30:
                await asyncio.sleep(0.002)
        after = eng.stats()
        await asyncio.gather(*[_drain(r) for r in reqs])
        await eng.stop()
        return before, after, seen, compiles, profiling.jit_stats("serve_decode")["compiles"]

    before, after, seen, compiles, compiles_after = asyncio.run(main())
    assert after["steps"] - before["steps"] >= 20 and after["joined"] == before["joined"]
    for name in ("decode_host_bytes", "lane_edits"):
        assert type(after[name]) is int and after[name] == before[name], name
    assert after["decode_host_bytes"] == 0
    assert not [e for e in seen if "/compile/" in e] and compiles_after == compiles


def test_the_benchmark_call_forms_work_on_a_live_engine():
    """What the benchmark's runners call after a window: ``_decode_jit``
    and ``_prefill_jit`` on host arrays (OLMoE's runner unpacks exactly
    three values and sets ``k_pages``/``v_pages``), ``_run_on_cache``,
    ``_next_rng``, ``_prefill_bucket`` and the block manager.  The same
    shapes from another place cost no compile, and ``jit_stats`` counts
    none (the jit's fast-path cache does grow by it)."""
    from ray_tpu._private import profiling

    async def served():
        eng = LLMEngine(_config("tiny"))
        toks = await _drain(await eng.add_request([3, 1, 4, 1, 5], max_tokens=6))
        await eng.stop()
        return eng, toks

    eng, served_toks = asyncio.run(served())
    bm, lanes, pages = eng.bm, eng.config.max_batch_size, eng.bm.blocks_needed(eng.max_ctx)
    compiles = {f: profiling.jit_stats(f)["compiles"] for f in ("serve_prefill", "serve_decode")}
    with _jax_events() as events:
        seq, lane = [3, 1, 4, 1, 5], 1
        bm.allocate("replay", len(seq) + 5)
        bucket = eng._prefill_bucket(len(seq), eng.max_ctx)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :len(seq)] = seq
        bm.advance("replay", len(seq))
        first, eng.k_pages, eng.v_pages = eng._prefill_jit(
            eng.params, eng.k_pages, eng.v_pages, toks, bm.phys_indices("replay", len(seq), bucket),
            np.array([len(seq) - 1], np.int32), np.zeros(1, np.float32), eng._next_rng())
        got = [int(np.asarray(first).reshape(-1)[0])]
        for pos in range(len(seq), len(seq) + 5):
            tok, lengths, write = (np.zeros(lanes, np.int32) for _ in range(3))
            tables = np.zeros((lanes, pages), np.int32)
            tok[lane], lengths[lane], tables[lane] = got[-1], pos, bm.block_table("replay", pages)
            bm.advance("replay", 1)
            write[lane] = bm.phys_index("replay", pos)
            if pos % 2:
                nxt, eng.k_pages, eng.v_pages = eng._decode_jit(
                    eng.params, eng.k_pages, eng.v_pages, tok, lengths, tables, write,
                    np.zeros(lanes, np.float32), eng._next_rng())
            else:
                nxt = eng._run_on_cache(eng._decode_jit, tok, lengths, tables, write,
                                        np.zeros(lanes, np.float32), eng._next_rng())
            got.append(int(np.asarray(nxt)[lane]))
    bm.free("replay")
    assert got == served_toks and bm.blocks_in_use == 0
    assert not [e for e in events if e.endswith(("jaxpr_to_mlir_module_duration", "backend_compile_duration"))]
    assert compiles == {f: profiling.jit_stats(f)["compiles"] for f in compiles}

"""The serve runner: ``serve.run(llm.build_app(...))`` with one replica,
driven through a streaming ``DeploymentHandle`` by one client thread of
this process, open loop (Poisson arrivals at the cell's fixed rate, each
request timed from the instant it was DUE) or closed loop (a fixed
number of clients, each sending its next request when the last ended).

This process imports no JAX.  Whatever needs the chip (device facts,
the compile count, the profiler, the float32 reference) runs inside the
replica, the named actor ``SERVE_REPLICA::<id>``, through
``__ray_call__``; an async actor runs such a call on its event loop, the
thread the engine's step loop lives on, so reading ``stats()`` there
races with nothing.
"""

from __future__ import annotations

import collections
import json
import os
import time

from benchmark import traffic as traffic_mod
from benchmark.runners.common import _rep_settle

# A stream cut off at the end of the drain counts as failed only if it had
# stalled: no token for this long, a dozen engine steps and more.  An
# answer of 256 tokens takes 28 s at 110 ms a token, longer than any
# drain worth its chip time, and is no failure of the system.
STALL_S = 2.0
# Tokens of one engine step reach the client within 0.3 ms of each other
# (16 streams, one pass of the polling loop); the next step's, or a
# prefill's first token, come 8 ms later and more.
BURST_S = 0.002
APP = "bench_llm_app"
DEPLOYMENT = "bench_llm"
_REPLICA = {}  # replica-side state between __ray_call__s (its process only)


# ----------------------------------------------------------------------
# replica side (the process that holds the chip)
# ----------------------------------------------------------------------
def _rep_install(rep):
    import jax.numpy as jnp

    from benchmark.runners import common

    common.count_compiles()  # counting starts here
    cfg = rep.callable.engine.model_cfg
    return {"n_layer": cfg.n_layer, "n_embd": cfg.d_model, "n_head": cfg.n_head,
            "n_positions": cfg.max_seq_len, "vocab_rows": cfg.vocab_size,
            "dtype": jnp.dtype(cfg.dtype).name}


def _rep_stats(rep):
    from benchmark.runners import common

    out = {k: v for k, v in rep.callable.stats().items() if isinstance(v, (int, float, str))}
    out["t"] = time.time()
    out["compiles"] = common.count_compiles()
    return out


def _rep_device(rep):
    from benchmark.runners import common

    return common.device_facts()


def _rep_trace_start(rep):
    from benchmark.runners import common

    _REPLICA["tracer"] = common.Tracer()
    _REPLICA["tracer"].start()
    return _REPLICA["tracer"].t_start


def _rep_trace_facts(rep, seconds, keep_dir):
    """Stop the profiler and reduce the first `seconds` of its trace."""
    tracer = _REPLICA.pop("tracer", None)
    if tracer is None:
        return None
    tracer.stop()
    return tracer.facts((), keep_dir, first_s=seconds)


def _rep_reference_margins(rep, sequences, n_prompt):
    """For each sequence (prompt + the tokens the engine returned): how
    far each returned token's logit lies under the largest logit of a
    plain float32 forward over the whole sequence, on the engine's own
    weights."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference

    cfg = rep.callable.engine.model_cfg
    toks = jnp.asarray(np.asarray(sequences, dtype=np.int32))
    lg = np.asarray(reference.full_logits(rep.callable.engine.params, toks, cfg.n_layer, cfg.n_head))
    out = []
    for row, seq in zip(lg, sequences):
        margins = []
        for pos in range(n_prompt - 1, len(seq) - 1):
            margins.append(float(row[pos].max() - row[pos, seq[pos + 1]]))
        out.append(margins)
    return out


# ----------------------------------------------------------------------
# client side
# ----------------------------------------------------------------------
class _Stream:
    __slots__ = ("gen", "req", "due", "t_sent", "token_t", "tokens", "done", "failed",
                 "t_done", "summary")

    def __init__(self, gen, req, due, t_sent):
        self.gen, self.req, self.due, self.t_sent = gen, req, due, t_sent
        self.token_t, self.tokens = [], []  # when each token arrived, and what it was
        self.done = self.failed = False
        self.t_done = self.summary = None

    @property
    def t_first(self):
        return self.token_t[0] if self.token_t else None

    @property
    def t_last(self):
        return self.token_t[-1] if self.token_t else None

    @property
    def gaps(self):
        return [b - a for a, b in zip(self.token_t, self.token_t[1:])]

    def poll(self) -> bool:
        """Take every event that is ready; True if any came."""
        got = False
        while not self.done:
            try:
                ev = self.gen.try_next()
            except StopIteration:
                self.done, self.t_done = True, time.time()
                return True
            except Exception as e:  # noqa: BLE001 - shed, replica error: the request failed
                self.done = self.failed = True
                self.t_done, self.summary = time.time(), {"error": repr(e)}
                return True
            if ev is None:
                return got
            got = True
            if "token" in ev:
                self.token_t.append(time.time())
                self.tokens.append(ev["token"])
            elif ev.get("done"):
                self.summary = ev
        return got


def _send(stream_handle, req, due):
    payload = {"prompt": req["prompt"], "max_tokens": req["max_tokens"]}
    return _Stream(stream_handle.generate.remote(payload), req, due, time.time())


def _settle(stream_handle, reqs, timeout_s=900):
    """Send `reqs` together and wait for all (set-up: warm-up, checks)."""
    streams = [_send(stream_handle, r, time.time()) for r in reqs]
    deadline = time.time() + timeout_s
    while not all(s.done for s in streams):
        if time.time() > deadline:
            raise TimeoutError("set-up requests did not finish")
        if not any([s.poll() for s in streams if not s.done]):
            time.sleep(0.002)
    bad = [s.summary for s in streams if s.failed]
    if bad:
        raise RuntimeError(f"set-up request failed: {bad[0]}")
    return streams


def drive(stream_handle, plan, t0, seconds, at=()):
    """One window.  `plan`: ``{"mode": "open", "requests": [... due_s]}``
    with ``drain_s``, or ``{"mode": "closed", "clients": n, "requests":
    iterator}``.  `at`: [(offset_s, callable)] run once when the
    window reaches that offset.  Returns every stream opened.

    A closed loop sends nothing after the window's end and goes on
    polling until the first step's tokens at or after it have come
    (STALL_S at most): they are the far edge of the rate, see `edge_rate`."""
    open_loop = plan["mode"] == "open"
    t_end = t0 + seconds
    at = collections.deque(sorted(at, key=lambda p: p[0]))
    streams, live, t_past = [], [], None
    if open_loop:
        pending = collections.deque(plan["requests"])
        stop_at = t_end + plan["drain_s"]
    else:
        source = iter(plan["requests"])
        stop_at = t_end + STALL_S
    while True:
        now = time.time()
        while at and now >= t0 + at[0][0]:
            at.popleft()[1]()
        if open_loop:
            while pending and t0 + pending[0]["due_s"] <= now:
                r = pending.popleft()
                live.append(_send(stream_handle, r, t0 + r["due_s"]))
                streams.append(live[-1])
        else:
            while len(live) < plan["clients"] and now < t_end:
                live.append(_send(stream_handle, next(source), now))
                streams.append(live[-1])
        got = any([s.poll() for s in live])
        if not open_loop and t_past is None and now >= t_end and any(
                s.token_t and s.token_t[-1] >= t_end for s in live):
            t_past = now  # the far edge's first token; its step's others follow within BURST_S
        live = [s for s in live if not s.done]
        if now >= stop_at or (t_past is not None and now >= t_past + 5 * BURST_S) or (
                open_loop and not pending and not live and now >= t_end):
            break
        if not got:
            time.sleep(0.0005)
    while at:
        at.popleft()[1]()
    for s in live:  # what is still running when the window (and its drain) ends
        s.gen.close()
    return streams


def bursts(streams):
    """[(time of the first token, tokens)] for every group of tokens that
    reached the client together, none more than BURST_S after the last."""
    out = []
    last = None
    for t in sorted(t for s in streams for t in s.token_t):
        if last is not None and t - last <= BURST_S:
            out[-1][1] += 1
        else:
            out.append([t, 1])
        last = t
    return out


def edge_rate(streams, t0, t_end):
    """Tokens a second between two bursts: the first at or after `t0` and
    the first at or after `t_end`.  -> (tokens, seconds).

    The engine delivers a step's tokens together, 16 every 124 ms in the
    backlog cell (all within 0.3 ms at the client) and a prefill's first
    token alone between two steps.  A window cut at fixed instants holds
    one step more or less by where its ends fall between two steps: 0.4%
    of 30 s, as much as the whole bound.  Cut at bursts, the span holds
    whole steps: every burst after the first edge up to and including the
    second, each with the time the engine took to make it, over all the
    time between the two, which is the window's length to within one
    step.  An edge with no burst (a stalled engine) stays at its instant."""
    groups = bursts(streams)
    t_a = next((t for t, _ in groups if t >= t0), t0)
    t_b = next((t for t, _ in groups if t >= t_end), t_end)
    return sum(n for t, n in groups if t_a < t <= t_b), t_b - t_a


def percentile(values, p):
    """The p-th percentile, nearest rank."""
    s = sorted(values)
    return s[min(len(s) - 1, int(p / 100.0 * len(s)))] if s else None


def deploy(job):
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve import llm
    from ray_tpu.serve._private.controller import CONTROLLER_NAME

    eng = job["cell"]["engine"]
    llm_config = llm.LLMConfig(
        model=job["config"]["preset"], seed=job["seed"] % (2**31 - 2), dtype=job["sizes"]["dtype"],
        max_batch_size=eng["max_batch_size"], block_size=eng["block_size"],
        # the pool holds pool_tokens slots plus the reserved scratch block 0
        num_blocks=eng["pool_tokens"] // eng["block_size"] + 1,
        max_queue=eng["max_queue"], name=DEPLOYMENT,
    )
    try:
        handle = serve.run(llm.build_app(llm_config, num_replicas=1), name=APP)
    except TimeoutError:
        # serve.run gives a replica 60 s; a cold first compile of the
        # model's init can take longer, and the deployment goes on
        handle = serve.get_deployment_handle(DEPLOYMENT)
    controller = ray_tpu.get_actor(CONTROLLER_NAME, "serve")
    deadline = time.time() + 900
    while True:
        reps = ray_tpu.get(controller.get_replicas.remote(DEPLOYMENT))
        if reps:
            break
        if time.time() > deadline:
            raise TimeoutError("no replica of the deployment came up")
        time.sleep(0.5)
    actor = ray_tpu.get_actor(reps[0]["actor_name"], "serve")
    return handle, actor


def setup_checks(job, stream_handle):
    """Warm up every shape the mix can ask for, then the requests the
    correctness checks need: one prompt twice, and another."""
    tr, chk = job["cell"]["traffic"], job["cell"]["checks"]
    vocab = job["sizes"]["vocab_size"]
    lens = traffic_mod.warmup_prompt_lengths(tr)
    _settle(stream_handle, traffic_mod.fixed_requests(lens, 3, vocab, job["seed"] + 7))
    a, b = traffic_mod.fixed_requests(
        [chk["prompt_len"]] * 2, chk["max_tokens"], vocab, job["seed"] + 11)
    return _settle(stream_handle, [a, a, b])


def run(job) -> dict:
    import ray_tpu

    cell, tr = job["cell"], job["cell"]["traffic"]
    seconds, seed = job["seconds"], job["seed"]
    handle, actor = deploy(job)
    t_deployed = time.time()

    def call(fn, *args):
        return actor.__ray_call__.remote(fn, *args)

    installed = ray_tpu.get(call(_rep_install), timeout=600)
    stream_handle = handle.options(stream=True)
    a1, a2, b = setup_checks(job, stream_handle)
    ray_tpu.get(call(_rep_settle), timeout=300)

    vocab = job["sizes"]["vocab_size"]
    if tr["mode"] == "open":
        plan = {"mode": "open", "drain_s": tr["drain_s"],
                "requests": traffic_mod.open_loop(tr, seconds, vocab, seed)}
        lead_in = 0.0
    else:
        pool = traffic_mod.make_requests(tr["pool_requests"], tr, vocab, seed)
        plan = {"mode": "closed", "clients": tr["clients"], "requests": _cycle(pool)}
        lead_in = tr["lead_in_s"]

    probes = {}

    def probe(name, fn=_rep_stats):
        return lambda: probes.__setitem__(name, call(fn))

    at = [(lead_in, probe("before")), (lead_in + seconds / 2, probe("middle")),
          (lead_in + seconds, probe("after"))]
    if job["trace"]:
        # The window's last seconds.  Stopping the profiler holds the
        # replica's event loop for seconds, so it is stopped only after
        # the drain, when no stream is open, and the reduction reads the
        # first trace_seconds of what it recorded.
        at.append((lead_in + seconds - tr["trace_seconds"], probe("trace_start", _rep_trace_start)))
    t_begin = time.time() + 0.05
    t0 = t_begin + lead_in  # the first measured instant
    streams = drive(stream_handle, plan, t_begin, lead_in + seconds, at)
    t_cut, t_end = time.time(), t0 + seconds

    stats = {k: ray_tpu.get(v, timeout=120) for k, v in probes.items()}
    after_drain = ray_tpu.get(call(_rep_stats), timeout=120)
    deadline = time.time() + 30
    while after_drain["kv_blocks_in_use"] and time.time() < deadline:
        time.sleep(0.2)
        after_drain = ray_tpu.get(call(_rep_stats), timeout=120)
    trace = None
    if job["trace"]:
        trace = ray_tpu.get(call(_rep_trace_facts, tr["trace_seconds"], job.get("keep_trace")), timeout=600)

    # the float32 reference, outside the window
    chk = cell["checks"]
    sequences = [s.req["prompt"] + s.tokens for s in (a1, b)]
    margins = ray_tpu.get(call(_rep_reference_margins, sequences, chk["prompt_len"]), timeout=900)
    worst_margin = max(m for row in margins for m in row)
    device = ray_tpu.get(call(_rep_device), timeout=120)

    in_window = [s for s in streams if t0 <= s.due < t_end] if tr["mode"] == "open" else \
        [s for s in streams if s.done and not s.failed and t0 <= s.t_done < t_end]
    bad = [s for s in streams if s.failed]
    if tr["mode"] == "open":
        stalled = [s for s in in_window if not s.done and (s.t_last or 0) < t_cut - STALL_S]
        failed = len([s for s in in_window if s.failed]) + len(stalled)
        finished = [s for s in in_window if s.done and not s.failed]
    else:
        failed = len(bad)
        finished = in_window
    out_tokens = sum(1 for s in streams for t in s.token_t if t0 <= t < t_end)
    # closed loop: the rate between two arrivals (edge_rate says why); the
    # open loop's, which no end-to-end metric reads, over the fixed window
    rate_tokens, rate_s = edge_rate(streams, t0, t_end) if tr["mode"] == "closed" else \
        (out_tokens, seconds)
    asked_tokens = sum(s.req["max_tokens"] for s in in_window)

    want = installed
    sizes = job["sizes"]
    checks = {
        "preset_has_the_configuration's_sizes": all(
            want[k] == sizes[k] for k in ("n_layer", "n_embd", "n_head", "n_positions", "vocab_rows")
        ) and sizes["dtype"] == want["dtype"],
        "finished_requests_returned_max_tokens": all(
            len(s.tokens) == s.req["max_tokens"] for s in finished + [a1, a2, b]),
        "same_prompt_twice_same_tokens": a1.tokens == a2.tokens,
        "kv_blocks_back_to_zero": after_drain["kv_blocks_in_use"] == 0,
        "returned_tokens_within_margin_of_float32_reference": worst_margin <= chk["logit_margin"],
        "no_compile_in_window": stats["after"]["compiles"] == stats["before"]["compiles"],
        "some_request_finished": len(finished) > 0,
    }
    late = [s.t_sent - s.due for s in in_window] if tr["mode"] == "open" else []
    values = {
        "t_window_start": t0,
        "deploy_ready_s": t_deployed - job["t_init"],
        "out_tokens_in_window": out_tokens, "asked_tokens": asked_tokens,
        "requests_in_window": len(in_window), "requests_finished": len(finished),
        "first_tokens_in_window": sum(1 for s in streams if s.t_first and t0 <= s.t_first < t_end),
        "waiting_middle": stats["middle"]["waiting"], "waiting_after": stats["after"]["waiting"],
        "kv_blocks_middle": stats["middle"]["kv_blocks_in_use"],
        "kv_blocks_after": stats["after"]["kv_blocks_in_use"],
        "worst_logit_margin": worst_margin,
        "rate_tokens": rate_tokens, "rate_s": rate_s,
        "serve_out_tokens_per_s": rate_tokens / rate_s,
    }
    if tr["mode"] == "open":
        # a request with no first token by the end of the drain waited at
        # least that long: it enters the tail at the time it was given up
        gave_up = max([s.t_done or 0 for s in in_window] + [t_end])
        ttft = [(s.t_first if s.t_first else gave_up) - s.due for s in in_window]
        gaps = [g for s in in_window for g in s.gaps]
        values.update({
            "ttft_p90_ms": 1000 * percentile(ttft, 90), "ttft_p50_ms": 1000 * percentile(ttft, 50),
            "itl_p95_ms": 1000 * percentile(gaps, 95), "itl_p50_ms": 1000 * percentile(gaps, 50),
            "gen_late_p95_ms": 1000 * percentile(late, 95), "itl_samples": len(gaps),
            "delivered_tokens": sum(len(s.tokens) for s in in_window),
        })
    if job.get("keep"):  # --keep: when the tokens came, for a look at a run by hand
        os.makedirs(job["keep"], exist_ok=True)
        with open(os.path.join(job["keep"], "bursts.json"), "w") as f:
            json.dump({"t0": t0, "t_end": t_end, "bursts": bursts(streams)}, f)
    window_stats = {"before": stats["before"], "after": stats["after"],
                    "window_s": stats["after"]["t"] - stats["before"]["t"]}
    print("[serve] " + ", ".join(f"{k}={v}" for k, v in values.items()), flush=True)
    print(f"[serve] checks={checks} failed_streams={[s.summary for s in bad][:3]}", flush=True)
    # the engine's counters at the window's ends, for benchmark/spread.py
    print("[serve] stats=" + json.dumps(window_stats), flush=True)
    return {
        "checks": checks, "attempted": len(in_window), "failed": failed,
        "values": values, "device": device, "trace": trace, "stats": window_stats,
    }


def _cycle(pool):
    while True:
        yield from pool


def stop():
    from ray_tpu import serve

    serve.shutdown()

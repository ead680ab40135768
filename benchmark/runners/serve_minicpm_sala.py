"""The serve runner for the MiniCPM-SALA family: the client side of
``runners/serve.py`` (one replica behind ``serve.run``, driven through a
streaming handle; closed loops only) with what this family needs:

- the engine serves ``engine.max_model_len`` positions of the model's
  published 524,288 (a block table a lane a step is as wide as the
  longest sequence it may hold), so the deployment is made here;
- prompts go in by chunks, so the shapes to warm up are the chunk's
  buckets (every power of two up to ``engine.prefill_chunk``), not the
  prompts' lengths: one short prompt of each;
- the float32 reference is ``benchmark/reference_minicpm_sala.py``, and
  the program's own logits are held to it, not its tokens alone, for two
  set-up requests, one under ``dense_len`` and one past it: the prompt's
  through the family's chunked prefill (the last chunk's program, reading
  what the engine's own programs wrote for the chunks before it), the
  answer's through its paged decode at the engine's lane count over the
  engine's OWN cache.  Beside the distance, the share of the blocks the
  decode steps chose that the reference keeps too (over the larger of
  the two counts): under random weights
  the block scores are near uniform, and logits barely see a selection
  that is wrong;
- the checks also hold the preset to the configuration file's mixers and
  sparse sizes, and the state slots back to zero with the blocks;
- the window opens a fixed time after every lane has its first token,
  not after the first send (``drive_from_full`` says why);
- the change of ``kv_positions_gathered`` over the window's decode steps
  gives the decode kernel's least time a call
  (``flops_sala.sparse_decode_work``), held against a call's time in the
  trace for ``sparse_paged_decode_attention_roofline``.

A checkout whose program has no ``ray_tpu.models.minicpm_sala`` fails
here at once, before anything is deployed.  This process imports no JAX.
"""

from __future__ import annotations

import collections
import importlib.util
import json
import os
import re
import time

from benchmark import flops, flops_sala, spec
from benchmark import traffic as traffic_mod
from benchmark.runners.serve import (  # noqa: F401 - stop is the harness's hook
    APP, BURST_S, DEPLOYMENT, STALL_S, _cycle, _rep_device, _rep_install, _rep_stats, _send, _settle,
    bursts, edge_rate, stop,
)
from benchmark.runners.serve_olmoe import _rep_trace_facts, _rep_trace_start, from_the_head

FAMILY = "ray_tpu.models.minicpm_sala"
# the configuration file's keys the preset must agree with, beside the
# sizes every serve cell checks: (key of the file, attribute of the config)
SALA_KEYS = (("intermediate_size", "intermediate_size"), ("num_key_value_heads", "n_kv_head"),
             ("head_dim", "head_dim"), ("lightning_nh", "lightning_nh"),
             ("lightning_head_dim", "lightning_head_dim"), ("scale_emb", "scale_emb"),
             ("scale_depth", "scale_depth"), ("dim_model_base", "dim_model_base"),
             ("rope_theta", "rope_theta"), ("rms_norm_eps", "rms_norm_eps"))
KERNEL = re.compile(r"^sparse_paged_decode_attention")  # its name in the device trace
# One request is sent at a time, this long after the last: the engine
# admits in the order requests REACH it, and 64 sent at once (4k to 32k
# ids each) reached it in another order in 2 runs of 6, a short one
# overtaking a long one on the way: the first 16 prompts then differ (a
# lead-in of 26.0 s for 19.5) or their successors do (73 chunks and 18
# joins in the window for 75 and 19: 179.9 tokens/s for 198.8; my chip
# runs, PR 30).  The first prompt alone takes over a second of the chip,
# so every lane's first request is there long before it is needed.
SEND_GAP_S = 0.05


# ----------------------------------------------------------------------
# replica side (the process that holds the chip)
# ----------------------------------------------------------------------
def _rep_sala_sizes(rep):
    cfg = rep.callable.engine.model_cfg
    out = {key: getattr(cfg, attr) for key, attr in SALA_KEYS}
    out["mixer_types"] = list(cfg.mixer_types)
    out["sparse_config"] = {k: getattr(cfg, k) for k in (
        "kernel_size", "kernel_stride", "block_size", "init_blocks", "window_size", "topk", "dense_len")}
    out["max_context"] = rep.callable.engine.max_ctx
    return out


def _rep_reference(rep, sequences, n_prompts, wrong=None):
    """The engine's answers against the plain float32 forward over the
    whole of each sequence (prompt + the tokens the engine returned), on
    the engine's own weights, after the drain (the engine is idle).
    `sequences` may differ in length; sequence i goes to lane i.
    -> margin: how far a returned token's logit lies under the
    reference's largest, at most; prefill and decode: how far the
    program's logits lie from the reference's over the whole vocabulary,
    at most, each of the answer's positions through the path that gave
    its token (the first from the family's ``prefill_chunk`` on the
    prompt's last chunk, the chunks before it written by the engine's own
    prefill program; the others from its paged decode at the engine's
    lane count over the engine's own cache, which the engine's own decode
    program then writes), for each sequence; agree: of the blocks the
    decode steps chose at positions past ``dense_len``, the share the
    reference keeps too, over the larger of the two counts (a path that
    chooses fewer blocks, all of them right, does not read 1); resampled: the tokens the engine's programs gave
    otherwise this time.  `wrong` (a dict of config fields) computes the
    program's side with that config: the builder's wrong-on-purpose
    readings."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_minicpm_sala as reference
    from ray_tpu.models import minicpm_sala as sala

    eng = rep.callable.engine
    cfg, bm, lanes = eng.model_cfg, eng.bm, eng.config.max_batch_size
    run_cfg = dataclasses.replace(cfg, **wrong) if wrong else cfg
    bs, most = bm.block_size, eng._spec.prefill_chunk
    pages = bm.blocks_needed(eng.max_ctx)
    chunk_logits = jax.jit(lambda params, cache, *a: sala.prefill_chunk(params, run_cfg, cache, *a, bs)[0])
    decode_chosen = jax.jit(lambda params, cache, *a: sala.decode_chosen(params, run_cfg, cache, *a, bs)[::6])

    seqs = [np.asarray(s, np.int32) for s in sequences]
    ids = [f"reference-{i}" for i in range(len(seqs))]
    want, keeps, margin = [], [], 0.0
    for seq, n in zip(seqs, n_prompts):
        positions = list(range(n - 1, len(seq) - 1))
        logits, keep = reference.full_logits(eng.params, jnp.asarray(seq), cfg, positions)
        logits = np.asarray(logits)
        want.append(logits)
        keeps.append(np.asarray(keep)[:, n:len(seq) - 1])  # [Lp, answer's positions but the last, G, NB]
        margin = max(margin, max(float(row.max() - row[seq[pos + 1]]) for row, pos in zip(logits, positions)))

    # the prompts: chunk by chunk into the cache by the engine's own
    # program (arrays made anew for every call, as the engine makes them)
    distances = {"prefill": [], "decode": []}
    for lane, (rid, seq, n) in enumerate(zip(ids, seqs, n_prompts)):
        bm.allocate(rid, len(seq))
        for start in range(0, n, most):
            m = min(most, n - start)
            bucket = eng._prefill_bucket(m, most)
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :m] = seq[start:start + m]
            bm.advance(rid, m)
            last, table = np.array([m - 1], np.int32), bm.block_table(rid, pages)
            if start + m == n:
                got = chunk_logits(eng.params, eng.cache, toks, np.int32(start), last, table, np.int32(lane))
                distances["prefill"].append(float(np.abs(np.asarray(got[0], np.float32) - want[lane][0]).max()))
            eng._run_on_cache(eng._prefill_jit, toks, bm.phys_indices(rid, start + m, bucket, start=start),
                              last, np.zeros(1, np.float32), eng._next_rng(), np.int32(start), table,
                              np.int32(lane))

    # the answers: each position's logits from the cache as it lies, then
    # the engine's own decode program writes that position
    steps = min(len(seq) - n for seq, n in zip(seqs, n_prompts)) - 1
    resampled, agree, chosen_n = 0, 0, 0
    per_seq = [[] for _ in seqs]
    for step in range(steps):
        tok, lengths, write = (np.zeros(lanes, np.int32) for _ in range(3))
        tables = np.zeros((lanes, pages), np.int32)
        for lane, (rid, seq, n) in enumerate(zip(ids, seqs, n_prompts)):
            tok[lane], lengths[lane] = seq[n + step], n + step
            tables[lane] = bm.block_table(rid, pages)
            bm.advance(rid, 1)
            write[lane] = bm.phys_index(rid, n + step)
        got, (blocks, counts) = decode_chosen(eng.params, eng.cache, tok, tables, lengths)
        got, blocks, counts = np.asarray(got, np.float32), np.asarray(blocks), np.asarray(counts)
        for lane, n in enumerate(n_prompts):
            per_seq[lane].append(float(np.abs(got[lane] - want[lane][step + 1]).max()))
            if n + step >= cfg.dense_len:
                keep = keeps[lane][:, step]  # [Lp, G, NB]
                for layer in range(keep.shape[0]):
                    for g in range(keep.shape[1]):
                        mine = blocks[layer, lane, g, :counts[layer, lane, g]]
                        agree += int(keep[layer, g][mine].sum())
                        chosen_n += max(len(mine), int(keep[layer, g].sum()))
        nxt = np.asarray(eng._run_on_cache(eng._decode_jit, tok, lengths, tables, write,
                                           np.zeros(lanes, np.float32), eng._next_rng()))
        resampled += sum(int(nxt[lane] != seq[n + step + 1]) for lane, (seq, n) in enumerate(zip(seqs, n_prompts)))
    for rid in ids:
        bm.free(rid)
    distances["decode"] = [max(d) for d in per_seq]
    # numpy's max keeps a NaN, which then fails the limit
    return {"margin": margin, "resampled": resampled,
            "prefill": float(np.max(distances["prefill"])), "decode": float(np.max(distances["decode"])),
            "by_sequence": distances, "agree": agree / chosen_n if chosen_n else None, "chosen": chosen_n}


# ----------------------------------------------------------------------
# client side
# ----------------------------------------------------------------------
def deploy(job):
    """As ``serve.deploy``, with the engine's ``max_model_len``."""
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
        max_queue=eng["max_queue"], max_model_len=eng["max_model_len"], name=DEPLOYMENT,
    )
    try:
        handle = serve.run(llm.build_app(llm_config, num_replicas=1), name=APP)
    except TimeoutError:
        # serve.run gives a replica 60 s; making 10 GB of weights and the
        # cache takes longer cold, and the deployment goes on
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
    return handle, ray_tpu.get_actor(reps[0]["actor_name"], "serve")


def chunk_buckets(most: int) -> list:
    """Every shape a prefill program can have: the powers of two from
    the engine's least bucket to the chunk."""
    out, n = [], 8
    while n < most:
        out.append(n)
        n *= 2
    return out + [most]


def setup_checks(job, stream_handle):
    """Warm up every chunk bucket and the decode program, then the
    requests the correctness checks need: the short prompt twice, and
    the long one."""
    chk, vocab = job["cell"]["checks"], job["sizes"]["vocab_size"]
    lens = chunk_buckets(job["cell"]["engine"]["prefill_chunk"])
    _settle(stream_handle, traffic_mod.fixed_requests(lens, 3, vocab, job["seed"] + 7), timeout_s=1800)
    short, long_ = chk["prompt_lens"]
    a, b = traffic_mod.fixed_requests([short, long_], chk["max_tokens"], vocab, job["seed"] + 11)
    return _settle(stream_handle, [a, a, b], timeout_s=900)


def drive_from_full(stream_handle, plan, lanes, lead_in, seconds, at):
    """``serve.drive``'s closed loop with the window's start locked to
    the ENGINE's timeline: it opens ``lead_in["after_full_s"]`` seconds
    after the instant the ``lanes``-th request has its first token
    (every lane has taken its first prompt in and decodes), not a fixed
    time after the first request was sent.  `at`: [(offset_s from the
    window's start, callable)].  -> (every stream opened, the window's
    start, when the first request was sent).

    Why: the engine dispatches the first 16 prompts' 55 chunks back to
    back (16.9 s without a token), and what follows is not stationary
    yet: a window taken 2 s earlier on the same timeline reads 211
    tokens/s where this one reads 183 (its first seconds are all lanes
    decoding), and one run of seven on the chip had its timeline slip by
    that much against the clock of the first send (PERF.md section 6, PR
    30).  Requests go out one at a time (SEND_GAP_S).  Where the lanes never fill, the window opens ``at_most_s``
    after the first send and the run's checks say so."""
    at = collections.deque(sorted(at, key=lambda p: p[0]))
    source = iter(plan["requests"])
    streams, live = [], []
    t_begin, t0, t_end, t_past, t_sent = time.time(), None, None, None, 0.0
    while True:
        now = time.time()
        if t0 is None:
            firsts = sorted(s.t_first for s in streams if s.t_first)
            if len(firsts) >= lanes:
                t0 = firsts[lanes - 1] + lead_in["after_full_s"]
            elif now >= t_begin + lead_in["at_most_s"]:
                t0 = now
            t_end = None if t0 is None else t0 + seconds
        while t0 is not None and at and now >= t0 + at[0][0]:
            at.popleft()[1]()
        if len(live) < plan["clients"] and (t0 is None or now < t_end) and now >= t_sent + SEND_GAP_S:
            live.append(_send(stream_handle, next(source), now))
            streams.append(live[-1])
            t_sent = now
        got = any([s.poll() for s in live])
        if t0 is not None and t_past is None and now >= t_end and any(
                s.token_t and s.token_t[-1] >= t_end for s in live):
            t_past = now  # the far edge's first token; its step's others follow within BURST_S
        live = [s for s in live if not s.done]
        if t0 is not None and (now >= t_end + STALL_S or (t_past is not None and now >= t_past + 5 * BURST_S)):
            break
        if not got:
            time.sleep(0.0005)
    while at:
        at.popleft()[1]()
    for s in live:  # what is still running when the window ends
        s.gen.close()
    return streams, t0, t_begin


def kernel_roofline_pct(config, trace, before, after, peak):
    """The least time the chip could take for ONE call of the decode
    kernel, from what the window's decode steps copied on average
    (``kv_positions_gathered`` over ``steps`` x sparse layers calls),
    over the time a call took in the trace (the kernel's device seconds
    over its calls there).  A call, not a second: in this cell decode
    steps come in bursts between prompts, and the engine's counters
    cannot be read at the trace's own edges (the replica's loop waits
    seconds for a prompt's chunks).  None where there is nothing to read."""
    if not trace or not trace.get("devices") or not peak:
        return None
    named = [n for n in trace["op_seconds"] if KERNEL.search(n)]
    kernel_s = sum(trace["op_seconds"][n] for n in named)
    calls = sum(trace.get("op_counts", {}).get(n, 0) for n in named)
    if kernel_s <= 0 or not calls or "kv_positions_gathered" not in after:
        return None
    sparse_layers = config["mixer_types"].count("minicpm4")
    steps = after["steps"] - before["steps"]
    if steps <= 0:
        return None
    pairs = steps * after["max_batch_size"] * config["num_key_value_heads"] * sparse_layers
    work = flops_sala.sparse_decode_work(
        config, after["kv_positions_gathered"] - before["kv_positions_gathered"], pairs)
    least = flops.least_seconds(work, peak)["seconds"] / (steps * sparse_layers)
    return 100.0 * least / (kernel_s / calls)


def run(job) -> dict:
    if importlib.util.find_spec(FAMILY) is None:
        raise RuntimeError(f"this checkout's program has no {FAMILY}: it cannot run {job['config']['name']}")
    import ray_tpu

    cell, tr, config = job["cell"], job["cell"]["traffic"], job["config"]
    if tr["mode"] != "closed":
        raise ValueError("runners/serve_minicpm_sala.py drives closed loops only")
    seconds, seed = job["seconds"], job["seed"]
    handle, actor = deploy(job)
    t_deployed = time.time()

    def call(fn, *args):
        return actor.__ray_call__.remote(fn, *args)

    installed = ray_tpu.get(call(_rep_install), timeout=600)
    sala_sizes = ray_tpu.get(call(_rep_sala_sizes), timeout=120)
    stream_handle = handle.options(stream=True)
    a1, a2, b = setup_checks(job, stream_handle)

    pool = from_the_head(
        traffic_mod.make_requests(tr["pool_requests"], tr, job["sizes"]["vocab_size"], seed), seed)
    plan = {"mode": "closed", "clients": tr["clients"], "requests": _cycle(pool)}
    probes = {}

    def probe(name, fn=_rep_stats):
        return lambda: probes.__setitem__(name, call(fn))

    at = [(0.0, probe("before")), (seconds / 2, probe("middle")), (seconds, probe("after"))]
    if job["trace"]:
        # the window's last seconds; stopped only after the drain (serve.run says why)
        at.append((seconds - tr["trace_seconds"], probe("trace_start", _rep_trace_start)))
    streams, t0, t_begin = drive_from_full(
        stream_handle, plan, cell["engine"]["max_batch_size"], tr["lead_in"], seconds, at)
    t_end = t0 + seconds

    stats = {k: ray_tpu.get(v, timeout=300) for k, v in probes.items()}
    after_drain = ray_tpu.get(call(_rep_stats), timeout=300)
    deadline = time.time() + 120  # the chunks of the prompts in flight when the streams closed
    while (after_drain["kv_blocks_in_use"] or after_drain["state_slots_in_use"]) and time.time() < deadline:
        time.sleep(0.5)
        after_drain = ray_tpu.get(call(_rep_stats), timeout=300)
    trace = None
    if job["trace"]:
        trace = ray_tpu.get(call(_rep_trace_facts, tr["trace_seconds"], job.get("keep_trace"),
                                 cell.get("trace_annotations", ())), timeout=900)

    # the float32 reference, outside the window
    chk = cell["checks"]
    sequences = [s.req["prompt"] + s.tokens for s in (a1, b)]
    ref = ray_tpu.get(call(_rep_reference, sequences, chk["prompt_lens"]), timeout=1800)
    device = ray_tpu.get(call(_rep_device), timeout=120)

    finished = [s for s in streams if s.done and not s.failed and t0 <= s.t_done < t_end]
    bad = [s for s in streams if s.failed]
    out_tokens = sum(1 for s in streams for t in s.token_t if t0 <= t < t_end)
    rate_tokens, rate_s = edge_rate(streams, t0, t_end)  # whole engine steps (serve.edge_rate)

    sizes = job["sizes"]
    before, after = stats["before"], stats["after"]
    checks = {
        "preset_has_the_configuration's_sizes": all(
            installed[k] == sizes[k] for k in ("n_layer", "n_embd", "n_head", "n_positions", "vocab_rows")
        ) and sizes["dtype"] == installed["dtype"],
        "preset_has_the_configuration's_mixers": all(
            sala_sizes[key] == config[key] for key, _ in SALA_KEYS
        ) and sala_sizes["mixer_types"] == config["mixer_types"] and (
            sala_sizes["sparse_config"] == config["assumed"]["sparse_config"]),
        "engine_serves_max_model_len": sala_sizes["max_context"] == cell["engine"]["max_model_len"],
        "finished_requests_returned_max_tokens": all(
            len(s.tokens) == s.req["max_tokens"] for s in finished + [a1, a2, b]),
        "same_prompt_twice_same_tokens": a1.tokens == a2.tokens,
        "kv_blocks_back_to_zero": after_drain["kv_blocks_in_use"] == 0,
        "state_slots_back_to_zero": after_drain["state_slots_in_use"] == 0,
        "returned_tokens_within_margin_of_float32_reference": ref["margin"] <= chk["logit_margin"],
        "prefill_logits_within_distance_of_float32_reference": ref["prefill"] <= chk["logit_distance"],
        "paged_decode_logits_within_distance_of_float32_reference": ref["decode"] <= chk["logit_distance"],
        "chosen_blocks_agree_with_float32_reference": (
            ref["agree"] is not None and ref["agree"] >= chk["selection_agreement_min"]),
        "no_compile_in_window": after["compiles"] == before["compiles"],
        "some_request_finished": len(finished) > 0,
        "every_lane_decoded_before_the_window": t0 - t_begin < tr["lead_in"]["at_most_s"],
    }
    values = {
        "t_window_start": t0, "lead_in_s": t0 - t_begin,
        "deploy_ready_s": t_deployed - job["t_init"],
        "out_tokens_in_window": out_tokens,
        "asked_tokens": sum(s.req["max_tokens"] for s in finished),
        "requests_finished": len(finished),
        "first_tokens_in_window": sum(1 for s in streams if s.t_first and t0 <= s.t_first < t_end),
        "joined_in_window": after["joined"] - before["joined"],
        # where a window's time went, without a traced run
        **{k + "_in_window": after[k] - before[k]
           for k in ("steps", "decode_fetch_s", "prefill_fetch_s", "idle_s", "stall_s", "total_tokens",
                     "prefill_chunks", "prompt_tokens")},
        "running_before": before["running"], "waiting_middle": stats["middle"]["waiting"],
        "waiting_after": after["waiting"], "kv_blocks_middle": stats["middle"]["kv_blocks_in_use"],
        "kv_blocks_after": after["kv_blocks_in_use"],
        "worst_logit_margin": ref["margin"], "worst_logit_distance_prefill": ref["prefill"],
        "worst_logit_distance_decode": ref["decode"], "logit_distance_by_sequence": ref["by_sequence"],
        "chosen_blocks_agree": ref["agree"], "chosen_blocks": ref["chosen"],
        "replay_resampled_tokens": ref["resampled"],
        "rate_tokens": rate_tokens, "rate_s": rate_s,
        "serve_out_tokens_per_s": rate_tokens / rate_s,
    }
    if job["trace"]:
        values["sparse_paged_decode_attention_roofline"] = kernel_roofline_pct(
            config, trace, before, after, spec.load_peaks().get(device["kind"]))
    if job.get("keep"):  # --keep: when the tokens came, for a look at a run by hand
        os.makedirs(job["keep"], exist_ok=True)
        with open(os.path.join(job["keep"], "bursts.json"), "w") as f:
            json.dump({"t0": t0, "t_end": t_end, "bursts": bursts(streams)}, f)
    print("[serve] " + ", ".join(f"{k}={v}" for k, v in values.items()), flush=True)
    print(f"[serve] checks={checks} failed_streams={[s.summary for s in bad][:3]}", flush=True)
    return {
        "checks": checks, "attempted": len(finished) + len(bad), "failed": len(bad),
        "values": values, "device": device, "trace": trace,
        "stats": {"before": before, "after": after, "window_s": after["t"] - before["t"]},
    }

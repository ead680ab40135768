"""The serve runner for the OLMoE family: the client side of
``runners/serve.py`` (one replica behind ``serve.run``, driven through a
streaming handle; closed loops only, the one kind this family has a
cell of) with what another family needs:

- the float32 reference is ``benchmark/reference_olmoe.py``, and the
  program's own logits are held to it too, not its tokens alone: the
  prefill's, and the paged decode's over the engine's own pool;
- the checks also hold the preset to the configuration file's expert
  sizes and the engine's ``moe_pairs`` to the rows its programs were
  given (a pair dropped anywhere makes the run not ``correct``);
- the pool's cycle starts at the head of the mix's fixed order for every
  seed (``from_the_head``): a window is shorter than one cycle here;
- the replica's trace is reduced with the names in the cell's
  ``trace_annotations`` (the engine's spans), so idle gaps are labelled;
- ``stats()`` is also taken where the trace starts, and the expert
  counters' change from there to the window's end gives the grouped
  matmul's least time (``flops_moe.grouped_matmul_work``) for
  ``moe_gmm_roofline_pct``.

A checkout whose program has no ``ray_tpu.models.olmoe`` fails here at
once, before anything is deployed.  This process imports no JAX.
"""

from __future__ import annotations

import importlib.util
import json
import os
import random
import re
import time

from benchmark import flops, flops_moe, spec
from benchmark import traffic as traffic_mod
from benchmark.runners import serve
from benchmark.runners.serve import (  # noqa: F401 - stop is the harness's hook
    _REPLICA, _cycle, _rep_device, _rep_install, _rep_stats, bursts, deploy, drive,
    edge_rate, setup_checks, stop,
)

FAMILY = "ray_tpu.models.olmoe"
# the configuration file's keys the preset must agree with, beside the
# sizes every serve cell checks
MOE_KEYS = ("num_experts", "num_experts_per_tok", "intermediate_size", "rope_theta",
            "norm_topk_prob")
GMM = re.compile(r"^moe_gmm")  # ops/moe.py: the grouped matmul's name in the device trace


# ----------------------------------------------------------------------
# replica side (the process that holds the chip)
# ----------------------------------------------------------------------
def _rep_moe_sizes(rep):
    cfg = rep.callable.engine.model_cfg
    return {k: getattr(cfg, k) for k in MOE_KEYS}


def _rep_trace_start(rep):
    """Start the profiler; the engine's counters at that instant."""
    serve._rep_trace_start(rep)
    return _rep_stats(rep)


def _rep_trace_facts(rep, seconds, keep_dir, annotations):
    """As ``serve._rep_trace_facts``, with the host spans named."""
    tracer = _REPLICA.pop("tracer", None)
    if tracer is None:
        return None
    tracer.stop()
    return tracer.facts(tuple(annotations), keep_dir, first_s=seconds)


def _rep_reference(rep, sequences, n_prompt):
    """The engine's answers against the plain float32 forward over the
    whole of each sequence (prompt + the tokens the engine returned), on
    the engine's own weights, after the drain (the engine is idle).
    -> margin: how far a returned token's logit lies under the
    reference's largest, at most (it moves only when an argmax flips);
    prefill and decode: how far the program's logits lie from the
    reference's over the whole vocabulary, at most (what a lower
    precision shows in), each of the answer's positions through the
    path that gave its token: the first from the family's prefill at
    the prompt's bucket, the others from its paged decode at the
    engine's lane count over the engine's OWN pool, into which the
    engine's own prefill and decode programs wrote the sequence again;
    resampled: the tokens those programs gave otherwise this time."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_olmoe
    from ray_tpu.models import olmoe

    eng = rep.callable.engine
    cfg, bm, lanes = eng.model_cfg, eng.bm, eng.config.max_batch_size
    toks = np.asarray(sequences, dtype=np.int32)  # [S, T]: the set-up requests have one shape
    want = np.asarray(reference_olmoe.full_logits(eng.params, jnp.asarray(toks), cfg))
    length = toks.shape[1]
    margin = max(float(row[pos].max() - row[pos, seq[pos + 1]])
                 for row, seq in zip(want, toks) for pos in range(n_prompt - 1, length - 1))

    def distance(got, pos):
        got = np.asarray(got, np.float32)[:len(toks)]
        return float(np.abs(got - want[:, pos]).max())

    prefill_logits = jax.jit(lambda params, t, last: olmoe.prefill_forward(params, cfg, t, last_index=last)[0])
    decode_logits = jax.jit(lambda params, tok, k_pages, v_pages, tables, lengths: olmoe.decode_forward_paged(
        params, cfg, tok, k_pages, v_pages, tables, lengths, bm.block_size)[0])

    # the prompts: their last position's logits, and their K/V into the
    # pool by the engine's own prefill program (arrays made anew for
    # every call, as the engine makes them: a dispatch may still read one)
    ids = [f"reference-{i}" for i in range(len(toks))]
    bucket = eng._prefill_bucket(n_prompt, eng.max_ctx)
    last, first = np.array([n_prompt - 1], np.int32), []
    for rid, seq in zip(ids, toks):
        prompt = np.zeros((1, bucket), np.int32)
        prompt[0, :n_prompt] = seq[:n_prompt]
        first.append(prefill_logits(eng.params, prompt, last)[0])
        bm.allocate(rid, length)
        bm.advance(rid, n_prompt)
        _, eng.k_pages, eng.v_pages = eng._prefill_jit(
            eng.params, eng.k_pages, eng.v_pages, prompt, bm.phys_indices(rid, n_prompt, bucket),
            last, np.zeros(1, np.float32), eng._next_rng())
    distances = {"prefill": [distance(np.stack(first), n_prompt - 1)], "decode": []}

    # the answers: each position's logits from the pool as it lies, then
    # the engine's own decode program writes that position (as _decode_once)
    resampled = 0
    for pos in range(n_prompt, length - 1):
        tok, lengths, write = (np.zeros(lanes, np.int32) for _ in range(3))
        tables = np.zeros((lanes, bm.blocks_needed(eng.max_ctx)), np.int32)
        for lane, rid in enumerate(ids):
            tok[lane], lengths[lane] = toks[lane, pos], pos
            tables[lane] = bm.block_table(rid, tables.shape[1])
            bm.advance(rid, 1)
            write[lane] = bm.phys_index(rid, pos)
        distances["decode"].append(distance(
            decode_logits(eng.params, tok, eng.k_pages, eng.v_pages, tables, lengths), pos))
        nxt, eng.k_pages, eng.v_pages = eng._decode_jit(
            eng.params, eng.k_pages, eng.v_pages, tok, lengths, tables, write,
            np.zeros(lanes, np.float32), eng._next_rng())
        resampled += int((np.asarray(nxt)[:len(toks)] != toks[:, pos + 1]).sum())
    for rid in ids:
        bm.free(rid)
    # numpy's max keeps a NaN, which then fails the limit
    return {"margin": margin, "resampled": resampled, **{k: float(np.max(v)) for k, v in distances.items()}}


# ----------------------------------------------------------------------
# client side
# ----------------------------------------------------------------------
def from_the_head(pool, seed):
    """The seed's requests in the mix's own fixed order, whatever the
    seed: the rotation ``traffic.make_requests`` gave them
    (``random.Random(seed).randrange(n)`` places on) taken back.  A
    window of this cell joins fewer requests than one cycle of its pool
    holds, and answers of up to 512 tokens stay in a lane for half the
    window, so where the cycle began decided which of them lay across
    the window's two ends: 2% of the rate between seeds, beside 0.3-0.5%
    between two runs of one order (PERF.md section 6, PR 26).  Every run
    now does the same work in the same order, on token ids and weights
    of the seed's own."""
    k = random.Random(seed).randrange(len(pool))
    return pool[-k:] + pool[:-k]


def gmm_roofline_pct(config, trace, at_trace_start, after, peak):
    """The least time the chip could take for the grouped matmuls the
    counters saw between the trace's start and the window's end, a
    second of host time, over the kernel's device seconds a second of
    the traced window.  None where there is nothing to read."""
    if not trace or not trace.get("devices") or not peak:
        return None
    kernel_s = sum(s for name, s in trace["op_seconds"].items() if GMM.search(name))
    span = after["t"] - at_trace_start["t"]
    if kernel_s <= 0 or span < 0.5 or "moe_pairs" not in after:
        return None
    work = flops_moe.grouped_matmul_work(
        config, after["moe_pairs"] - at_trace_start["moe_pairs"],
        after["moe_experts_hit"] - at_trace_start["moe_experts_hit"])
    least = flops.least_seconds(work, peak)["seconds"]
    return 100.0 * (least / span) / (kernel_s / trace["window_s"])


def run(job) -> dict:
    if importlib.util.find_spec(FAMILY) is None:
        raise RuntimeError(f"this checkout's program has no {FAMILY}: it cannot run {job['config']['name']}")
    import ray_tpu

    cell, tr, config = job["cell"], job["cell"]["traffic"], job["config"]
    if tr["mode"] != "closed":
        raise ValueError("runners/serve_olmoe.py drives closed loops only")
    seconds, seed = job["seconds"], job["seed"]
    handle, actor = deploy(job)
    t_deployed = time.time()

    def call(fn, *args):
        return actor.__ray_call__.remote(fn, *args)

    installed = ray_tpu.get(call(_rep_install), timeout=600)
    moe_sizes = ray_tpu.get(call(_rep_moe_sizes), timeout=120)
    stream_handle = handle.options(stream=True)
    a1, a2, b = setup_checks(job, stream_handle)

    pool = from_the_head(
        traffic_mod.make_requests(tr["pool_requests"], tr, job["sizes"]["vocab_size"], seed), seed)
    plan = {"mode": "closed", "clients": tr["clients"], "requests": _cycle(pool)}
    lead_in = tr["lead_in_s"]

    probes = {}

    def probe(name, fn=_rep_stats):
        return lambda: probes.__setitem__(name, call(fn))

    at = [(lead_in, probe("before")), (lead_in + seconds / 2, probe("middle")),
          (lead_in + seconds, probe("after"))]
    if job["trace"]:
        # the window's last seconds; stopped only after the drain (serve.run says why)
        at.append((lead_in + seconds - tr["trace_seconds"], probe("trace_start", _rep_trace_start)))
    t_begin = time.time() + 0.05
    t0 = t_begin + lead_in  # the first measured instant
    streams = drive(stream_handle, plan, t_begin, lead_in + seconds, at)
    t_end = t0 + seconds

    stats = {k: ray_tpu.get(v, timeout=120) for k, v in probes.items()}
    after_drain = ray_tpu.get(call(_rep_stats), timeout=120)
    deadline = time.time() + 30
    while after_drain["kv_blocks_in_use"] and time.time() < deadline:
        time.sleep(0.2)
        after_drain = ray_tpu.get(call(_rep_stats), timeout=120)
    trace = None
    if job["trace"]:
        trace = ray_tpu.get(call(_rep_trace_facts, tr["trace_seconds"], job.get("keep_trace"),
                                 cell.get("trace_annotations", ())), timeout=600)

    # the float32 reference, outside the window
    chk = cell["checks"]
    sequences = [s.req["prompt"] + s.tokens for s in (a1, b)]
    ref = ray_tpu.get(call(_rep_reference, sequences, chk["prompt_len"]), timeout=900)
    device = ray_tpu.get(call(_rep_device), timeout=120)

    finished = [s for s in streams if s.done and not s.failed and t0 <= s.t_done < t_end]
    bad = [s for s in streams if s.failed]
    out_tokens = sum(1 for s in streams for t in s.token_t if t0 <= t < t_end)
    rate_tokens, rate_s = edge_rate(streams, t0, t_end)  # whole engine steps (serve.edge_rate)

    sizes = job["sizes"]
    before, after = stats["before"], stats["after"]
    # every row a program was given went to its experts_per_tok experts
    # in every layer: max_batch_size rows a decode program, the padded
    # prompt a prefill
    rows = after["max_batch_size"] * (after["steps"] - before["steps"]) + (
        after["prefill_bucket_tokens"] - before["prefill_bucket_tokens"])
    pairs = after.get("moe_pairs", 0) - before.get("moe_pairs", 0)
    checks = {
        "preset_has_the_configuration's_sizes": all(
            installed[k] == sizes[k] for k in ("n_layer", "n_embd", "n_head", "n_positions", "vocab_rows")
        ) and sizes["dtype"] == installed["dtype"],
        "preset_has_the_configuration's_experts": all(moe_sizes[k] == config[k] for k in MOE_KEYS),
        "finished_requests_returned_max_tokens": all(
            len(s.tokens) == s.req["max_tokens"] for s in finished + [a1, a2, b]),
        "same_prompt_twice_same_tokens": a1.tokens == a2.tokens,
        "kv_blocks_back_to_zero": after_drain["kv_blocks_in_use"] == 0,
        "returned_tokens_within_margin_of_float32_reference": ref["margin"] <= chk["logit_margin"],
        "prefill_logits_within_distance_of_float32_reference": ref["prefill"] <= chk["logit_distance"],
        "paged_decode_logits_within_distance_of_float32_reference": ref["decode"] <= chk["logit_distance"],
        "no_compile_in_window": after["compiles"] == before["compiles"],
        "some_request_finished": len(finished) > 0,
        "no_token_expert_pair_dropped": rows > 0 and pairs == (
            config["num_experts_per_tok"] * sizes["n_layer"] * rows),
    }
    values = {
        "t_window_start": t0,
        "deploy_ready_s": t_deployed - job["t_init"],
        "out_tokens_in_window": out_tokens,
        "asked_tokens": sum(s.req["max_tokens"] for s in finished),
        "requests_finished": len(finished),
        "first_tokens_in_window": sum(1 for s in streams if s.t_first and t0 <= s.t_first < t_end),
        "joined_in_window": after["joined"] - before["joined"],
        # where a slow window's time went, without a traced run: steps,
        # seconds the loop waited for the device and for work, and tokens
        **{k + "_in_window": after[k] - before[k]
           for k in ("steps", "decode_fetch_s", "prefill_fetch_s", "idle_s", "stall_s", "total_tokens")},
        "waiting_middle": stats["middle"]["waiting"], "waiting_after": after["waiting"],
        "kv_blocks_middle": stats["middle"]["kv_blocks_in_use"],
        "kv_blocks_after": after["kv_blocks_in_use"],
        "worst_logit_margin": ref["margin"], "worst_logit_distance_prefill": ref["prefill"],
        "worst_logit_distance_decode": ref["decode"], "replay_resampled_tokens": ref["resampled"],
        "moe_rows": rows, "moe_pairs": pairs,
        "rate_tokens": rate_tokens, "rate_s": rate_s,
        "serve_out_tokens_per_s": rate_tokens / rate_s,
    }
    if job["trace"]:
        values["moe_gmm_roofline_pct"] = gmm_roofline_pct(
            config, trace, stats["trace_start"], after, spec.load_peaks().get(device["kind"]))
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

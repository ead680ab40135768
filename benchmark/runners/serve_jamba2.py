"""The serve runner for the Jamba family: the client side of
``runners/serve_nemotron_3_nano.py`` (one replica behind ``serve.run``, a
closed loop whose window is locked to the engine's own timeline by
``drive_from_full``, the pool taken ``from_the_head`` of the mix's fixed
order, prompts in chunks, the engine's ``max_model_len``, the replica's
heap frozen after set-up; the roofline share of a decode kernel's call
and the grouped-query kernel's name in the trace are imported from it)
with what this family needs:

- the float32 reference is ``benchmark/reference_jamba2.py`` (the
  Mamba-1 scan a position at a time over the whole sequence, four
  shifted products, one softmax a query with the one K/V head repeated,
  dense SwiGLU, tied head), and the program's own logits are held to it
  for the set-up requests of ``checks.prompt_lens``: inside one small
  bucket; one mid bucket; two grid steps of the chunk kernel and more;
  across a chunk boundary, so that a state and a tail cross it.  The
  prompt's last position goes through the family's last chunk program,
  reading the lane's state and tail and the pages the engine's own
  programs wrote for the chunks before it; the answer's through
  ``mamba1_decode_step`` and the grouped-query kernel at the engine's
  lane count over the engine's OWN pool and lane state;
- the model is DENSE: no router can flip, so EVERY position is held to
  ``logit_distance`` (the largest absolute difference a position) and
  ``logit_margin`` (how far a returned token's logit lies under the
  reference's largest);
- wrong on purpose (``checks.wrong_on_purpose``, the builder's readings
  and the tests'): ``e4m3`` rounds the program's weights; a name of
  ``reference_jamba2.WRONG``, ``inner_norms_off`` (its three at once) or
  ``attention_one_layer_early`` tells the REFERENCE another model
  (``wrong_reference``), which the program must then be far from;
- the checks hold the preset to the configuration file's sizes, widths
  and layer order, the head to the embedding, the engine's cache to what
  the family states (2 paged layers of one K/V head; a tail and a state
  ``[16, 5120]`` a Mamba layer a lane), the lanes' state to 256 x
  9,318,400 B whatever the pool, the state slots back to zero with the
  blocks, and the counters to the steps: every token a decode step gave
  updated 26 states (``ssm_lane_steps``);
- the least work of its kernels by ``benchmark/flops_jamba.py`` (a
  state of 16 x 5,120 float32 in and out; a chunk's rows) and
  ``flops_ssm.gqa_decode_work`` given one K/V head of 128, and
  ``prefill_mfu_pct``.

A checkout whose program has no ``ray_tpu.models.jamba`` fails here at
once, with one line, before anything is deployed.  This process imports
no JAX.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import time

from benchmark import flops, flops_jamba, flops_ssm, spec
from benchmark import traffic as traffic_mod
from benchmark.runners.serve import (  # noqa: F401 - stop is the harness's hook
    _cycle, _rep_device, _rep_install, _rep_stats, _settle, bursts, edge_rate, stop,
)
from benchmark.runners import serve_minicpm_sala as chunked
from benchmark.runners.serve_minicpm_sala import chunk_buckets, deploy, drive_from_full
from benchmark.runners.common import _rep_settle
from benchmark.runners.serve_mistral_small_4 import _round_to_e4m3
from benchmark.runners.serve_nemotron_3_nano import GQA_KERNEL, _kernel_seconds_a_call, kernel_roofline_pct
from benchmark.runners.serve_olmoe import _rep_trace_facts, _rep_trace_start, from_the_head

FAMILY = "ray_tpu.models.jamba"
# the configuration file's keys the preset must agree with, beside the
# sizes every serve cell checks: (key of the file, attribute of the config)
WIDTH_KEYS = (("attn_layer_period", "attn_layer_period"), ("attn_layer_offset", "attn_layer_offset"),
              ("num_key_value_heads", "n_kv_head"), ("intermediate_size", "intermediate_size"),
              ("mamba_expand", "mamba_expand"), ("mamba_d_state", "mamba_d_state"),
              ("mamba_d_conv", "mamba_d_conv"), ("mamba_dt_rank", "mamba_dt_rank"),
              ("rms_norm_eps", "layer_norm_epsilon"))
MAMBA_MIXER = ("in_proj", "conv_w", "conv_b", "x_proj", "dt_norm", "b_norm", "c_norm", "dt_proj", "dt_bias",
               "A_log", "D", "out_proj")
ATTENTION_MIXER = ("wqkv", "wo")
INNER_NORMS = ("no_dt_norm", "no_b_norm", "no_c_norm")
# the kernels' names in the device trace
STEP_KERNEL = re.compile(r"^mamba1_decode_step")
CHUNK_KERNEL = re.compile(r"^mamba1_chunk_scan")


# ----------------------------------------------------------------------
# replica side (the process that holds the chip)
# ----------------------------------------------------------------------
def _rep_sizes(rep):
    eng = rep.callable.engine
    cfg = eng.model_cfg
    return {"config": {key: getattr(cfg, attr) for key, attr in WIDTH_KEYS},
            "layer_types": list(cfg.layer_types), "head_dim": cfg.head_dim,
            "tied_head": "lm_head" not in eng.params,
            "max_context": eng.max_ctx,
            "cache": {k: [list(v.shape), v.dtype.name] for k, v in eng.cache.items()}}


def wrong_reference(numbers: dict, params: dict, wrong: str):
    """``reference_jamba2``'s arguments for a wrong-on-purpose reading ->
    (numbers, the tree, the reference's ``wrong`` names).  A name of
    ``reference_jamba2.WRONG`` is passed on; ``inner_norms_off`` is its
    three norms at once; ``attention_one_layer_early`` tells it
    ``attn_layer_offset`` one less, with each attention layer's mixer
    weights and those of the Mamba layer before it changed places (every
    layer keeps its norms and its MLP), so that the tree is the other
    model's."""
    if wrong == "inner_norms_off":
        return numbers, params, INNER_NORMS
    if wrong != "attention_one_layer_early":
        return numbers, params, (wrong,)
    layers = list(params["layers"])
    for i, lp in enumerate(params["layers"]):
        if ATTENTION_MIXER[0] in lp:
            rest = {k: v for k, v in lp.items() if k not in ATTENTION_MIXER}
            before = {k: v for k, v in layers[i - 1].items() if k not in MAMBA_MIXER}
            layers[i - 1] = {**before, **{k: lp[k] for k in ATTENTION_MIXER}}
            layers[i] = {**rest, **{k: params["layers"][i - 1][k] for k in MAMBA_MIXER}}
    return dict(numbers, attn_layer_offset=numbers["attn_layer_offset"] - 1), dict(params, layers=layers), ()


def _rep_reference(rep, sequences, n_prompts, wrong=None):
    """The engine's answers against the plain float32 forward over the
    whole of each sequence (prompt + the tokens the engine returned), on
    the engine's own weights, after the drain (the engine is idle).
    `sequences` may differ in length; sequence i goes to lane i.  Each of
    the answer's positions goes through the path that gave its token:
    the first from the family's chunk program on the prompt's last chunk
    (the chunks before it written by the engine's own prefill program:
    pages, tails, states), the others from its decode forward at the
    engine's lane count over the engine's own pool and lane state, which
    the engine's own decode program then writes.  -> margin, prefill,
    decode: how far a returned token's logit lies under the reference's
    largest, and how far the program's logits lie from the reference's,
    at most, over EVERY position; resampled: the tokens the engine's
    programs gave otherwise this time.  `wrong`: "e4m3" computes the
    program's side on rounded weights (which leaves the engine's weights
    rounded); any other name tells the reference another model
    (``wrong_reference``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_jamba2 as reference
    from ray_tpu.models import jamba

    eng = rep.callable.engine
    cfg, bm, lanes = eng.model_cfg, eng.bm, eng.config.max_batch_size
    bs, most = bm.block_size, eng._spec.prefill_chunk
    pages = bm.blocks_needed(eng.max_ctx)
    chunk_logits = jax.jit(lambda params, cache, *a: jamba.prefill_chunk(params, cfg, cache, *a, bs)[0])
    decode_logits = jax.jit(lambda params, cache, *a: jamba.decode_forward_cached(params, cfg, cache, *a, bs)[0])

    seqs = [np.asarray(s, np.int32) for s in sequences]
    ids = [f"reference-{i}" for i in range(len(seqs))]
    told, tree, flags = reference.numbers(cfg), eng.params, ()
    if wrong and wrong != "e4m3":
        told, tree, flags = wrong_reference(told, tree, wrong)
    want = [np.asarray(reference.full_logits(tree, jnp.asarray(seq), told, list(range(n - 1, len(seq) - 1)), flags))
            for seq, n in zip(seqs, n_prompts)]
    if wrong == "e4m3":
        eng.params = _round_to_e4m3(eng.params)

    # the prompts: chunk by chunk into the cache by the engine's own
    # program (arrays made anew for every call, as the engine makes them)
    rows = {"prefill": [], "decode": []}  # (distance, margin) a position
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
                got, ref = np.asarray(got[0], np.float32), want[lane][0]
                rows["prefill"].append((float(np.abs(got - ref).max()), float(ref.max() - ref[seq[n]])))
            eng._run_on_cache(eng._prefill_jit, toks, bm.phys_indices(rid, start + m, bucket, start=start),
                              last, np.zeros(1, np.float32), eng._next_rng(), np.int32(start), table,
                              np.int32(lane))

    # the answers: each position's logits from the cache as it lies, then
    # the engine's own decode program writes that position
    steps = min(len(seq) - n for seq, n in zip(seqs, n_prompts)) - 1
    resampled = 0
    for step in range(steps):
        tok, lengths, write = (np.zeros(lanes, np.int32) for _ in range(3))
        tables = np.zeros((lanes, pages), np.int32)
        for lane, (rid, seq, n) in enumerate(zip(ids, seqs, n_prompts)):
            tok[lane], lengths[lane] = seq[n + step], n + step
            tables[lane] = bm.block_table(rid, pages)
            bm.advance(rid, 1)
            write[lane] = bm.phys_index(rid, n + step)
        got = np.asarray(decode_logits(eng.params, eng.cache, tok, tables, lengths), np.float32)
        for lane, (seq, n) in enumerate(zip(seqs, n_prompts)):
            ref = want[lane][step + 1]
            rows["decode"].append((float(np.abs(got[lane] - ref).max()), float(ref.max() - ref[seq[n + step + 1]])))
        nxt = np.asarray(eng._run_on_cache(eng._decode_jit, tok, lengths, tables, write,
                                           np.zeros(lanes, np.float32), eng._next_rng()))
        resampled += sum(int(nxt[lane] != seq[n + step + 1]) for lane, (seq, n) in enumerate(zip(seqs, n_prompts)))
    for rid in ids:
        bm.free(rid)

    def worst(kinds, column):
        # numpy's max keeps a NaN, which then fails the limit
        return float(np.max([r[column] for k in kinds for r in rows[k]]))

    both = ("prefill", "decode")
    return {"resampled": resampled, "positions": sum(len(rows[k]) for k in both),
            "margin": worst(both, 1), "prefill": worst(("prefill",), 0), "decode": worst(("decode",), 0),
            # (distance, margin) a position, sequence by sequence within a step
            "by_position": {k: [(round(d, 5), round(m, 5)) for d, m in rows[k]] for k in both}}


# ----------------------------------------------------------------------
# client side
# ----------------------------------------------------------------------
def warm_up_lens(cell) -> list:
    """A prompt of each chunk bucket the mix can use (from the bucket
    that takes its shortest prompt to the chunk), and one of two
    chunks."""
    most, shortest = cell["engine"]["prefill_chunk"], cell["traffic"]["prompt_len"]["lo"]
    buckets = chunk_buckets(most)
    return [n for n in buckets if n >= min(b for b in buckets if b >= shortest)] + [2 * most]


def setup_checks(job, stream_handle):
    """Warm up every chunk bucket the mix can use and the decode
    program, then the requests the correctness checks need: the first of
    ``checks.prompt_lens`` twice, and each of the others."""
    chk, vocab = job["cell"]["checks"], job["sizes"]["vocab_size"]
    _settle(stream_handle, traffic_mod.fixed_requests(warm_up_lens(job["cell"]), 3, vocab, job["seed"] + 7),
            timeout_s=1800)
    reqs = traffic_mod.fixed_requests(chk["prompt_lens"], chk["max_tokens"], vocab, job["seed"] + 11)
    first, again, *others = _settle(stream_handle, [reqs[0], *reqs], timeout_s=900)
    return first, again, others


def chunk_roofline_pct(config, mamba_layers, trace, before, after, peak):
    """The least time the chip could take for ONE call of the chunk
    kernel, from what the window's chunk programs took in on average
    (``ssm_chunk_tokens`` over ``prefill_chunks`` x Mamba layers calls,
    through ``flops_jamba.ssm1_chunk_work``), over the time a call took
    in the trace.  A call, not a second, as ``kernel_roofline_pct`` says.
    The bytes' roof: it reads LOW for this kernel, which the vector unit
    bounds (``flops_jamba``'s docstring).  None where there is nothing to
    read."""
    if not trace or not trace.get("devices") or not peak or "ssm_chunk_tokens" not in after:
        return None
    a_call = _kernel_seconds_a_call(trace, CHUNK_KERNEL)
    calls = (after["prefill_chunks"] - before["prefill_chunks"]) * mamba_layers
    if not a_call or calls <= 0:
        return None
    work = flops_jamba.ssm1_chunk_work(config, after["ssm_chunk_tokens"] - before["ssm_chunk_tokens"], calls)
    return 100.0 * flops.least_seconds(work, peak)["seconds"] / calls / a_call


def stated_cache(config, cell, dtype):
    """What ``cache_spec`` must have made of the configuration, by the
    engine's names: K and V pools of the attention layers alone, one K/V
    head wide, and for every Mamba layer a lane's tail (3 rows of x side
    by side) and state (N on the sublanes, the channels along the
    lanes)."""
    eng, kinds = cell["engine"], config["assumed"]["layers_block_type"]
    slots = eng["pool_tokens"] + eng["block_size"]  # the scratch block beside the pool
    inner = config["mamba_expand"] * config["hidden_size"]
    pool = [[kinds.count("attention"), slots, config["num_key_value_heads"] * config["assumed"]["head_dim"]], dtype]
    out = {"k_pages": pool, "v_pages": pool}
    for i in range(kinds.count("mamba")):
        out[f"conv_tail_{i}"] = [[eng["max_batch_size"], (config["mamba_d_conv"] - 1) * inner], dtype]
        out[f"ssm_state_{i}"] = [[eng["max_batch_size"], config["mamba_d_state"], inner], "float32"]
    return out


def lane_state_bytes(config, dtype) -> int:
    """What a lane owns whatever its sequence's length: a float32 state
    and a tail a Mamba layer."""
    inner = config["mamba_expand"] * config["hidden_size"]
    width = {"bfloat16": 2, "float32": 4}[dtype]
    return config["assumed"]["layers_block_type"].count("mamba") * (
        inner * config["mamba_d_state"] * 4 + (config["mamba_d_conv"] - 1) * inner * width)


def run(job) -> dict:
    if importlib.util.find_spec(FAMILY) is None:
        raise RuntimeError(f"this checkout's program has no {FAMILY}: it cannot run {job['config']['name']}")
    import ray_tpu

    cell, tr, config = job["cell"], job["cell"]["traffic"], job["config"]
    if tr["mode"] != "closed":
        raise ValueError("runners/serve_jamba2.py drives closed loops only")
    seconds, seed = job["seconds"], job["seed"]
    handle, actor = deploy(job)
    t_deployed = time.time()

    def call(fn, *args):
        return actor.__ray_call__.remote(fn, *args)

    installed = ray_tpu.get(call(_rep_install), timeout=600)
    held_sizes = ray_tpu.get(call(_rep_sizes), timeout=120)
    stream_handle = handle.options(stream=True)
    a1, a2, others = setup_checks(job, stream_handle)
    ray_tpu.get(call(_rep_settle), timeout=300)

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
    # the gap between two sends is the SALA runner's module constant, which its
    # drive_from_full reads when it runs: this cell states its own
    chunked.SEND_GAP_S = tr["send_gap_s"]
    streams, t0, t_begin = drive_from_full(
        stream_handle, plan, cell["engine"]["max_batch_size"], tr["lead_in"], seconds, at)
    t_end = t0 + seconds

    stats = {k: ray_tpu.get(v, timeout=300) for k, v in probes.items()}
    after_drain = ray_tpu.get(call(_rep_stats), timeout=300)
    deadline = time.time() + 180  # the chunks of the prompts in flight when the streams closed
    while (after_drain["kv_blocks_in_use"] or after_drain["state_slots_in_use"]) and time.time() < deadline:
        time.sleep(0.5)
        after_drain = ray_tpu.get(call(_rep_stats), timeout=300)
    trace = None
    if job["trace"]:
        trace = ray_tpu.get(call(_rep_trace_facts, tr["trace_seconds"], job.get("keep_trace"),
                                 cell.get("trace_annotations", ())), timeout=900)

    # the float32 reference, outside the window
    chk = cell["checks"]
    sequences = [s.req["prompt"] + s.tokens for s in (a1, *others)]
    ref = ray_tpu.get(call(_rep_reference, sequences, chk["prompt_lens"], chk.get("wrong_on_purpose")),
                      timeout=1800)
    device = ray_tpu.get(call(_rep_device), timeout=120)

    finished = [s for s in streams if s.done and not s.failed and t0 <= s.t_done < t_end]
    bad = [s for s in streams if s.failed]
    out_tokens = sum(1 for s in streams for t in s.token_t if t0 <= t < t_end)
    rate_tokens, rate_s = edge_rate(streams, t0, t_end)  # whole engine steps (serve.edge_rate)

    sizes, eng = job["sizes"], cell["engine"]
    before, after = stats["before"], stats["after"]
    kinds = config["assumed"]["layers_block_type"]
    n_m, n_a = kinds.count("mamba"), kinds.count("attention")
    steps = after["steps"] - before["steps"]
    lane_steps = after["ssm_lane_steps"] - before["ssm_lane_steps"]
    checks = {
        "preset_has_the_configuration's_sizes": all(
            installed[k] == sizes[k] for k in ("n_layer", "n_embd", "n_head", "n_positions", "vocab_rows")
        ) and sizes["dtype"] == installed["dtype"] and len(kinds) == sizes["n_layer"] == config["num_hidden_layers"],
        "preset_has_the_configuration's_widths_and_layer_order": all(
            held_sizes["config"][key] == config[key] for key, _ in WIDTH_KEYS
        ) and held_sizes["layer_types"] == kinds and held_sizes["head_dim"] == config["assumed"]["head_dim"],
        "head_is_the_embedding": held_sizes["tied_head"] == config["tie_word_embeddings"],
        "whole_model_is_held": config["reduced"] == [] and after["param_bytes"] == (
            config["parameters"] * {"bfloat16": 2, "float32": 4}[sizes["dtype"]]
            # A_log, D and dt_bias are float32 whatever the serving dtype: two bytes more each where it is bf16
            + n_m * (config["mamba_d_state"] + 2) * config["mamba_expand"] * config["hidden_size"]
            * (4 - {"bfloat16": 2, "float32": 4}[sizes["dtype"]])),
        "engine_serves_max_model_len": held_sizes["max_context"] == eng["max_model_len"],
        "cache_is_what_the_family_states": held_sizes["cache"] == stated_cache(config, cell, sizes["dtype"]),
        "lanes_hold_their_state_whatever_the_pool": after["state_bytes_held"] == (
            eng["max_batch_size"] * lane_state_bytes(config, sizes["dtype"])),
        "finished_requests_returned_max_tokens": all(
            len(s.tokens) == s.req["max_tokens"] for s in finished + [a1, a2, *others]),
        "same_prompt_twice_same_tokens": a1.tokens == a2.tokens,
        "kv_blocks_back_to_zero": after_drain["kv_blocks_in_use"] == 0,
        "state_slots_back_to_zero": after_drain["state_slots_in_use"] == 0,
        "returned_tokens_within_margin_of_float32_reference": ref["margin"] <= chk["logit_margin"],
        "prefill_logits_within_distance_of_float32_reference": ref["prefill"] <= chk["logit_distance"],
        "decode_logits_within_distance_of_float32_reference": ref["decode"] <= chk["logit_distance"],
        "every_position_was_held": ref["positions"] == len(chk["prompt_lens"]) * chk["max_tokens"],
        "no_compile_in_window": after["compiles"] == before["compiles"],
        "some_request_finished": len(finished) > 0,
        "every_lane_decoded_before_the_window": t0 - t_begin < tr["lead_in"]["at_most_s"],
        # a running lane a decode step updates the state of every Mamba layer and no other does
        "every_running_lane_updated_its_states": 0 < lane_steps <= n_m * after["max_batch_size"] * steps
        and lane_steps % n_m == 0,
        "a_chunk_s_scan_took_its_real_tokens": (
            after["ssm_chunk_tokens"] - before["ssm_chunk_tokens"] == n_m * (
                after["prompt_tokens"] - before["prompt_tokens"])),
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
                     "prefill_chunks", "prompt_tokens", "kv_positions_attended", "kv_positions_gathered",
                     "ssm_lane_steps", "ssm_chunk_tokens", "state_bytes")},
        "weight_bytes": after["param_bytes"], "state_bytes_held": after["state_bytes_held"],
        "running_before": before["running"], "waiting_middle": stats["middle"]["waiting"],
        "waiting_after": after["waiting"], "kv_blocks_middle": stats["middle"]["kv_blocks_in_use"],
        "kv_blocks_after": after["kv_blocks_in_use"],
        "worst_logit_margin": ref["margin"], "worst_logit_distance_prefill": ref["prefill"],
        "worst_logit_distance_decode": ref["decode"], "positions_checked": ref["positions"],
        "replay_resampled_tokens": ref["resampled"], "logit_readings_by_position": ref["by_position"],
        "rate_tokens": rate_tokens, "rate_s": rate_s,
        "serve_out_tokens_per_s": rate_tokens / rate_s,
    }
    if job["trace"]:
        peak = spec.load_peaks().get(device["kind"])
        one_head = {"num_attention_heads": config["num_attention_heads"],
                    "num_key_value_heads": config["num_key_value_heads"], "head_dim": config["assumed"]["head_dim"]}
        values["mamba1_decode_step_roofline"] = kernel_roofline_pct(
            STEP_KERNEL, "ssm_lane_steps", n_m, lambda done, _: flops_jamba.ssm1_step_work(config, done),
            trace, before, after, peak)
        values["mamba1_chunk_scan_roofline"] = chunk_roofline_pct(config, n_m, trace, before, after, peak)
        a_call = trace and trace.get("devices") and _kernel_seconds_a_call(trace, CHUNK_KERNEL)
        values["mamba1_chunk_scan_ms_a_call"] = 1e3 * a_call if a_call else None
        values["gqa_paged_decode_attention_roofline"] = kernel_roofline_pct(
            GQA_KERNEL, "kv_positions_attended", n_a,
            lambda done, lane_calls: flops_ssm.gqa_decode_work(one_head, done, lane_calls), trace, before, after, peak)
        values["prefill_mfu_pct"] = flops_jamba.prefill_mfu_pct(
            config, after["prompt_tokens"] - before["prompt_tokens"],
            sum(after[k] - before[k] for k in ("prefill_build_s", "prefill_await_s", "prefill_fetch_s")), peak)
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

"""The serve runner for the ZAYA1 family: the client side of
``runners/serve_granite_4_0_h_small.py`` (one replica behind
``serve.run``, a closed loop whose window is locked to the engine's own
timeline by ``drive_from_full``, the pool taken ``from_the_head`` of the
mix's fixed order, prompts in chunks, the engine's ``max_model_len``,
the replica's heap frozen after set-up; the set-up requests, the
grouped-query kernel's roofline share and its name in the trace are
imported from the runners that have them) with what this family needs:

- the float32 reference is ``benchmark/reference_zaya1.py`` (the whole
  sequence at once, position ``t - 1`` by a shift, dense experts, the
  tied head), and the program's own logits are held to it for the set-up
  requests of ``checks.prompt_lens``: inside a bucket, a mid bucket, and
  two longer than ``prefill_chunk`` so that EVERY layer's tail crosses
  one and two chunk boundaries: the prompt's last position through the
  family's last chunk program, reading the lane's tails and the pages
  the engine's own programs wrote for the chunks before it; the answer's
  through the grouped-query kernel (four queries a group) and the
  one-position mixing at the engine's lane count over the engine's OWN
  pool and tails;
- beside the distance, the share of (token, layer) whose ONE output is
  the reference's, and the distance and the margin over the positions
  whose own routing agrees in every layer, as the Mistral-Small-4 runner
  says and for its reason: top-1 makes a flip swap a token's WHOLE
  expert.  ``checks.logit_distance`` holds the MEDIAN distance over
  those positions, both paths together, not the largest: a position
  that routes as the reference does still attends positions that do not,
  and under a peaked softmax the largest of a hundred then reads five to
  ten times the median (the cell's ``checks.logit_why`` has both); the
  largest of each path stays among the values, for the record;
- the load over the router's 17 outputs as the replay's own programs
  chose them (``router_load_x17``: each output's share times 17, so that
  1 is an even load) and the skip share of the window
  (``skip_share_pct``), the builder's readings;
- wrong on purpose (``checks.wrong_on_purpose``, the builder's readings
  and the tests'): ``e4m3`` rounds the program's weights; one of
  ``reference_zaya1.WRONG`` tells the REFERENCE a model with that
  mechanism off, which the program must then be far from;
- the checks hold the preset to the configuration file's widths, the
  engine's cache to what the family states (20 paged layers of 256
  values; one tail a lane a layer, whatever the pool), the state slots
  back to zero with the blocks, and the counters to the rows: every row
  a program was given made ONE pair in EVERY layer, each either held
  (and then computed) or skipped;
- the least work of its kernels by ``benchmark/flops_zaya.py``, and
  ``prefill_mfu_pct``.

A checkout whose program has no ``ray_tpu.models.zaya`` fails here at
once, with one line, before anything is deployed.  This process imports
no JAX.
"""

from __future__ import annotations

import importlib.util
import json
import os
import time

from benchmark import flops, flops_zaya, spec
from benchmark import traffic as traffic_mod
from benchmark.runners.serve import (  # noqa: F401 - stop is the harness's hook
    _cycle, _rep_device, _rep_install, _rep_stats, bursts, edge_rate, stop,
)
from benchmark.runners import serve_minicpm_sala as chunked
from benchmark.runners.serve_minicpm_sala import deploy, drive_from_full
from benchmark.runners.common import _rep_settle
from benchmark.runners.serve_mistral_small_4 import _round_to_e4m3
from benchmark.runners.serve_nemotron_3_nano import GQA_KERNEL, kernel_roofline_pct, setup_checks
from benchmark.runners.serve_olmoe import GMM, _rep_trace_facts, _rep_trace_start, from_the_head

FAMILY = "ray_tpu.models.zaya"
# the configuration file's keys the preset must agree with, beside the
# sizes every serve cell checks: (key of the file, attribute of the config)
WIDTH_KEYS = (("num_key_value_heads", "n_kv_head"), ("head_dim", "head_dim"), ("cca_time0", "cca_time0"),
              ("cca_time1", "cca_time1"), ("partial_rotary_factor", "partial_rotary_factor"),
              ("num_experts", "num_experts"), ("num_experts_per_tok", "num_experts_per_tok"),
              ("moe_intermediate_size", "moe_intermediate_size"), ("router_hidden_size", "router_hidden_size"),
              ("rms_norm_eps", "layer_norm_epsilon"))


# ----------------------------------------------------------------------
# replica side (the process that holds the chip)
# ----------------------------------------------------------------------
def _rep_sizes(rep):
    eng = rep.callable.engine
    cfg = eng.model_cfg
    return {"config": {key: getattr(cfg, attr) for key, attr in WIDTH_KEYS},
            "rope_theta": cfg.rope_theta, "experts_held": [cfg.experts_first, cfg.experts_held],
            "tied_head": "lm_head" not in eng.params,
            "max_context": eng.max_ctx,
            "cache": {k: [list(v.shape), v.dtype.name] for k, v in eng.cache.items()}}


def _rep_reference(rep, sequences, n_prompts, wrong=None):
    """``serve_nemotron_3_nano._rep_reference`` for this family (its
    docstring says what each reading is): the engine's answers against
    the plain float32 forward over the whole of each sequence, each of
    the answer's positions through the path that gave its token (the
    family's last chunk program; its decode forward at the engine's lane
    count over the engine's own pool and tails).  Beside them ``load``:
    how often the program's own routers chose each of the 17 outputs,
    over the last chunks' real tokens and the decode steps.  `wrong`:
    "e4m3" computes the program's side on rounded weights (which leaves
    the engine's weights rounded); one of ``reference_zaya1.WRONG``
    tells the reference a model with that mechanism off."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_zaya1 as reference
    from ray_tpu.models import zaya

    eng = rep.callable.engine
    cfg, bm, lanes = eng.model_cfg, eng.bm, eng.config.max_batch_size
    bs, most = bm.block_size, eng._spec.prefill_chunk
    pages = bm.blocks_needed(eng.max_ctx)
    # of (logits, k, v, rows, state, counters, chosen): the logits and the experts chosen
    chunk_chosen = jax.jit(lambda params, cache, *a: zaya.prefill_chosen(params, cfg, cache, *a, bs)[::6])
    decode_chosen = jax.jit(lambda params, cache, *a: zaya.decode_chosen(params, cfg, cache, *a, bs)[::6])

    seqs = [np.asarray(s, np.int32) for s in sequences]
    ids = [f"reference-{i}" for i in range(len(seqs))]
    if wrong not in (None, "e4m3", *reference.WRONG):
        raise ValueError(f"no wrong-on-purpose reading named {wrong!r}")
    want, want_e = [], []
    for seq, n in zip(seqs, n_prompts):
        logits, chose, _ = reference.full_logits(eng.params, jnp.asarray(seq), cfg, list(range(n - 1, len(seq) - 1)),
                                                 wrong=None if wrong == "e4m3" else wrong)
        want.append(np.asarray(logits))
        want_e.append(np.asarray(chose))  # [L, T, 1]
    if wrong == "e4m3":
        eng.params = _round_to_e4m3(eng.params)
    load = np.zeros(cfg.num_experts + 1, np.int64)

    def same(mine, theirs):
        """Outputs of the program and of the reference, both [L, N, 1]
        -> [L, N] bool: the same one."""
        return (np.asarray(mine) == theirs).all(-1)

    # the prompts: chunk by chunk into the cache by the engine's own
    # program (arrays made anew for every call, as the engine makes them)
    rows = {"prefill": [], "decode": []}  # (distance, margin, routing agrees) a position
    pairs = agreed = 0
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
                got, chose = chunk_chosen(eng.params, eng.cache, toks, np.int32(start), last, table, np.int32(lane))
                ok = same(np.asarray(chose)[:, :m], want_e[lane][:, start:n])  # [L, m]
                load += np.bincount(np.asarray(chose)[:, :m].ravel(), minlength=len(load))
                pairs, agreed = pairs + ok.size, agreed + int(ok.sum())
                got, ref = np.asarray(got[0], np.float32), want[lane][0]
                rows["prefill"].append((float(np.abs(got - ref).max()), float(ref.max() - ref[seq[n]]),
                                        bool(ok[:, -1].all())))
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
        got, chose = decode_chosen(eng.params, eng.cache, tok, tables, lengths)
        got, chose = np.asarray(got, np.float32), np.asarray(chose)
        load += np.bincount(chose[:, :len(seqs)].ravel(), minlength=len(load))
        for lane, (seq, n) in enumerate(zip(seqs, n_prompts)):
            ok = same(chose[:, lane:lane + 1], want_e[lane][:, n + step:n + step + 1])[:, 0]  # [L]
            pairs, agreed = pairs + ok.size, agreed + int(ok.sum())
            ref = want[lane][step + 1]
            rows["decode"].append((float(np.abs(got[lane] - ref).max()),
                                   float(ref.max() - ref[seq[n + step + 1]]), bool(ok.all())))
        nxt = np.asarray(eng._run_on_cache(eng._decode_jit, tok, lengths, tables, write,
                                           np.zeros(lanes, np.float32), eng._next_rng()))
        resampled += sum(int(nxt[lane] != seq[n + step + 1]) for lane, (seq, n) in enumerate(zip(seqs, n_prompts)))
    for rid in ids:
        bm.free(rid)

    def worst(kinds, column, agreeing):
        # numpy's max keeps a NaN, which then fails the limit
        picked = [r[column] for k in kinds for r in rows[k] if r[2] or not agreeing]
        return float(np.max(picked)) if picked else None

    def middle(agreeing):
        """The median distance over both paths' positions: top-1 routing
        and a peaked softmax give the LARGEST a heavy tail (a position
        that routes as the reference does and attends one that does not),
        which a limit on it would have to clear by a logit's whole
        spread; the median over a hundred positions does not have it."""
        picked = [r[0] for k in both for r in rows[k] if r[2] or not agreeing]
        return float(np.median(picked)) if picked else None

    both = ("prefill", "decode")
    return {"agree": agreed / pairs, "median": middle(True), "median_all": middle(False), "pairs": pairs, "resampled": resampled,
            "positions": sum(len(rows[k]) for k in both),
            "positions_agreeing": sum(r[2] for k in both for r in rows[k]),
            "margin": worst(both, 1, True), "prefill": worst(("prefill",), 0, True),
            "decode": worst(("decode",), 0, True), "margin_all": worst(both, 1, False), "load": load.tolist(),
            "prefill_all": worst(("prefill",), 0, False), "decode_all": worst(("decode",), 0, False),
            # (distance, margin, routing agrees) a position, sequence by sequence within a step
            "by_position": {k: [(round(d, 5), round(m, 5), int(ok)) for d, m, ok in rows[k]] for k in both}}


# ----------------------------------------------------------------------
# client side
# ----------------------------------------------------------------------
def gmm_roofline_pct(config, trace, at_trace_start, after, peak):
    """``serve_olmoe.gmm_roofline_pct`` with the least work of SwiGLU
    experts of this family's widths (``flops_zaya.experts_work``; a
    skipped pair is no work and is not among ``moe_pairs``): what the
    counters saw between the trace's start and the window's end, a second
    of host time, over the kernel's device seconds a second of the traced
    window.  None where there is nothing to read."""
    if not trace or not trace.get("devices") or not peak:
        return None
    kernel_s = sum(s for name, s in trace["op_seconds"].items() if GMM.search(name))
    span = after["t"] - at_trace_start["t"]
    if kernel_s <= 0 or span < 0.5 or "moe_pairs" not in after:
        return None
    work = flops_zaya.experts_work(
        config, after["moe_pairs"] - at_trace_start["moe_pairs"],
        after["moe_experts_hit"] - at_trace_start["moe_experts_hit"])
    return 100.0 * (flops.least_seconds(work, peak)["seconds"] / span) / (kernel_s / trace["window_s"])


def stated_cache(config, cell, dtype):
    """What ``cache_spec`` must have made of the configuration, by the
    engine's names: K and V pools of EVERY layer, a row the K/V heads
    alone, and for every layer a lane's tail: the latents and the first
    convolution's output of the last position (heads + K/V heads of
    head_dim each) and that position's shifted values (half of the K/V
    heads), side by side."""
    eng, layers, dh = cell["engine"], config["num_hidden_layers"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    slots = eng["pool_tokens"] + eng["block_size"]  # the scratch block beside the pool
    pool = [[layers, slots, kv * dh], dtype]
    out = {"k_pages": pool, "v_pages": pool}
    for i in range(layers):
        out[f"cca_tail_{i}"] = [[eng["max_batch_size"], 2 * (heads + kv) * dh + kv // 2 * dh], dtype]
    return out


def run(job) -> dict:
    if importlib.util.find_spec(FAMILY) is None:
        raise RuntimeError(f"this checkout's program has no {FAMILY}: it cannot run {job['config']['name']}")
    import ray_tpu

    cell, tr, config = job["cell"], job["cell"]["traffic"], job["config"]
    if tr["mode"] != "closed":
        raise ValueError("runners/serve_zaya1.py drives closed loops only")
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
    n_l = config["num_hidden_layers"]
    # every row a program was given made its pairs in every layer:
    # max_batch_size rows a decode program, the padded chunk a prefill
    rows = after["max_batch_size"] * (after["steps"] - before["steps"]) + (
        after["prefill_bucket_tokens"] - before["prefill_bucket_tokens"])
    routed, held, computed, skipped = (after.get(k, 0) - before.get(k, 0) for k in (
        "moe_pairs_routed", "moe_pairs_held", "moe_pairs", "moe_pairs_skipped"))
    load = ref["load"]
    # a path none of whose positions routed as the reference did has nothing to hold;
    # positions_whose_routing_agrees_are_enough holds the count
    within = {k: ref[k] is None or ref[k] <= chk[limit] for k, limit in (
        ("margin", "logit_margin"), ("median", "logit_distance"))}
    checks = {
        "preset_has_the_configuration's_sizes": all(
            installed[k] == sizes[k] for k in ("n_layer", "n_embd", "n_head", "n_positions", "vocab_rows")
        ) and sizes["dtype"] == installed["dtype"] and n_l == sizes["n_layer"],
        "preset_has_the_configuration's_widths_rotation_and_layer_types": all(
            held_sizes["config"][key] == config[key] for key, _ in WIDTH_KEYS
        ) and held_sizes["rope_theta"] == config["rope_parameters"]["hybrid"]["rope_theta"] and (
            config["layer_types"] == ["hybrid"] * n_l),
        "preset_holds_every_expert": held_sizes["experts_held"] == [0, config["num_experts"]],
        "head_is_the_embedding": held_sizes["tied_head"] == config["tie_word_embeddings"],
        "engine_serves_max_model_len": held_sizes["max_context"] == eng["max_model_len"],
        "cache_is_what_the_family_states": held_sizes["cache"] == stated_cache(config, cell, sizes["dtype"]),
        "finished_requests_returned_max_tokens": all(
            len(s.tokens) == s.req["max_tokens"] for s in finished + [a1, a2, *others]),
        "same_prompt_twice_same_tokens": a1.tokens == a2.tokens,
        "kv_blocks_back_to_zero": after_drain["kv_blocks_in_use"] == 0,
        "state_slots_back_to_zero": after_drain["state_slots_in_use"] == 0,
        "returned_tokens_within_margin_of_float32_reference": within["margin"],
        "logits_within_distance_of_float32_reference": within["median"],
        "chosen_experts_agree_with_float32_reference": ref["agree"] >= chk["expert_agreement_min"],
        "positions_whose_routing_agrees_are_enough": (
            ref["positions_agreeing"] >= chk["positions_agreeing_min"] * ref["positions"]),
        "no_compile_in_window": after["compiles"] == before["compiles"],
        "some_request_finished": len(finished) > 0,
        "every_lane_decoded_before_the_window": t0 - t_begin < tr["lead_in"]["at_most_s"],
        "every_row_made_its_pair": rows > 0 and routed == config["num_experts_per_tok"] * n_l * rows,
        "every_held_pair_was_computed_and_no_other": 0 < held == computed,
        "every_pair_is_held_or_skipped": 0 < skipped and held + skipped == routed,
        "every_output_was_chosen": all(n > 0 for n in load),
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
                     "prefill_chunks", "prompt_tokens", "kv_positions_attended", "moe_experts_hit",
                     "state_bytes")},
        "running_before": before["running"], "waiting_middle": stats["middle"]["waiting"],
        "waiting_after": after["waiting"], "kv_blocks_middle": stats["middle"]["kv_blocks_in_use"],
        "kv_blocks_after": after["kv_blocks_in_use"],
        "moe_rows": rows, "moe_pairs_routed": routed, "moe_pairs_held": held, "moe_pairs": computed,
        "moe_pairs_skipped": skipped, "skip_share_pct": 100.0 * skipped / routed if routed else None,
        # each output's share of the replay's pairs times 17: 1 is an even load
        "router_load_x17": [round(len(load) * n / max(sum(load), 1), 4) for n in load],
        "median_logit_distance": ref["median"], "median_logit_distance_all": ref["median_all"],
        "worst_logit_margin": ref["margin"], "worst_logit_distance_prefill": ref["prefill"],
        "worst_logit_distance_decode": ref["decode"],
        **{"worst_" + k: ref[k] for k in ("margin_all", "prefill_all", "decode_all")},
        "chosen_experts_agree": ref["agree"], "chosen_expert_pairs": ref["pairs"],
        "positions_checked": ref["positions"], "positions_agreeing": ref["positions_agreeing"],
        "replay_resampled_tokens": ref["resampled"], "logit_readings_by_position": ref["by_position"],
        "rate_tokens": rate_tokens, "rate_s": rate_s,
        "serve_out_tokens_per_s": rate_tokens / rate_s,
    }
    if job["trace"]:
        peak = spec.load_peaks().get(device["kind"])
        values["gqa_paged_decode_attention_roofline"] = kernel_roofline_pct(
            GQA_KERNEL, "kv_positions_attended", n_l,
            lambda done, lane_calls: flops_zaya.gqa_decode_work(config, done, lane_calls),
            trace, before, after, peak)
        values["moe_gmm_roofline_pct"] = gmm_roofline_pct(config, trace, stats["trace_start"], after, peak)
        values["prefill_mfu_pct"] = flops_zaya.prefill_mfu_pct(
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

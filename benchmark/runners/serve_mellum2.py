"""The serve runner for the Mellum 2 family: the client side of
``runners/serve_granite_4_0_h_small.py`` (one replica behind
``serve.run``, a closed loop whose window is locked to the engine's own
timeline by ``drive_from_full``, the pool taken ``from_the_head`` of the
mix's fixed order, prompts in chunks, the engine's ``max_model_len``,
the replica's heap frozen after set-up; the set-up requests, the
grouped-query kernel's roofline share and its name in the trace are
imported from the runners that have them) with what this family needs:

- the float32 reference is ``benchmark/reference_mellum2.py`` (full
  masks by layer kind, both rotations written out, dense experts), and
  the program's own logits are held to it for the set-up requests of
  ``checks.prompt_lens``: inside the window; past it inside one chunk;
  across a chunk boundary; the ring wrapped several times and decode
  continuing over it.  The prompt's last position goes through the
  family's last chunk program, reading the pages and the lane's rings
  the engine's own programs wrote for the chunks before it; the answer's
  through the grouped-query kernel over pages and rings at the engine's
  lane count over the engine's OWN pool and rings;
- beside the distance, the share of (token, layer) whose eight experts
  are the reference's, and the distance and the margin over the
  positions whose own routing agrees in every layer, as the
  Mistral-Small-4 runner says and for its reason;
- the window layers' RINGS themselves, as the replay leaves them: lane
  i's ring of each window layer must hold the reference's keys (normed,
  rotated) of sequence i's last ``sliding_window - 1`` cached positions,
  position p at row ``p mod (sliding_window - 1)``, the worst row's
  root-mean-square distance under ``checks.ring_key_distance``.  The logits cannot hold the window's
  EDGE on the chip: one key of 1,024 moves them by about what bf16
  rounding does (the cell's ``checks.logit_why`` has the readings); a
  ring one position short, long or shifted holds another token's key,
  units away;
- wrong on purpose (``checks.wrong_on_purpose``, the builder's readings
  and the tests'): ``e4m3`` rounds the program's weights; ``window_1023``,
  ``no_window`` and ``rotations_swapped`` tell the REFERENCE another
  model (``reference_mellum2.numbers`` edited), which the program must
  then be far from;
- the checks hold the preset to the configuration file's widths, layer
  types, window and rotations, the engine's cache to what the family
  states (3 paged layers; two rings a lane of 9 window layers x 1,024
  positions, whatever the pool), the state slots back to zero with the
  blocks, and the counters to the rows: every row a program was given
  made ``num_experts_per_tok`` pairs in EVERY layer, all of them
  computed; a decode step's window layers attended no more than
  ``sliding_window - 1`` cached positions a lane;
- the least work of its kernels by ``benchmark/flops_mellum.py`` (the
  configuration's own key names; SwiGLU experts of three matrices; 4 K/V
  heads; a window layer's read ``min(length, 1023)``), and
  ``prefill_mfu_pct``.

A checkout whose program has no ``ray_tpu.models.mellum`` fails here at
once, with one line, before anything is deployed.  This process imports
no JAX.
"""

from __future__ import annotations

import importlib.util
import json
import os
import time

from benchmark import flops, flops_mellum, spec
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

FAMILY = "ray_tpu.models.mellum"
# the configuration file's keys the preset must agree with, beside the
# sizes every serve cell checks: (key of the file, attribute of the config)
WIDTH_KEYS = (("num_key_value_heads", "n_kv_head"), ("head_dim", "head_dim"), ("sliding_window", "sliding_window"),
              ("moe_intermediate_size", "moe_intermediate_size"), ("num_experts", "num_experts"),
              ("num_experts_per_tok", "num_experts_per_tok"), ("norm_topk_prob", "norm_topk_prob"),
              ("rms_norm_eps", "layer_norm_epsilon"))
# rope_parameters.full_attention's keys, and the one of sliding_attention
YARN_KEYS = (("rope_theta", "rope_theta"), ("factor", "yarn_factor"),
             ("original_max_position_embeddings", "original_max_position_embeddings"), ("beta_fast", "beta_fast"),
             ("beta_slow", "beta_slow"), ("attention_factor", "attention_factor"))
WRONG_REFERENCES = ("window_1023", "no_window", "rotations_swapped")


# ----------------------------------------------------------------------
# replica side (the process that holds the chip)
# ----------------------------------------------------------------------
def _rep_sizes(rep):
    eng = rep.callable.engine
    cfg = eng.model_cfg
    return {"config": {key: getattr(cfg, attr) for key, attr in WIDTH_KEYS},
            "yarn": {key: getattr(cfg, attr) for key, attr in YARN_KEYS},
            "layer_types": list(cfg.layer_types),
            "untied_head": "lm_head" in eng.params,
            "max_context": eng.max_ctx,
            "cache": {k: [list(v.shape), v.dtype.name] for k, v in eng.cache.items()}}


def wrong_reference(numbers: dict, wrong: str) -> dict:
    """``reference_mellum2.numbers`` told another model: a window one key
    shorter, none, or each kind's rotation on the other kind."""
    rope = numbers["rope_parameters"]
    if wrong == "window_1023":
        return dict(numbers, sliding_window=numbers["sliding_window"] - 1)
    if wrong == "no_window":
        return dict(numbers, sliding_window=1 << 30)
    if wrong == "rotations_swapped":
        return dict(numbers, rope_parameters={"sliding_attention": rope["full_attention"],
                                              "full_attention": rope["sliding_attention"]})
    raise ValueError(f"no wrong-on-purpose reading named {wrong!r}")


def _rep_reference(rep, sequences, n_prompts, wrong=None):
    """``serve_nemotron_3_nano._rep_reference`` for this family (its
    docstring says what each reading is): the engine's answers against
    the plain float32 forward over the whole of each sequence, each of
    the answer's positions through the path that gave its token (the
    family's last chunk program; its decode forward at the engine's lane
    count over the engine's own pool and rings).  `wrong`: "e4m3"
    computes the program's side on rounded weights (which leaves the
    engine's weights rounded); one of WRONG_REFERENCES tells the
    reference another model (``wrong_reference``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_mellum2 as reference
    from ray_tpu.models import mellum

    eng = rep.callable.engine
    cfg, bm, lanes = eng.model_cfg, eng.bm, eng.config.max_batch_size
    bs, most = bm.block_size, eng._spec.prefill_chunk
    pages = bm.blocks_needed(eng.max_ctx)
    # of (logits, k, v, rows, state, counters, chosen): the logits and the experts chosen
    chunk_chosen = jax.jit(lambda params, cache, *a: mellum.prefill_chosen(params, cfg, cache, *a, bs)[::6])
    decode_chosen = jax.jit(lambda params, cache, *a: mellum.decode_chosen(params, cfg, cache, *a, bs)[::6])

    seqs = [np.asarray(s, np.int32) for s in sequences]
    ids = [f"reference-{i}" for i in range(len(seqs))]
    told = reference.numbers(cfg)
    if wrong in WRONG_REFERENCES:
        told = wrong_reference(told, wrong)
    window_layers = [i for i, kind in enumerate(told["layer_types"]) if kind == "sliding_attention"]
    want, want_e, want_k = [], [], []
    for seq, n in zip(seqs, n_prompts):
        logits, chose, keys = reference.full_logits(
            eng.params, jnp.asarray(seq), told, list(range(n - 1, len(seq) - 1)), keys=True)
        want.append(np.asarray(logits))
        want_e.append(np.sort(np.asarray(chose), axis=-1))  # [L, T, k]
        want_k.append(np.asarray(keys)[window_layers])  # [Lw, T, 512]
    if wrong == "e4m3":
        eng.params = _round_to_e4m3(eng.params)
    elif wrong and wrong not in WRONG_REFERENCES:
        raise ValueError(f"no wrong-on-purpose reading named {wrong!r}")

    def same(mine, theirs):
        """Experts of the program (any order) and of the reference
        (sorted), both [L, N, k] -> [L, N] bool: the same eight."""
        return (np.sort(np.asarray(mine), axis=-1) == theirs).all(-1)

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
        for lane, (seq, n) in enumerate(zip(seqs, n_prompts)):
            ok = same(chose[:, lane:lane + 1], want_e[lane][:, n + step:n + step + 1])[:, 0]  # [L]
            pairs, agreed = pairs + ok.size, agreed + int(ok.sum())
            ref = want[lane][step + 1]
            rows["decode"].append((float(np.abs(got[lane] - ref).max()),
                                   float(ref.max() - ref[seq[n + step + 1]]), bool(ok.all())))
        nxt = np.asarray(eng._run_on_cache(eng._decode_jit, tok, lengths, tables, write,
                                           np.zeros(lanes, np.float32), eng._next_rng()))
        resampled += sum(int(nxt[lane] != seq[n + step + 1]) for lane, (seq, n) in enumerate(zip(seqs, n_prompts)))
    # the rings as the replay left them: lane i holds sequence i's positions 0 .. cached - 1, of which a
    # window layer's later queries can still see the last window - 1, position p at row p mod (window - 1)
    ring_rows = told["sliding_window"] - 1
    rings = np.asarray(eng.cache["win_k"][:len(seqs)].astype(jnp.float32))  # [sequences, Lw, rows, 512]
    ring_distance = 0.0
    for lane, (seq, n) in enumerate(zip(seqs, n_prompts)):
        last = n + steps - 1  # the newest cached position
        held = np.arange(max(0, last + 1 - ring_rows), last + 1)
        if ring_rows >= rings.shape[2] and len(held) >= rings.shape[2]:
            ring_distance = float("inf")  # the reference's window does not fit the ring the program states
            continue
        # a row's distance: the root mean square over its 512 values (a row of another position's
        # keys lies about sqrt(2) away, whatever the precision); the reading is the worst row's
        off = rings[lane][:, held % ring_rows] - want_k[lane][:, held]
        ring_distance = max(ring_distance, float(np.sqrt((off * off).mean(-1)).max()))
    for rid in ids:
        bm.free(rid)

    def worst(kinds, column, agreeing):
        # numpy's max keeps a NaN, which then fails the limit
        picked = [r[column] for k in kinds for r in rows[k] if r[2] or not agreeing]
        return float(np.max(picked)) if picked else None

    both = ("prefill", "decode")
    return {"agree": agreed / pairs, "pairs": pairs, "resampled": resampled,
            "positions": sum(len(rows[k]) for k in both),
            "positions_agreeing": sum(r[2] for k in both for r in rows[k]),
            "margin": worst(both, 1, True), "prefill": worst(("prefill",), 0, True),
            "decode": worst(("decode",), 0, True), "margin_all": worst(both, 1, False),
            "prefill_all": worst(("prefill",), 0, False), "decode_all": worst(("decode",), 0, False),
            "ring": ring_distance,
            # (distance, margin, routing agrees) a position, sequence by sequence within a step
            "by_position": {k: [(round(d, 5), round(m, 5), int(ok)) for d, m, ok in rows[k]] for k in both}}


# ----------------------------------------------------------------------
# client side
# ----------------------------------------------------------------------
def gmm_roofline_pct(config, trace, at_trace_start, after, peak):
    """``serve_olmoe.gmm_roofline_pct`` with this configuration's key
    names (``flops_mellum.experts_work``): what the counters saw between
    the trace's start and the window's end, a second of host time, over
    the kernel's device seconds a second of the traced window.  None
    where there is nothing to read."""
    if not trace or not trace.get("devices") or not peak:
        return None
    kernel_s = sum(s for name, s in trace["op_seconds"].items() if GMM.search(name))
    span = after["t"] - at_trace_start["t"]
    if kernel_s <= 0 or span < 0.5 or "moe_pairs" not in after:
        return None
    work = flops_mellum.experts_work(
        config, after["moe_pairs"] - at_trace_start["moe_pairs"],
        after["moe_experts_hit"] - at_trace_start["moe_experts_hit"])
    return 100.0 * (flops.least_seconds(work, peak)["seconds"] / span) / (kernel_s / trace["window_s"])


def stated_cache(config, cell, dtype):
    """What ``cache_spec`` must have made of the configuration, by the
    engine's names: K and V pools of the full layers alone, by the pool,
    and the window layers' two rings a lane, by the window: their shapes
    have no pool and no sequence length in them."""
    eng, kinds = cell["engine"], config["layer_types"]
    slots = eng["pool_tokens"] + eng["block_size"]  # the scratch block beside the pool
    row = config["num_key_value_heads"] * config["head_dim"]
    pool = [[kinds.count("full_attention"), slots, row], dtype]
    ring = [[eng["max_batch_size"], kinds.count("sliding_attention"), config["sliding_window"], row], dtype]
    return {"k_pages": pool, "v_pages": pool, "win_k": ring, "win_v": ring}


def run(job) -> dict:
    if importlib.util.find_spec(FAMILY) is None:
        raise RuntimeError(f"this checkout's program has no {FAMILY}: it cannot run {job['config']['name']}")
    import ray_tpu

    cell, tr, config = job["cell"], job["cell"]["traffic"], job["config"]
    if tr["mode"] != "closed":
        raise ValueError("runners/serve_mellum2.py drives closed loops only")
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
    kinds = config["layer_types"]
    n_w, n_f, n_l = kinds.count("sliding_attention"), kinds.count("full_attention"), len(kinds)
    steps = after["steps"] - before["steps"]
    # every row a program was given made its pairs in every layer:
    # max_batch_size rows a decode program, the padded chunk a prefill
    rows = after["max_batch_size"] * steps + after["prefill_bucket_tokens"] - before["prefill_bucket_tokens"]
    computed = after.get("moe_pairs", 0) - before.get("moe_pairs", 0)
    window, full, unwindowed = (after.get(k, 0) - before.get(k, 0) for k in (
        "attn_positions_window", "attn_positions_full", "attn_positions_unwindowed"))
    rope = config["rope_parameters"]
    # a path none of whose positions routed as the reference did has nothing to hold;
    # positions_whose_routing_agrees_are_enough holds the count
    within = {k: ref[k] is None or ref[k] <= chk[limit] for k, limit in (
        ("margin", "logit_margin"), ("prefill", "logit_distance"), ("decode", "logit_distance"))}
    checks = {
        "preset_has_the_configuration's_sizes": all(
            installed[k] == sizes[k] for k in ("n_layer", "n_embd", "n_head", "n_positions", "vocab_rows")
        ) and sizes["dtype"] == installed["dtype"] and n_l == sizes["n_layer"],
        "preset_has_the_configuration's_widths_window_and_layer_types": all(
            held_sizes["config"][key] == config[key] for key, _ in WIDTH_KEYS
        ) and held_sizes["layer_types"] == kinds == config["published"]["layer_types"][:n_l],
        "preset_has_the_configuration's_rotations": held_sizes["yarn"] == {
            key: rope["full_attention"][key] for key, _ in YARN_KEYS} and (
            rope["sliding_attention"] == {"rope_type": "default", "rope_theta": held_sizes["yarn"]["rope_theta"]}),
        "head_is_its_own": held_sizes["untied_head"] == (not config["tie_word_embeddings"]),
        "engine_serves_max_model_len": held_sizes["max_context"] == eng["max_model_len"],
        "cache_is_what_the_family_states": held_sizes["cache"] == stated_cache(config, cell, sizes["dtype"]),
        "finished_requests_returned_max_tokens": all(
            len(s.tokens) == s.req["max_tokens"] for s in finished + [a1, a2, *others]),
        "same_prompt_twice_same_tokens": a1.tokens == a2.tokens,
        "kv_blocks_back_to_zero": after_drain["kv_blocks_in_use"] == 0,
        "state_slots_back_to_zero": after_drain["state_slots_in_use"] == 0,
        "returned_tokens_within_margin_of_float32_reference": within["margin"],
        "prefill_logits_within_distance_of_float32_reference": within["prefill"],
        "decode_logits_within_distance_of_float32_reference": within["decode"],
        "chosen_experts_agree_with_float32_reference": ref["agree"] >= chk["expert_agreement_min"],
        "window_rings_hold_the_reference's_keys_of_the_window's_positions": ref["ring"] <= chk["ring_key_distance"],
        "positions_whose_routing_agrees_are_enough": (
            ref["positions_agreeing"] >= chk["positions_agreeing_min"] * ref["positions"]),
        "no_compile_in_window": after["compiles"] == before["compiles"],
        "some_request_finished": len(finished) > 0,
        "every_lane_decoded_before_the_window": t0 - t_begin < tr["lead_in"]["at_most_s"],
        "every_row_made_its_pairs_and_all_were_computed": rows > 0 and (
            computed == config["num_experts_per_tok"] * n_l * rows),
        # a window layer's step reads a lane's ring and no more; the full layers' share of what
        # all-full layers would read is their share of the layers
        "window_layers_attended_no_more_than_their_rings": 0 < window <= (
            n_w * after["max_batch_size"] * steps * (config["sliding_window"] - 1)),
        "full_layers_attended_every_cached_position": 0 < full and full * n_l == unwindowed * n_f,
        "rings_are_held_whatever_the_pool": after["state_bytes_held"] == (
            2 * after["max_batch_size"] * n_w * config["sliding_window"]
            * config["num_key_value_heads"] * config["head_dim"] * {"bfloat16": 2, "float32": 4}[sizes["dtype"]]),
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
                     "moe_experts_hit", "attn_positions_window", "attn_positions_full",
                     "attn_positions_unwindowed")},
        "attn_positions_kept_pct": 100.0 * (window + full) / unwindowed if unwindowed else None,
        "ring_bytes_held": after["state_bytes_held"],
        "running_before": before["running"], "waiting_middle": stats["middle"]["waiting"],
        "waiting_after": after["waiting"], "kv_blocks_middle": stats["middle"]["kv_blocks_in_use"],
        "kv_blocks_after": after["kv_blocks_in_use"],
        "moe_rows": rows, "moe_pairs": computed,
        "worst_logit_margin": ref["margin"], "worst_logit_distance_prefill": ref["prefill"],
        "worst_logit_distance_decode": ref["decode"], "worst_ring_key_distance": ref["ring"],
        **{"worst_" + k: ref[k] for k in ("margin_all", "prefill_all", "decode_all")},
        "chosen_experts_agree": ref["agree"], "chosen_expert_pairs": ref["pairs"],
        "positions_checked": ref["positions"], "positions_agreeing": ref["positions_agreeing"],
        "replay_resampled_tokens": ref["resampled"], "logit_readings_by_position": ref["by_position"],
        "rate_tokens": rate_tokens, "rate_s": rate_s,
        "serve_out_tokens_per_s": rate_tokens / rate_s,
    }
    if job["trace"]:
        peak = spec.load_peaks().get(device["kind"])
        # one kernel, twelve calls a step: three over the pages, nine over the rings
        values["gqa_paged_decode_attention_roofline"] = kernel_roofline_pct(
            GQA_KERNEL, "kv_positions_attended", n_l,
            lambda done, lane_calls: flops_mellum.gqa_decode_work(config, done, lane_calls),
            trace, before, after, peak)
        values["moe_gmm_roofline_pct"] = gmm_roofline_pct(config, trace, stats["trace_start"], after, peak)
        values["prefill_mfu_pct"] = flops_mellum.prefill_mfu_pct(
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

"""The serve runner for the GLM-5 family: the client side of
``runners/serve_mistral_small_4.py`` (one replica behind ``serve.run``, a
closed loop whose window is locked to the engine's own timeline, prompts
in chunks, the engine's ``max_model_len``, the replica's heap settled
after set-up) with what this family needs:

- the float32 reference is ``benchmark/reference_glm_5.py`` (non-absorbed
  attention over a choice made by a full sort, the same share of experts
  and of vocabulary), and the program's own logits over the rows held are
  held to it for two set-up requests, one under ``index_topk`` (every
  position kept) and one of three chunks (chunks two and three and every
  decode step select): the prompt's through the family's last chunk
  program, reading what the engine's own programs wrote for the chunks
  before it into BOTH pools; the answer's through its paged decode (index
  kernel, choice, attention kernel under the choice) at the engine's lane
  count over the engine's OWN pools;
- beside the distance, two shares with limits of their own: of the
  (token, expert layer) pairs, those whose eight experts are the
  reference's (what it holds is the router: sigmoid, bias, choice); and
  of the positions the program and the reference chose a (token, layer),
  those both chose, over the larger count (what it holds is the indexer
  and the exact choice).  Under random weights the eighth and ninth
  largest router scores lie close and so do the index scores around the
  2,048th, and a bf16 path differs from float32 at both margins; a token
  with another HELD expert moves its logits by far more than rounding
  does (one absent expert for another computes nothing on this chip), and
  under random weights attention is near uniform, so a layer's output is
  the mean of the chosen rows' values and moves with the set; so the
  distance and the margin are taken over the positions whose held experts
  agree in every expert layer and whose choice agrees with the
  reference's to ``selection_position_min`` in every layer;
- the checks hold the preset to the configuration file's widths, to the
  experts and rows it says are held, the cache to its two pools, and the
  engine's counters to its rows: every row a program was given made
  ``num_experts_per_tok`` pairs in each of the EXPERT layers, every pair
  whose expert is held was computed, and no query attended more than
  ``index_topk`` positions or more than it had;
- ``dsa_index_paged_scores_roofline`` and
  ``mla_sparse_paged_decode_attention_roofline``: a call's least time
  (``flops_dsa`` over the window's scored and attended positions, by
  ``steps`` x layers calls) against a call's time in the trace; and
  ``moe_gmm_roofline_pct`` by the OLMoE runner's own function, at this
  model's expert width and over the held experts' pairs.

A checkout whose program has no ``ray_tpu.models.glm_moe_dsa`` fails here
at once, before anything is deployed.  This process imports no JAX.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import time

from benchmark import flops, flops_dsa, flops_mla, spec
from benchmark import traffic as traffic_mod
from benchmark.runners.serve import (  # noqa: F401 - stop is the harness's hook
    _cycle, _rep_device, _rep_install, _rep_stats, bursts, edge_rate, stop,
)
from benchmark.runners import serve_minicpm_sala as chunked
from benchmark.runners.common import _rep_settle
from benchmark.runners.serve_minicpm_sala import deploy, drive_from_full, setup_checks
from benchmark.runners.serve_mistral_small_4 import _round_to_e4m3
from benchmark.runners.serve_olmoe import (
    _rep_trace_facts, _rep_trace_start, from_the_head, gmm_roofline_pct,
)

FAMILY = "ray_tpu.models.glm_moe_dsa"
# the configuration file's keys the preset must agree with, beside the
# sizes every serve cell checks: (key of the file, attribute of the config)
WIDTH_KEYS = (("q_lora_rank", "q_lora_rank"), ("kv_lora_rank", "kv_lora_rank"),
              ("qk_nope_head_dim", "qk_nope_head_dim"), ("qk_rope_head_dim", "qk_rope_head_dim"),
              ("v_head_dim", "v_head_dim"), ("index_n_heads", "index_n_heads"),
              ("index_head_dim", "index_head_dim"), ("index_topk", "index_topk"),
              ("intermediate_size", "intermediate_size"), ("moe_intermediate_size", "moe_intermediate_size"),
              ("n_routed_experts", "experts_held"), ("num_experts_per_tok", "num_experts_per_tok"),
              ("n_shared_experts", "n_shared_experts"), ("norm_topk_prob", "norm_topk_prob"),
              ("routed_scaling_factor", "routed_scaling_factor"), ("rms_norm_eps", "rms_norm_eps"),
              ("first_k_dense_replace", "first_k_dense_replace"))
HELD_KEYS = (("experts_first", "experts_first"), ("experts_held", "experts_held"),
             ("vocab_first", "vocab_first"), ("vocab_rows", "vocab_size"),
             ("router_outputs", "n_routed_experts"), ("num_experts_per_tok", "num_experts_per_tok"))
INDEX_KERNEL = re.compile(r"^dsa_index_paged_scores")  # their names in the device trace
ATTEND_KERNEL = re.compile(r"^mla_sparse_paged_decode_attention")


# ----------------------------------------------------------------------
# replica side (the process that holds the chip)
# ----------------------------------------------------------------------
def _rep_glm_sizes(rep):
    eng = rep.callable.engine
    cfg = eng.model_cfg
    dense = cfg.first_k_dense_replace
    return {"config": {key: getattr(cfg, attr) for key, attr in WIDTH_KEYS},
            "rope_theta": cfg.rope_theta,
            "held": {**{key: getattr(cfg, attr) for key, attr in HELD_KEYS},
                     "dense_layers": dense, "expert_layers": cfg.n_layer - dense},
            "published": {"n_routed_experts": cfg.n_routed_experts, "vocab_size": cfg.published_vocab_size},
            "max_context": eng.max_ctx, "cache": {k: list(v.shape) for k, v in eng.cache.items()},
            "cache_bytes": sum(v.nbytes for v in eng.cache.values())}


def _rep_reference(rep, sequences, n_prompts, wrong=None, position_min=1.0):
    """The engine's answers against the plain float32 forward over the
    whole of each sequence (prompt + the tokens the engine returned), on
    the engine's own weights, after the drain (the engine is idle).
    `sequences` may differ in length; sequence i goes to lane i.  Each of
    the answer's positions goes through the path that gave its token:
    the first from the family's chunk program on the prompt's last chunk
    (the chunks before it written by the engine's own prefill program),
    the others from its paged decode at the engine's lane count over the
    engine's own pools, which the engine's own decode program then
    writes.  -> agree: of the (token, expert layer) pairs of the last
    chunks' real tokens and of the decode steps, the share whose experts
    are the reference's; selection: of the positions program and
    reference chose for the positions checked, in every layer, those both
    chose over the larger count; margin, prefill, decode: how far a
    returned token's logit lies under the reference's largest, and how
    far the program's logits lie from the reference's over the rows
    held, at most, over the positions whose own HELD experts agree in
    every expert layer and whose own choice agrees to `position_min` in
    every layer (``*_all``: over every position); resampled: the tokens the
    engine's programs gave otherwise this time.  `wrong`: the builder's
    wrong-on-purpose readings.  "e4m3" computes the program's side on
    weights rounded to float8_e4m3's mantissa (and leaves the engine's
    weights rounded); "recent" and "all" compute the REFERENCE's side
    with the choice replaced by the latest ``index_topk`` positions, or
    with the indexer left out and every position attended: the
    disagreement is the same whichever side is wrong, and the program has
    no switch for it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_glm_5 as reference
    from ray_tpu.models import glm_moe_dsa as glm

    eng = rep.callable.engine
    cfg, bm, lanes = eng.model_cfg, eng.bm, eng.config.max_batch_size
    bs, most = bm.block_size, eng._spec.prefill_chunk
    pages = bm.blocks_needed(eng.max_ctx)
    dense = cfg.first_k_dense_replace
    chunk_chosen = jax.jit(lambda params, cache, *a: (
        lambda out: (out[0], out[6], out[7]))(glm.prefill_chosen(params, cfg, cache, *a, bs)))
    decode_chosen = jax.jit(lambda params, cache, *a: (
        lambda out: (out[0], out[6], out[7]))(glm.decode_chosen(params, cfg, cache, *a, bs)))

    seqs = [np.asarray(s, np.int32) for s in sequences]
    ids = [f"reference-{i}" for i in range(len(seqs))]
    choice = wrong if wrong in ("recent", "all") else "index"
    want, want_e, want_s = [], [], []
    for seq, n in zip(seqs, n_prompts):
        logits, chose, kept = reference.full_logits(eng.params, jnp.asarray(seq), cfg,
                                                    list(range(n - 1, len(seq) - 1)), choice=choice)
        want.append(np.asarray(logits))
        want_e.append(np.sort(np.asarray(chose), axis=-1)[dense:])  # [expert layers, T, k]
        want_s.append(np.asarray(kept))  # [L, the positions checked, T]
    if wrong == "e4m3":
        eng.params = _round_to_e4m3(eng.params)
    elif wrong and choice == "index":
        raise ValueError(f"no wrong-on-purpose reading named {wrong!r}")

    first, held = cfg.experts_first, cfg.experts_held

    def same(mine, theirs):
        """Experts of the program (any order, every layer) and of the
        reference (sorted, the expert layers), [layers, N, k] -> ([expert
        layers, N] bool: the same eight; [expert layers, N] bool: the same
        HELD experts among them, which is what a token's logits on this
        chip depend on: an absent expert taken for another absent one
        moves the weights' sum by the difference of two scores at the
        eighth rank and computes nothing here)."""
        mine = np.sort(np.asarray(mine), axis=-1)[dense:]

        def here(e):
            return np.sort(np.where((e >= first) & (e < first + held), e, -1), axis=-1)

        return (mine == theirs).all(-1), (here(mine) == here(theirs)).all(-1)

    chosen = {"both": 0, "larger": 0}

    def selection(mine, theirs):
        """Two choices as masks [L, T'] each -> the least share, over the
        layers, of positions both chose over the larger count."""
        both, larger = (mine & theirs).sum(-1), np.maximum(mine.sum(-1), theirs.sum(-1))
        chosen["both"] += int(both.sum())
        chosen["larger"] += int(larger.sum())
        return float((both / np.maximum(larger, 1)).min())

    # the prompts: chunk by chunk into the cache by the engine's own
    # program (arrays made anew for every call, as the engine makes them)
    rows = {"prefill": [], "decode": []}  # (distance, margin, routing agrees, selection share) a position
    pairs = agreed = agreed_held = 0
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
                got, chose, mask = chunk_chosen(eng.params, eng.cache, toks, np.int32(start), last, table,
                                                np.int32(lane))
                ok, ok_held = same(np.asarray(chose)[:, :m], want_e[lane][:, start:n])  # [expert layers, m]
                pairs, agreed, agreed_held = pairs + ok.size, agreed + int(ok.sum()), agreed_held + int(ok_held.sum())
                # the choice of the prompt's last position, the one whose logits are held
                share = selection(np.asarray(mask[:, m - 1, :len(seq)]), want_s[lane][:, 0])
                got, ref = np.asarray(got[0], np.float32), want[lane][0]
                rows["prefill"].append((float(np.abs(got - ref).max()), float(ref.max() - ref[seq[n]]),
                                        bool(ok_held[:, -1].all()), share))
                del mask
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
        got, chose, masks = decode_chosen(eng.params, eng.cache, tok, tables, lengths)
        got, chose = np.asarray(got, np.float32), np.asarray(chose)
        masks = np.asarray(masks[:, :len(seqs)])  # [L, sequences, positions]
        for lane, (seq, n) in enumerate(zip(seqs, n_prompts)):
            ok, ok_held = (a[:, 0] for a in same(chose[:, lane:lane + 1], want_e[lane][:, n + step:n + step + 1]))
            pairs, agreed, agreed_held = pairs + ok.size, agreed + int(ok.sum()), agreed_held + int(ok_held.sum())
            share = selection(masks[:, lane, :len(seq)], want_s[lane][:, step + 1])
            ref = want[lane][step + 1]
            rows["decode"].append((float(np.abs(got[lane] - ref).max()),
                                   float(ref.max() - ref[seq[n + step + 1]]), bool(ok_held.all()), share))
        nxt = np.asarray(eng._run_on_cache(eng._decode_jit, tok, lengths, tables, write,
                                           np.zeros(lanes, np.float32), eng._next_rng()))
        resampled += sum(int(nxt[lane] != seq[n + step + 1]) for lane, (seq, n) in enumerate(zip(seqs, n_prompts)))
    for rid in ids:
        bm.free(rid)

    def agrees(r):
        return r[2] and r[3] >= position_min

    def worst(kinds, column, agreeing):
        # numpy's max keeps a NaN, which then fails the limit
        picked = [r[column] for k in kinds for r in rows[k] if agrees(r) or not agreeing]
        return float(np.max(picked)) if picked else None

    both = ("prefill", "decode")
    every = [r for k in both for r in rows[k]]
    return {"agree": agreed / pairs, "agree_held": agreed_held / pairs, "pairs": pairs, "resampled": resampled,
            "selection": chosen["both"] / max(chosen["larger"], 1), "selection_positions": chosen["larger"],
            "selection_least": min(r[3] for r in every),
            "positions": len(every), "positions_agreeing": sum(agrees(r) for r in every),
            "positions_routing_agrees": sum(r[2] for r in every),
            "positions_selection_exact": sum(r[3] == 1.0 for r in every),
            "margin": worst(both, 1, True), "prefill": worst(("prefill",), 0, True),
            "decode": worst(("decode",), 0, True), "margin_all": worst(both, 1, False),
            "prefill_all": worst(("prefill",), 0, False), "decode_all": worst(("decode",), 0, False),
            # (distance, margin, routing agrees, selection share) a position, sequence by sequence within a step
            "by_position": {k: [(round(d, 4), round(m, 4), int(ok), round(s, 4)) for d, m, ok, s in rows[k]]
                            for k in both}}


# ----------------------------------------------------------------------
# client side
# ----------------------------------------------------------------------
def kernel_roofline_pct(kernel, work, counter, config, trace, before, after, peak):
    """The least time the chip could take for ONE call of a decode
    kernel (the operations of the device trace whose name matches
    `kernel`), from what the window's decode steps gave it on average
    (`counter` of ``LLMEngine.stats()`` over ``steps`` x layers calls, by
    `work` of ``flops_dsa``), over the time a call took in the trace (the
    kernel's device seconds over its calls there).  A call, not a second,
    as ``serve_minicpm_sala.kernel_roofline_pct`` says.  The least bytes
    are the positions' the counter counts, never a whole page's or a
    padded row's, so the share cannot pass 100.  None where there is
    nothing to read."""
    if not trace or not trace.get("devices") or not peak:
        return None
    named = [n for n in trace["op_seconds"] if kernel.search(n)]
    kernel_s = sum(trace["op_seconds"][n] for n in named)
    calls = sum(trace.get("op_counts", {}).get(n, 0) for n in named)
    if kernel_s <= 0 or not calls or counter not in after:
        return None
    layers = config["num_hidden_layers"]
    steps = after["steps"] - before["steps"]
    if steps <= 0:
        return None
    least = flops.least_seconds(
        work(config, after[counter] - before[counter], steps * after["max_batch_size"] * layers), peak)
    return 100.0 * least["seconds"] / (steps * layers) / (kernel_s / calls)


def run(job) -> dict:
    if importlib.util.find_spec(FAMILY) is None:
        raise RuntimeError(f"this checkout's program has no {FAMILY}: it cannot run {job['config']['name']}")
    import ray_tpu

    cell, tr, config = job["cell"], job["cell"]["traffic"], job["config"]
    if tr["mode"] != "closed":
        raise ValueError("runners/serve_glm_5.py drives closed loops only")
    seconds, seed = job["seconds"], job["seed"]
    handle, actor = deploy(job)
    t_deployed = time.time()

    def call(fn, *args):
        return actor.__ray_call__.remote(fn, *args)

    installed = ray_tpu.get(call(_rep_install), timeout=600)
    glm_sizes = ray_tpu.get(call(_rep_glm_sizes), timeout=120)
    stream_handle = handle.options(stream=True)
    a1, a2, b = setup_checks(job, stream_handle)
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
    # drive_from_full reads when it runs: this cell's is its own (traffic.send_gap_s says why)
    chunked.SEND_GAP_S = tr["send_gap_s"]
    streams, t0, t_begin = drive_from_full(
        stream_handle, plan, cell["engine"]["max_batch_size"], tr["lead_in"], seconds, at)
    t_end = t0 + seconds

    stats = {k: ray_tpu.get(v, timeout=300) for k, v in probes.items()}
    after_drain = ray_tpu.get(call(_rep_stats), timeout=300)
    deadline = time.time() + 180  # the chunks of the prompts in flight when the streams closed
    while after_drain["kv_blocks_in_use"] and time.time() < deadline:
        time.sleep(0.5)
        after_drain = ray_tpu.get(call(_rep_stats), timeout=300)
    trace = None
    if job["trace"]:
        trace = ray_tpu.get(call(_rep_trace_facts, tr["trace_seconds"], job.get("keep_trace"),
                                 cell.get("trace_annotations", ())), timeout=900)

    # the float32 reference, outside the window
    chk = cell["checks"]
    sequences = [s.req["prompt"] + s.tokens for s in (a1, b)]
    ref = ray_tpu.get(call(_rep_reference, sequences, chk["prompt_lens"], chk.get("wrong_on_purpose"),
                           chk["selection_position_min"]), timeout=2400)
    device = ray_tpu.get(call(_rep_device), timeout=120)

    finished = [s for s in streams if s.done and not s.failed and t0 <= s.t_done < t_end]
    bad = [s for s in streams if s.failed]
    out_tokens = sum(1 for s in streams for t in s.token_t if t0 <= t < t_end)
    rate_tokens, rate_s = edge_rate(streams, t0, t_end)  # whole engine steps (serve.edge_rate)

    sizes, eng = job["sizes"], cell["engine"]
    before, after = stats["before"], stats["after"]
    # every row a program was given made its pairs in every EXPERT layer:
    # max_batch_size rows a decode program, the padded chunk a prefill
    rows = after["max_batch_size"] * (after["steps"] - before["steps"]) + (
        after["prefill_bucket_tokens"] - before["prefill_bucket_tokens"])
    delta = {k: after.get(k, 0) - before.get(k, 0) for k in (
        "moe_pairs_routed", "moe_pairs_held", "moe_pairs", "dsa_positions_cached", "dsa_positions_kept",
        "dsa_positions_cached_prefill", "dsa_positions_kept_prefill", "dsa_index_positions_scored",
        "kv_positions_attended", "steps")}
    slots = eng["pool_tokens"] + eng["block_size"]  # the scratch block beside the pool
    held_bytes = after["param_bytes"] + glm_sizes["cache_bytes"]
    # a path none of whose positions agreed with the reference has nothing to hold;
    # positions_that_agree_are_enough holds the count
    within = {k: ref[k] is None or ref[k] <= chk[limit] for k, limit in (
        ("margin", "logit_margin"), ("prefill", "logit_distance"), ("decode", "logit_distance"))}
    expert_layers = config["held"]["expert_layers"]
    checks = {
        "preset_has_the_configuration's_sizes": all(
            installed[k] == sizes[k] for k in ("n_layer", "n_embd", "n_head", "n_positions", "vocab_rows")
        ) and sizes["dtype"] == installed["dtype"],
        "preset_has_the_configuration's_widths": all(
            glm_sizes["config"][key] == config[key] for key, _ in WIDTH_KEYS
        ) and glm_sizes["rope_theta"] == config["rope_parameters"]["rope_theta"],
        "preset_holds_the_configuration's_share": glm_sizes["held"] == config["held"] and (
            glm_sizes["published"] == {k: config["published"][k] for k in ("n_routed_experts", "vocab_size")}),
        "engine_serves_max_model_len": glm_sizes["max_context"] == eng["max_model_len"],
        "cache_is_a_pool_of_latent_rows_and_a_pool_of_index_keys": glm_sizes["cache"] == {
            "k_pages": [sizes["n_layer"], slots, chk["cached_row_columns"]],
            "index_k": [sizes["n_layer"], slots, config["index_head_dim"]]},
        "weights_and_pools_fill_the_chip": held_bytes >= chk["held_bytes_min"],
        "finished_requests_returned_max_tokens": all(
            len(s.tokens) == s.req["max_tokens"] for s in finished + [a1, a2, b]),
        "same_prompt_twice_same_tokens": a1.tokens == a2.tokens,
        "kv_blocks_back_to_zero": after_drain["kv_blocks_in_use"] == 0,
        "returned_tokens_within_margin_of_float32_reference": within["margin"],
        "prefill_logits_within_distance_of_float32_reference": within["prefill"],
        "paged_decode_logits_within_distance_of_float32_reference": within["decode"],
        "chosen_experts_agree_with_float32_reference": ref["agree"] >= chk["expert_agreement_min"],
        "chosen_positions_agree_with_float32_reference": ref["selection"] >= chk["selection_agreement_min"],
        "positions_that_agree_are_enough": (
            ref["positions_agreeing"] >= chk["positions_agreeing_min"] * ref["positions"]),
        "no_compile_in_window": after["compiles"] == before["compiles"],
        "some_request_finished": len(finished) > 0,
        "every_lane_decoded_before_the_window": t0 - t_begin < tr["lead_in"]["at_most_s"],
        "every_row_made_its_pairs": rows > 0 and delta["moe_pairs_routed"] == (
            config["num_experts_per_tok"] * expert_layers * rows),
        "every_held_pair_was_computed_and_no_other": 0 < delta["moe_pairs_held"] == delta["moe_pairs"],
        "no_query_attended_more_than_index_topk": (
            0 < delta["dsa_positions_kept"] <= min(
                delta["dsa_positions_cached"],
                config["index_topk"] * sizes["n_layer"] * after["max_batch_size"] * delta["steps"])
            and delta["dsa_positions_kept_prefill"] <= delta["dsa_positions_cached_prefill"]),
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
                     "prefill_chunks", "prompt_tokens", "moe_experts_hit")},
        **{k + "_in_window": v for k, v in delta.items() if k != "steps"},
        "running_before": before["running"], "waiting_middle": stats["middle"]["waiting"],
        "waiting_after": after["waiting"], "kv_blocks_middle": stats["middle"]["kv_blocks_in_use"],
        "kv_blocks_after": after["kv_blocks_in_use"],
        "moe_rows": rows, "param_bytes": after["param_bytes"], "cache_bytes": glm_sizes["cache_bytes"],
        "held_bytes": held_bytes,
        "worst_logit_margin": ref["margin"], "worst_logit_distance_prefill": ref["prefill"],
        "worst_logit_distance_decode": ref["decode"],
        **{"worst_" + k: ref[k] for k in ("margin_all", "prefill_all", "decode_all")},
        "chosen_experts_agree": ref["agree"], "held_experts_agree": ref["agree_held"],
        "chosen_expert_pairs": ref["pairs"],
        "chosen_positions_agree": ref["selection"], "chosen_positions_compared": ref["selection_positions"],
        "chosen_positions_agree_least": ref["selection_least"],
        "positions_checked": ref["positions"], "positions_agreeing": ref["positions_agreeing"],
        "positions_routing_agrees": ref["positions_routing_agrees"],
        "positions_selection_exact": ref["positions_selection_exact"],
        "replay_resampled_tokens": ref["resampled"], "logit_readings_by_position": ref["by_position"],
        "rate_tokens": rate_tokens, "rate_s": rate_s,
        "serve_out_tokens_per_s": rate_tokens / rate_s,
    }
    if job["trace"]:
        peak = spec.load_peaks().get(device["kind"])
        values["dsa_index_paged_scores_roofline"] = kernel_roofline_pct(
            INDEX_KERNEL, flops_dsa.index_scores_work, "dsa_index_positions_scored", config, trace, before, after,
            peak)
        values["mla_sparse_paged_decode_attention_roofline"] = kernel_roofline_pct(
            ATTEND_KERNEL, flops_dsa.sparse_decode_work, "kv_positions_attended", config, trace, before, after,
            peak)
        # the OLMoE runner's reading, at this model's expert width: moe_pairs counts the HELD pairs
        values["moe_gmm_roofline_pct"] = gmm_roofline_pct(
            flops_mla.expert_sizes(config), trace, stats["trace_start"], after, peak)
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

"""The serve runner for the Kimi-Linear family: the client side of
``runners/serve_mistral_small_4.py`` (one replica behind ``serve.run``, a
closed loop whose window is locked to the engine's own timeline by
``serve_minicpm_sala.drive_from_full``, prompts in chunks, the engine's
``max_model_len``, the replica's heap settled after set-up by
``common._rep_settle``, the mix's fixed order and the traced run from
``serve_olmoe.py``, a kernel's seconds a call from
``serve_nemotron_3_nano.py``) with what this family needs:

- the float32 reference is ``benchmark/reference_kimi_linear.py`` (the
  delta rule as its recurrence, non-absorbed attention with nothing
  rotated, the same share of experts and of vocabulary), and the
  program's own logits over the rows held are held to it for two set-up
  requests, one under a chunk and one of three chunks: the prompt's
  through the family's last chunk program, reading the STATE, the tails
  and the latent rows the engine's own programs wrote for the chunks
  before it; the answer's through its decode forward (the KDA kernel in
  place, the latent kernel over the pages) at the engine's lane count
  over the engine's OWN state slots and pool;
- beside the distance, the share of (token, expert layer) pairs whose
  eight experts are the reference's.  Under random weights the eighth
  and ninth largest of 256 sigmoid scores lie close, a bf16 path gives
  some tokens another eighth expert than float32 does, and a token with
  another HELD expert moves its logits by far more than rounding does
  (one absent expert for another computes nothing on this chip): so the
  distance and the margin are taken over the positions whose held
  experts agree in every expert layer, and the share that agrees has a
  limit of its own;
- the checks hold the preset to the configuration file's widths and
  layer lists, to the experts and rows it says are held, the cache to
  its pool and its lane state, and the engine's counters to its rows:
  every row a program was given made ``num_experts_per_token`` pairs in
  each of the EXPERT layers, every pair whose expert is held was
  computed, and a decode step updated the states of its running lanes
  and of no other;
- ``kda_decode_step_roofline`` and
  ``mla_paged_decode_attention_roofline``: a call's least time
  (``flops_kda.kda_step_work`` over the window's updated states,
  ``flops_mla.mla_decode_work`` over its attended positions, by the calls
  the steps made) against a call's time in the trace; and
  ``moe_gmm_roofline_pct`` by the OLMoE runner's own function, at this
  model's expert width and over the held experts' pairs.

A checkout whose program has no ``ray_tpu.models.kimi_linear`` fails here
at once, before anything is deployed.  This process imports no JAX.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import time

from benchmark import flops, flops_kda, flops_mla, spec
from benchmark import traffic as traffic_mod
from benchmark.runners.serve import (  # noqa: F401 - stop is the harness's hook
    _cycle, _rep_device, _rep_install, _rep_stats, bursts, edge_rate, stop,
)
from benchmark.runners import serve_minicpm_sala as chunked
from benchmark.runners.common import _rep_settle
from benchmark.runners.serve_minicpm_sala import deploy, drive_from_full, setup_checks
from benchmark.runners.serve_nemotron_3_nano import _kernel_seconds_a_call
from benchmark.runners.serve_olmoe import (
    _rep_trace_facts, _rep_trace_start, from_the_head, gmm_roofline_pct,
)

FAMILY = "ray_tpu.models.kimi_linear"
# the configuration file's keys the preset must agree with, beside the
# sizes every serve cell checks: (key of the file, attribute of the config)
WIDTH_KEYS = (("kv_lora_rank", "kv_lora_rank"), ("qk_nope_head_dim", "qk_nope_head_dim"),
              ("qk_rope_head_dim", "qk_rope_head_dim"), ("v_head_dim", "v_head_dim"),
              ("intermediate_size", "intermediate_size"), ("moe_intermediate_size", "moe_intermediate_size"),
              ("num_experts", "experts_held"), ("num_experts_per_token", "num_experts_per_tok"),
              ("num_shared_experts", "n_shared_experts"), ("moe_renormalize", "norm_topk_prob"),
              ("routed_scaling_factor", "routed_scaling_factor"), ("rms_norm_eps", "rms_norm_eps"),
              ("first_k_dense_replace", "first_k_dense_replace"))
KDA_KEYS = (("num_heads", "kda_num_heads"), ("head_dim", "kda_head_dim"), ("short_conv_kernel_size", "conv_kernel"))
HELD_KEYS = (("experts_first", "experts_first"), ("experts_held", "experts_held"),
             ("vocab_first", "vocab_first"), ("vocab_rows", "vocab_size"),
             ("router_outputs", "n_routed_experts"), ("num_experts_per_token", "num_experts_per_tok"))
KDA_KERNEL = re.compile(r"^kda_decode_step")  # their names in the device trace
MLA_KERNEL = re.compile(r"^mla_paged_decode_attention")


# ----------------------------------------------------------------------
# replica side (the process that holds the chip)
# ----------------------------------------------------------------------
def _rep_sizes(rep):
    eng = rep.callable.engine
    cfg = eng.model_cfg
    dense = cfg.first_k_dense_replace
    layers = {kind: [n + 1 for n, k in enumerate(cfg.mixer_types) if k == kind] for kind in "KA"}  # from 1
    state = {name: [list(eng.cache[name].shape), eng.cache[name].dtype.name] for name, *_ in eng._spec.lane_state}
    return {"config": {key: getattr(cfg, attr) for key, attr in WIDTH_KEYS},
            "linear_attn_config": {**{key: getattr(cfg, attr) for key, attr in KDA_KEYS},
                                   "kda_layers": layers["K"], "full_attn_layers": layers["A"]},
            "low_rank": cfg.kda_low_rank,
            "held": {**{key: getattr(cfg, attr) for key, attr in HELD_KEYS},
                     "dense_layers": dense, "expert_layers": cfg.n_layer - dense},
            "published": {"num_experts": cfg.n_routed_experts, "vocab_size": cfg.published_vocab_size},
            "max_context": eng.max_ctx, "k_pages": list(eng.cache["k_pages"].shape), "lane_state": state,
            "v_pool": "v_pages" in eng.cache,
            "cache_bytes": sum(v.nbytes for v in eng.cache.values())}


def _rep_reference(rep, sequences, n_prompts, wrong=None):
    """The engine's answers against the plain float32 forward over the
    whole of each sequence (prompt + the tokens the engine returned), on
    the engine's own weights, after the drain (the engine is idle).
    `sequences` may differ in length; sequence i goes to lane i.  Each of
    the answer's positions goes through the path that gave its token:
    the first from the family's chunk program on the prompt's last chunk
    (the chunks before it written by the engine's own prefill program:
    states, tails, latent rows), the others from its decode forward at
    the engine's lane count over the engine's own state slots and pool,
    which the engine's own decode program then writes.  -> agree: of the
    (token, expert layer) pairs of the last chunks' real tokens and of
    the decode steps, the share whose eight experts are the reference's
    (agree_held: whose HELD experts are); margin, prefill, decode: how
    far a returned token's logit lies under the reference's largest, and
    how far the program's logits lie from the reference's over the rows
    held, at most, over the positions whose own held experts agree in
    every expert layer (``*_all``: over every position); state: how
    far each KDA layer's state of each sequence's lane, as the engine's
    own programs left it, lies from the reference's after the same
    positions, relative (what a state kept in a lower precision moves
    long before a logit's largest distance shows it); resampled: the
    tokens the engine's programs gave otherwise this time.  `wrong`
    (one of ``reference_kimi_linear.WRONG``) computes the REFERENCE's
    side as another model: the builder's wrong-on-purpose readings (the
    disagreement is the same whichever side is wrong, and the program has
    no switch for it)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_kimi_linear as reference
    from ray_tpu.models import kimi_linear as kimi

    eng = rep.callable.engine
    cfg, bm, lanes = eng.model_cfg, eng.bm, eng.config.max_batch_size
    bs, most = bm.block_size, eng._spec.prefill_chunk
    pages = bm.blocks_needed(eng.max_ctx)
    dense = cfg.first_k_dense_replace
    # of (logits, rows, None, {}, state, counters, chosen): the logits and the experts chosen
    chunk_chosen = jax.jit(lambda params, cache, *a: kimi.prefill_chosen(params, cfg, cache, *a, bs)[::6])
    decode_chosen = jax.jit(lambda params, cache, *a: kimi.decode_chosen(params, cfg, cache, *a, bs)[::6])

    seqs = [np.asarray(s, np.int32) for s in sequences]
    ids = [f"reference-{i}" for i in range(len(seqs))]
    want, want_e, want_s = [], [], []
    for seq, n in zip(seqs, n_prompts):
        # less its last token, which the engine returned and never fed: the states are the replay's last
        logits, chose, states = reference.full_logits(eng.params, jnp.asarray(seq[:-1]), cfg,
                                                      list(range(n - 1, len(seq) - 1)), wrong=wrong)
        want.append(np.asarray(logits))
        want_e.append(np.sort(np.asarray(chose), axis=-1)[dense:])  # [expert layers, T - 1, k]
        want_s.append([np.asarray(s) for s in states])

    first, held = cfg.experts_first, cfg.experts_held

    def same(mine, theirs):
        """Experts of the program (any order, every layer) and of the
        reference (sorted, the expert layers), [layers, N, k] -> ([expert
        layers, N] bool: the same eight; [expert layers, N] bool: the
        same HELD experts among them, which is what a token's logits on
        this chip depend on)."""
        mine = np.sort(np.asarray(mine), axis=-1)[dense:]

        def here(e):
            return np.sort(np.where((e >= first) & (e < first + held), e, -1), axis=-1)

        return (mine == theirs).all(-1), (here(mine) == here(theirs)).all(-1)

    # the prompts: chunk by chunk into the cache by the engine's own
    # program (arrays made anew for every call, as the engine makes them)
    rows = {"prefill": [], "decode": []}  # (distance, margin, held routing agrees) a position
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
                got, chose = chunk_chosen(eng.params, eng.cache, toks, np.int32(start), last, table, np.int32(lane))
                ok, ok_held = same(np.asarray(chose)[:, :m], want_e[lane][:, start:n])  # [expert layers, m]
                pairs, agreed, agreed_held = pairs + ok.size, agreed + int(ok.sum()), agreed_held + int(ok_held.sum())
                got, ref = np.asarray(got[0], np.float32), want[lane][0]
                rows["prefill"].append((float(np.abs(got - ref).max()), float(ref.max() - ref[seq[n]]),
                                        bool(ok_held[:, -1].all())))
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
            ok, ok_held = (a[:, 0] for a in same(chose[:, lane:lane + 1], want_e[lane][:, n + step:n + step + 1]))
            pairs, agreed, agreed_held = pairs + ok.size, agreed + int(ok.sum()), agreed_held + int(ok_held.sum())
            ref = want[lane][step + 1]
            rows["decode"].append((float(np.abs(got[lane] - ref).max()),
                                   float(ref.max() - ref[seq[n + step + 1]]), bool(ok_held.all())))
        nxt = np.asarray(eng._run_on_cache(eng._decode_jit, tok, lengths, tables, write,
                                           np.zeros(lanes, np.float32), eng._next_rng()))
        resampled += sum(int(nxt[lane] != seq[n + step + 1]) for lane, (seq, n) in enumerate(zip(seqs, n_prompts)))
    for rid in ids:
        bm.free(rid)
    # the lanes' states as the engine's own programs left them, against the reference's after the
    # same positions: |S - S_ref| over |S_ref| (Frobenius), a KDA layer a column, a sequence a row
    state = [[float(np.linalg.norm(np.asarray(eng.cache[kimi.state_name(i)][lane]) - ref) / np.linalg.norm(ref))
              for i, ref in enumerate(want_s[lane])] for lane in range(len(seqs))]

    def worst(kinds, column, agreeing):
        # numpy's max keeps a NaN, which then fails the limit
        picked = [r[column] for k in kinds for r in rows[k] if r[2] or not agreeing]
        return float(np.max(picked)) if picked else None

    both = ("prefill", "decode")
    return {"agree": agreed / pairs, "agree_held": agreed_held / pairs, "pairs": pairs, "resampled": resampled,
            "state": state,
            "positions": sum(len(rows[k]) for k in both),
            "positions_agreeing": sum(r[2] for k in both for r in rows[k]),
            "margin": worst(both, 1, True), "prefill": worst(("prefill",), 0, True),
            "decode": worst(("decode",), 0, True), "margin_all": worst(both, 1, False),
            "prefill_all": worst(("prefill",), 0, False), "decode_all": worst(("decode",), 0, False),
            # (distance, margin, held routing agrees) a position, sequence by sequence within a step
            "by_position": {k: [(round(d, 4), round(m, 4), int(ok)) for d, m, ok in rows[k]] for k in both}}


# ----------------------------------------------------------------------
# client side
# ----------------------------------------------------------------------
def kernel_roofline_pct(pattern, work, calls, trace, peak):
    """The least time the chip could take for ONE call of a decode
    kernel (`work`: the least operations and bytes of what the window's
    decode steps gave it, over the `calls` they made of it), over the
    time a call took in the trace (the kernel's device seconds over its
    calls there).  A call, not a second, as
    ``serve_minicpm_sala.kernel_roofline_pct`` says.  The least bytes are
    what the algorithm needs (the RUNNING lanes' states; the ATTENDED
    positions, never the whole pages copied or a padded row), so the
    share cannot pass 100.  None where there is nothing to read."""
    if not trace or not trace.get("devices") or not peak or calls <= 0:
        return None
    a_call = _kernel_seconds_a_call(trace, pattern)
    if not a_call:
        return None
    return 100.0 * flops.least_seconds(work, peak)["seconds"] / calls / a_call


def lane_state_bytes(config, dtype="bfloat16") -> int:
    """What the configuration says a lane holds: a float32 state and
    three tails of ``short_conv_kernel_size - 1`` rows in the serving
    dtype a KDA layer."""
    lin = config["linear_attn_config"]
    inner, itemsize = lin["num_heads"] * lin["head_dim"], {"bfloat16": 2, "float32": 4}[dtype]
    tails = 3 * (lin["short_conv_kernel_size"] - 1) * inner * itemsize
    return len(lin["kda_layers"]) * (inner * lin["head_dim"] * 4 + tails)


def run(job) -> dict:
    if importlib.util.find_spec(FAMILY) is None:
        raise RuntimeError(f"this checkout's program has no {FAMILY}: it cannot run {job['config']['name']}")
    import ray_tpu

    cell, tr, config = job["cell"], job["cell"]["traffic"], job["config"]
    if tr["mode"] != "closed":
        raise ValueError("runners/serve_kimi_linear.py drives closed loops only")
    seconds, seed = job["seconds"], job["seed"]
    handle, actor = deploy(job)
    t_deployed = time.time()

    def call(fn, *args):
        return actor.__ray_call__.remote(fn, *args)

    installed = ray_tpu.get(call(_rep_install), timeout=600)
    held_sizes = ray_tpu.get(call(_rep_sizes), timeout=120)
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
    ref = ray_tpu.get(call(_rep_reference, sequences, chk["prompt_lens"], chk.get("wrong_on_purpose")),
                      timeout=2400)
    device = ray_tpu.get(call(_rep_device), timeout=120)

    finished = [s for s in streams if s.done and not s.failed and t0 <= s.t_done < t_end]
    bad = [s for s in streams if s.failed]
    out_tokens = sum(1 for s in streams for t in s.token_t if t0 <= t < t_end)
    rate_tokens, rate_s = edge_rate(streams, t0, t_end)  # whole engine steps (serve.edge_rate)

    sizes, eng = job["sizes"], cell["engine"]
    before, after = stats["before"], stats["after"]
    lin = config["linear_attn_config"]
    n_k, n_a = len(lin["kda_layers"]), len(lin["full_attn_layers"])
    # every row a program was given made its pairs in every EXPERT layer:
    # max_batch_size rows a decode program, the padded chunk a prefill
    rows = after["max_batch_size"] * (after["steps"] - before["steps"]) + (
        after["prefill_bucket_tokens"] - before["prefill_bucket_tokens"])
    delta = {k: after.get(k, 0) - before.get(k, 0) for k in (
        "moe_pairs_routed", "moe_pairs_held", "moe_pairs", "kv_positions_attended", "kv_positions_gathered",
        "mla_decode_calls", "kda_lane_steps", "kda_chunk_tokens", "state_bytes", "steps")}
    slots = eng["pool_tokens"] + eng["block_size"]  # the scratch block beside the pool
    held_bytes = after["param_bytes"] + held_sizes["cache_bytes"]
    # a path none of whose positions routed as the reference did has nothing to hold;
    # positions_whose_routing_agrees_are_enough holds the count
    within = {k: ref[k] is None or ref[k] <= chk[limit] for k, limit in (
        ("margin", "logit_margin"), ("prefill", "logit_distance"), ("decode", "logit_distance"))}
    expert_layers = config["held"]["expert_layers"]
    tail = [[eng["max_batch_size"], 3 * lin["num_heads"] * lin["head_dim"]], sizes["dtype"]]
    state = [[eng["max_batch_size"], lin["num_heads"], lin["head_dim"], lin["head_dim"]], "float32"]
    checks = {
        "preset_has_the_configuration's_sizes": all(
            installed[k] == sizes[k] for k in ("n_layer", "n_embd", "n_head", "n_positions", "vocab_rows")
        ) and sizes["dtype"] == installed["dtype"],
        "preset_has_the_configuration's_widths": all(
            held_sizes["config"][key] == config[key] for key, _ in WIDTH_KEYS
        ) and held_sizes["linear_attn_config"] == lin and held_sizes["low_rank"] == config["assumed"]["low_rank"],
        "preset_holds_the_configuration's_share": held_sizes["held"] == config["held"] and (
            held_sizes["published"] == {k: config["published"][k] for k in ("num_experts", "vocab_size")}),
        "engine_serves_max_model_len": held_sizes["max_context"] == eng["max_model_len"],
        "cache_is_a_pool_of_latent_rows_and_no_v_pool": held_sizes["k_pages"] == [
            n_a, slots, chk["cached_row_columns"]] and not held_sizes["v_pool"],
        "every_lane_holds_a_state_and_three_tails_a_kda_layer": held_sizes["lane_state"] == {
            **{f"kda_tail_{w}_{i}": tail for i in range(n_k) for w in "qkv"},
            **{f"kda_state_{i}": state for i in range(n_k)}
        } and after["state_bytes_held"] == eng["max_batch_size"] * lane_state_bytes(config, sizes["dtype"]),
        "weights_states_and_pool_fill_the_chip": held_bytes >= chk["held_bytes_min"],
        "finished_requests_returned_max_tokens": all(
            len(s.tokens) == s.req["max_tokens"] for s in finished + [a1, a2, b]),
        "same_prompt_twice_same_tokens": a1.tokens == a2.tokens,
        "kv_blocks_back_to_zero": after_drain["kv_blocks_in_use"] == 0,
        "returned_tokens_within_margin_of_float32_reference": within["margin"],
        "prefill_logits_within_distance_of_float32_reference": within["prefill"],
        "decode_logits_within_distance_of_float32_reference": within["decode"],
        # the FIRST KDA layer's, whose inputs are the embedding's: a later layer's state differs by what bf16
        # matmuls did to the stream before it (0.012-0.069), which a rounded state no longer stands out of
        "first_layer_states_within_distance_of_float32_reference": max(
            s[0] for s in ref["state"]) <= chk["state_distance"],
        "chosen_experts_agree_with_float32_reference": ref["agree"] >= chk["expert_agreement_min"],
        "positions_whose_routing_agrees_are_enough": (
            ref["positions_agreeing"] >= chk["positions_agreeing_min"] * ref["positions"]),
        "no_compile_in_window": after["compiles"] == before["compiles"],
        "some_request_finished": len(finished) > 0,
        "every_lane_decoded_before_the_window": t0 - t_begin < tr["lead_in"]["at_most_s"],
        "every_row_made_its_pairs": rows > 0 and delta["moe_pairs_routed"] == (
            config["num_experts_per_token"] * expert_layers * rows),
        "every_held_pair_was_computed_and_no_other": 0 < delta["moe_pairs_held"] == delta["moe_pairs"],
        "every_running_lane_updated_its_states_and_no_other": 0 < delta["kda_lane_steps"] <= (
            after["max_batch_size"] * n_k * delta["steps"]),
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
           for k in ("decode_fetch_s", "prefill_fetch_s", "idle_s", "stall_s", "total_tokens",
                     "prefill_chunks", "prompt_tokens", "moe_experts_hit")},
        **{k + "_in_window": v for k, v in delta.items()},
        "running_before": before["running"], "waiting_middle": stats["middle"]["waiting"],
        "waiting_after": after["waiting"], "kv_blocks_middle": stats["middle"]["kv_blocks_in_use"],
        "kv_blocks_after": after["kv_blocks_in_use"],
        "moe_rows": rows, "param_bytes": after["param_bytes"], "cache_bytes": held_sizes["cache_bytes"],
        "state_bytes_held": after["state_bytes_held"], "held_bytes": held_bytes,
        # what a decode step reads of the weights: all of them but the embedding's unread rows, nearly
        "weight_bytes": after["param_bytes"],
        "worst_logit_margin": ref["margin"], "worst_logit_distance_prefill": ref["prefill"],
        "worst_logit_distance_decode": ref["decode"], "state_distance_by_sequence_and_layer": ref["state"],
        **{"worst_" + k: ref[k] for k in ("margin_all", "prefill_all", "decode_all")},
        "chosen_experts_agree": ref["agree"], "held_experts_agree": ref["agree_held"],
        "chosen_expert_pairs": ref["pairs"],
        "positions_checked": ref["positions"], "positions_agreeing": ref["positions_agreeing"],
        "replay_resampled_tokens": ref["resampled"], "logit_readings_by_position": ref["by_position"],
        "rate_tokens": rate_tokens, "rate_s": rate_s,
        "serve_out_tokens_per_s": rate_tokens / rate_s,
    }
    if job["trace"]:
        peak = spec.load_peaks().get(device["kind"])
        values["kda_decode_step_roofline"] = kernel_roofline_pct(
            KDA_KERNEL, flops_kda.kda_step_work(config, delta["kda_lane_steps"]), delta["steps"] * n_k, trace, peak)
        values["mla_paged_decode_attention_roofline"] = kernel_roofline_pct(
            MLA_KERNEL, flops_mla.mla_decode_work(config, delta["kv_positions_attended"],
                                                  delta["steps"] * after["max_batch_size"] * n_a),
            delta["mla_decode_calls"], trace, peak)
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

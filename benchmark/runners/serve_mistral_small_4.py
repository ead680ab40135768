"""The serve runner for the Mistral-Small-4 family: the client side of
``runners/serve_minicpm_sala.py`` (one replica behind ``serve.run``, a
closed loop whose window is locked to the engine's own timeline, prompts
in chunks, the engine's ``max_model_len``) with what this family needs:

- the float32 reference is ``benchmark/reference_mistral_small_4.py``
  (non-absorbed attention, the same share of experts and of vocabulary),
  and the program's own logits over the rows held are held to it for
  two set-up requests, one short and one of three chunks that decodes
  past position 8,192: the prompt's through the family's last chunk
  program, reading what the engine's own programs wrote for the chunks
  before it; the answer's through its absorbed paged decode at the
  engine's lane count over the engine's OWN pool;
- beside the distance, the share of (token, layer) whose four experts
  are the reference's.  Under random weights the fourth and fifth
  largest of the router's 128 outputs lie close, a bf16 path gives some
  tokens another fourth expert than float32 does, and such a token's
  logits move by far more than rounding moves them: so the distance and
  the margin are taken over the positions whose own routing agrees in
  every layer (what they then hold is the arithmetic), and the share
  that agrees has a limit of its own (what it holds is the router);
- the checks hold the preset to the configuration file's widths, to the
  experts and rows it says are held, and the engine's counters to its
  rows: every row a program was given made ``num_experts_per_tok`` pairs
  a layer (``moe_pairs_routed``), and every pair whose expert is held
  was computed (``moe_pairs == moe_pairs_held``);
- after set-up the replica's heap is collected once and frozen
  (``common._rep_settle`` says why);
- ``mla_paged_decode_attention_roofline``: a call's least time
  (``flops_mla.mla_decode_work`` over the window's attended positions, by
  ``steps`` x layers calls) against a call's time in the trace; and
  ``moe_gmm_roofline_pct`` by the OLMoE runner's own function, at this
  model's expert width and over the held experts' pairs.

A checkout whose program has no ``ray_tpu.models.mistral4`` fails here
at once, before anything is deployed.  This process imports no JAX.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import time

from benchmark import flops, flops_mla, spec
from benchmark import traffic as traffic_mod
from benchmark.runners.serve import (  # noqa: F401 - stop is the harness's hook
    _cycle, _rep_device, _rep_install, _rep_stats, bursts, edge_rate, stop,
)
from benchmark.runners import serve_minicpm_sala as chunked
from benchmark.runners.common import _rep_settle
from benchmark.runners.serve_minicpm_sala import deploy, drive_from_full, setup_checks
from benchmark.runners.serve_olmoe import (
    _rep_trace_facts, _rep_trace_start, from_the_head, gmm_roofline_pct,
)

FAMILY = "ray_tpu.models.mistral4"
# the configuration file's keys the preset must agree with, beside the
# sizes every serve cell checks: (key of the file, attribute of the config)
MLA_KEYS = (("q_lora_rank", "q_lora_rank"), ("kv_lora_rank", "kv_lora_rank"),
            ("qk_nope_head_dim", "qk_nope_head_dim"), ("qk_rope_head_dim", "qk_rope_head_dim"),
            ("v_head_dim", "v_head_dim"), ("moe_intermediate_size", "moe_intermediate_size"),
            ("n_routed_experts", "experts_held"), ("num_experts_per_tok", "num_experts_per_tok"),
            ("n_shared_experts", "n_shared_experts"), ("norm_topk_prob", "norm_topk_prob"),
            ("routed_scaling_factor", "routed_scaling_factor"), ("rms_norm_eps", "rms_norm_eps"))
ROPE_KEYS = (("rope_theta", "rope_theta"), ("factor", "rope_factor"),
             ("original_max_position_embeddings", "original_max_position_embeddings"),
             ("beta_fast", "beta_fast"), ("beta_slow", "beta_slow"), ("mscale", "mscale"),
             ("mscale_all_dim", "mscale_all_dim"), ("llama_4_scaling_beta", "llama_4_scaling_beta"))
HELD_KEYS = (("experts_first", "experts_first"), ("experts_held", "experts_held"),
             ("vocab_first", "vocab_first"), ("vocab_rows", "vocab_size"),
             ("router_outputs", "n_routed_experts"), ("num_experts_per_tok", "num_experts_per_tok"))
KERNEL = re.compile(r"^mla_paged_decode_attention")  # its name in the device trace


# ----------------------------------------------------------------------
# replica side (the process that holds the chip)
# ----------------------------------------------------------------------
def _rep_mla_sizes(rep):
    eng = rep.callable.engine
    cfg = eng.model_cfg
    return {"config": {key: getattr(cfg, attr) for key, attr in MLA_KEYS},
            "rope": {key: getattr(cfg, attr) for key, attr in ROPE_KEYS},
            "held": {key: getattr(cfg, attr) for key, attr in HELD_KEYS},
            "published": {"n_routed_experts": cfg.n_routed_experts, "vocab_size": cfg.published_vocab_size},
            "max_context": eng.max_ctx, "cache": {k: list(v.shape) for k, v in eng.cache.items()}}


def _round_to_e4m3(params):
    """The tree with every matrix rounded to float8_e4m3's three
    mantissa bits, arithmetically (``ldexp(round(16 m) / 16, e)``: XLA
    elides a round trip through the type), a leaf at a time in place."""
    import jax
    import jax.numpy as jnp

    def rounded(x):
        m, e = jnp.frexp(x.astype(jnp.float32))
        return jnp.ldexp(jnp.round(16 * m) / 16, e).astype(x.dtype)

    # donated: there is no room for a second copy of an expert tensor beside the cache
    in_place = jax.jit(rounded, donate_argnums=0)
    return jax.tree.map(lambda x: in_place(x) if x.ndim > 1 else x, params)


def _rep_reference(rep, sequences, n_prompts, wrong=None):
    """The engine's answers against the plain float32 forward over the
    whole of each sequence (prompt + the tokens the engine returned), on
    the engine's own weights, after the drain (the engine is idle).
    `sequences` may differ in length; sequence i goes to lane i.  Each of
    the answer's positions goes through the path that gave its token:
    the first from the family's chunk program on the prompt's last chunk
    (the chunks before it written by the engine's own prefill program),
    the others from its paged decode at the engine's lane count over the
    engine's own pool, which the engine's own decode program then
    writes.  -> agree: of the (token, layer) pairs of the last chunks'
    real tokens and of the decode steps, the share whose experts are the
    reference's; margin, prefill, decode: how far a returned token's
    logit lies under the reference's largest, and how far the program's
    logits lie from the reference's over the rows held, at most, over
    the positions whose own routing agrees in every layer (``*_all``:
    over every position); resampled: the tokens the engine's programs
    gave otherwise this time.  `wrong` ("e4m3") computes the program's
    side on rounded weights: the builder's wrong-on-purpose reading,
    which leaves the engine's weights rounded."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference_mistral_small_4 as reference
    from ray_tpu.models import mistral4

    eng = rep.callable.engine
    cfg, bm, lanes = eng.model_cfg, eng.bm, eng.config.max_batch_size
    bs, most = bm.block_size, eng._spec.prefill_chunk
    pages = bm.blocks_needed(eng.max_ctx)
    chunk_chosen = jax.jit(lambda params, cache, *a: mistral4.prefill_chosen(params, cfg, cache, *a, bs)[::6])
    decode_chosen = jax.jit(lambda params, cache, *a: mistral4.decode_chosen(params, cfg, cache, *a, bs)[::6])

    seqs = [np.asarray(s, np.int32) for s in sequences]
    ids = [f"reference-{i}" for i in range(len(seqs))]
    want, want_e = [], []
    for seq, n in zip(seqs, n_prompts):
        logits, chose = reference.full_logits(eng.params, jnp.asarray(seq), cfg, list(range(n - 1, len(seq) - 1)))
        want.append(np.asarray(logits))
        want_e.append(np.sort(np.asarray(chose), axis=-1))  # [L, T, k]
    if wrong == "e4m3":
        eng.params = _round_to_e4m3(eng.params)
    elif wrong:
        raise ValueError(f"no wrong-on-purpose reading named {wrong!r}")

    def same(mine, theirs):
        """Experts of the program (any order) and of the reference
        (sorted), both [L, N, k] -> [L, N] bool: the same four."""
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
            # (distance, margin, routing agrees) a position, sequence by sequence within a step
            "by_position": {k: [(round(d, 4), round(m, 4), int(ok)) for d, m, ok in rows[k]] for k in both}}


# ----------------------------------------------------------------------
# client side
# ----------------------------------------------------------------------
def kernel_roofline_pct(config, trace, before, after, peak):
    """The least time the chip could take for ONE call of the decode
    kernel, from what the window's decode steps attended on average
    (``kv_positions_attended`` over ``steps`` x layers calls), over the
    time a call took in the trace (the kernel's device seconds over its
    calls there).  A call, not a second, as
    ``serve_minicpm_sala.kernel_roofline_pct`` says.  The least bytes are
    the ATTENDED positions', never the whole pages copied, so the share
    cannot pass 100.  None where there is nothing to read."""
    if not trace or not trace.get("devices") or not peak:
        return None
    named = [n for n in trace["op_seconds"] if KERNEL.search(n)]
    kernel_s = sum(trace["op_seconds"][n] for n in named)
    calls = sum(trace.get("op_counts", {}).get(n, 0) for n in named)
    if kernel_s <= 0 or not calls or "kv_positions_attended" not in after:
        return None
    layers = config["num_hidden_layers"]
    steps = after["steps"] - before["steps"]
    if steps <= 0:
        return None
    work = flops_mla.mla_decode_work(
        config, after["kv_positions_attended"] - before["kv_positions_attended"],
        steps * after["max_batch_size"] * layers)
    least = flops.least_seconds(work, peak)["seconds"] / (steps * layers)
    return 100.0 * least / (kernel_s / calls)


def run(job) -> dict:
    if importlib.util.find_spec(FAMILY) is None:
        raise RuntimeError(f"this checkout's program has no {FAMILY}: it cannot run {job['config']['name']}")
    import ray_tpu

    cell, tr, config = job["cell"], job["cell"]["traffic"], job["config"]
    if tr["mode"] != "closed":
        raise ValueError("runners/serve_mistral_small_4.py drives closed loops only")
    seconds, seed = job["seconds"], job["seed"]
    handle, actor = deploy(job)
    t_deployed = time.time()

    def call(fn, *args):
        return actor.__ray_call__.remote(fn, *args)

    installed = ray_tpu.get(call(_rep_install), timeout=600)
    mla_sizes = ray_tpu.get(call(_rep_mla_sizes), timeout=120)
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
                      timeout=1800)
    device = ray_tpu.get(call(_rep_device), timeout=120)

    finished = [s for s in streams if s.done and not s.failed and t0 <= s.t_done < t_end]
    bad = [s for s in streams if s.failed]
    out_tokens = sum(1 for s in streams for t in s.token_t if t0 <= t < t_end)
    rate_tokens, rate_s = edge_rate(streams, t0, t_end)  # whole engine steps (serve.edge_rate)

    sizes, eng = job["sizes"], cell["engine"]
    before, after = stats["before"], stats["after"]
    # every row a program was given made its pairs in every layer:
    # max_batch_size rows a decode program, the padded chunk a prefill
    rows = after["max_batch_size"] * (after["steps"] - before["steps"]) + (
        after["prefill_bucket_tokens"] - before["prefill_bucket_tokens"])
    routed, held, computed = (after.get(k, 0) - before.get(k, 0)
                              for k in ("moe_pairs_routed", "moe_pairs_held", "moe_pairs"))
    slots = eng["pool_tokens"] + eng["block_size"]  # the scratch block beside the pool
    # a path none of whose positions routed as the reference did (the two prompts' last positions,
    # in one run of eight) has nothing to hold; positions_whose_routing_agrees_are_enough holds the count
    within = {k: ref[k] is None or ref[k] <= chk[limit] for k, limit in (
        ("margin", "logit_margin"), ("prefill", "logit_distance"), ("decode", "logit_distance"))}
    checks = {
        "preset_has_the_configuration's_sizes": all(
            installed[k] == sizes[k] for k in ("n_layer", "n_embd", "n_head", "n_positions", "vocab_rows")
        ) and sizes["dtype"] == installed["dtype"],
        "preset_has_the_configuration's_widths": all(
            mla_sizes["config"][key] == config[key] for key, _ in MLA_KEYS
        ) and all(mla_sizes["rope"][key] == config["rope_parameters"][key] for key, _ in ROPE_KEYS),
        "preset_holds_the_configuration's_share": mla_sizes["held"] == config["held"] and (
            mla_sizes["published"] == {k: config["published"][k] for k in ("n_routed_experts", "vocab_size")}),
        "engine_serves_max_model_len": mla_sizes["max_context"] == eng["max_model_len"],
        "cache_is_one_pool_of_latent_rows": mla_sizes["cache"] == {
            "k_pages": [sizes["n_layer"], slots, chk["cached_row_columns"]]},
        "finished_requests_returned_max_tokens": all(
            len(s.tokens) == s.req["max_tokens"] for s in finished + [a1, a2, b]),
        "same_prompt_twice_same_tokens": a1.tokens == a2.tokens,
        "kv_blocks_back_to_zero": after_drain["kv_blocks_in_use"] == 0,
        "returned_tokens_within_margin_of_float32_reference": within["margin"],
        "prefill_logits_within_distance_of_float32_reference": within["prefill"],
        "paged_decode_logits_within_distance_of_float32_reference": within["decode"],
        "chosen_experts_agree_with_float32_reference": ref["agree"] >= chk["expert_agreement_min"],
        "positions_whose_routing_agrees_are_enough": (
            ref["positions_agreeing"] >= chk["positions_agreeing_min"] * ref["positions"]),
        "no_compile_in_window": after["compiles"] == before["compiles"],
        "some_request_finished": len(finished) > 0,
        "every_lane_decoded_before_the_window": t0 - t_begin < tr["lead_in"]["at_most_s"],
        "every_row_made_its_pairs": rows > 0 and routed == (
            config["num_experts_per_tok"] * sizes["n_layer"] * rows),
        "every_held_pair_was_computed_and_no_other": 0 < held == computed,
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
                     "prefill_chunks", "prompt_tokens", "kv_positions_attended", "moe_experts_hit")},
        "running_before": before["running"], "waiting_middle": stats["middle"]["waiting"],
        "waiting_after": after["waiting"], "kv_blocks_middle": stats["middle"]["kv_blocks_in_use"],
        "kv_blocks_after": after["kv_blocks_in_use"],
        "moe_rows": rows, "moe_pairs_routed": routed, "moe_pairs_held": held, "moe_pairs": computed,
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
        values["mla_paged_decode_attention_roofline"] = kernel_roofline_pct(config, trace, before, after, peak)
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

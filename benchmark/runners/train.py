"""The train runner: one `JaxTrainer.fit()` whose loop steps a GPT-2 of
the configuration's sizes through the trainer's own sharding plane for
the length of the window.

The loop is a copy of ``chip_smoke.py``'s ``sharded_loop`` (kept here so
that later PRs may change the smoke and not the yardstick): the plan
comes from the trainer's ``ShardingConfig`` (``plan_from_context``), the
state is made on the mesh from the seed (``shard_init``), the step is
``plan.jit_train_step(gpt2.make_train_step(...))``.  One chip and a 2x2
mesh differ only in the cell's ``mesh`` and ``mesh_shape``.

The driver side imports no JAX; `worker_loop` runs in the worker that
holds the chips.
"""

from __future__ import annotations

import math
import shutil
import statistics
import tempfile
import time

ANNOTATIONS = ("step", "fetch")


def worker_loop(job):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import ray_tpu.train.sharding as sharding
    from benchmark import reference
    from benchmark.runners import common
    from ray_tpu import train
    from ray_tpu.models import gpt2

    jax.block_until_ready(jnp.zeros((8, 128), jnp.float32) + 1)
    t_owner_ready = time.time()
    marks = {"worker_entered": t_owner_ready}  # set-up's timeline, seconds since init()
    common.count_compiles()

    sizes, run, seed = job["sizes"], job["job"], job["seed"]
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[sizes["dtype"]]
    cfg = gpt2.GPT2Config(
        vocab_size=sizes["vocab_rows"], n_layer=sizes["n_layer"], n_head=sizes["n_head"],
        d_model=sizes["n_embd"], max_seq_len=sizes["n_positions"], dtype=dtype,
        remat=run["remat"],
    )
    B, T = run["batch"], run["seq"]
    plan = sharding.plan_from_context()
    chips = math.prod(plan.mesh.shape.values())

    def init(rng):
        return gpt2.GPT2(cfg).init(rng, jnp.zeros((2, min(T, 128)), jnp.int32))["params"]

    opt = gpt2.make_adamw(run["lr"])
    params, opt_state = plan.shard_init(init, opt, rng=jax.random.PRNGKey(seed % (2**31 - 1)))
    marks["state_on_device"] = time.time()
    toks = np.random.default_rng(seed).integers(0, sizes["vocab_size"], (B, T + 1), dtype=np.int32)
    tokens, targets = toks[:, :-1], toks[:, 1:]
    ref_tok = reference.token_losses(params, tokens, targets, cfg.n_layer, cfg.n_head)
    ref_loss = float(ref_tok.mean(dtype=np.float64))
    # the program's forward (bf16, its attention kernel) on the first
    # sequences: at initial parameters the MEAN loss is ln V whatever
    # attention does, one token's loss is not
    token_gap = None
    if run.get("token_check"):
        n = run["token_check"]["sequences"]
        with jax.set_mesh(plan.mesh):
            lg = jax.jit(lambda p, t: gpt2.GPT2(cfg).apply({"params": p}, t))(
                params, jax.device_put(tokens[:n], plan.data_sharding()))
        gap = np.abs(np.asarray(reference.cross_entropy(lg, jnp.asarray(targets[:n]))) - ref_tok[:n])
        token_gap = {"max": float(gap.max()), "mean": float(gap.mean())}
        del lg
    marks["reference_done"] = time.time()
    step = plan.jit_train_step(gpt2.make_train_step(cfg, opt), params, opt_state)

    losses = []
    for _ in range(run["warmup_steps"]):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        losses.append(float(jax.block_until_ready(loss)))
        marks[f"warmup_step_{len(losses)}"] = time.time()

    tracer = common.Tracer() if job["trace"] else None
    trace_from, trace_for = run["trace_from_s"], run["trace_seconds"]
    compiles_before = common.count_compiles()
    step_s = []
    t_start = t_prev = time.time()
    while t_prev - t_start < job["seconds"]:
        if tracer and tracer.t_start is None and t_prev - t_start >= trace_from:
            tracer.start()
        with jax.profiler.TraceAnnotation("step"):
            params, opt_state, loss = step(params, opt_state, tokens, targets)
            jax.block_until_ready(loss)
        with jax.profiler.TraceAnnotation("fetch"):
            losses.append(float(loss))
        now = time.time()
        step_s.append(now - t_prev)
        t_prev = now
        if tracer and tracer.on and now - tracer.t_start >= trace_for:
            tracer.stop()
    if tracer and tracer.on:
        tracer.stop()
    elapsed = t_prev - t_start
    compiles_in_window = common.count_compiles() - compiles_before

    tokens_per_s_chip = len(step_s) * B * T / elapsed / chips
    report = {
        "device": common.device_facts(),
        "t_owner_ready": t_owner_ready, "t_window_start": t_start,
        "timeline_s": {k: round(v - job["t_init"], 2) for k, v in marks.items()},
        "mesh": dict(plan.mesh.shape), "chips": chips,
        "params": int(sum(x.size for x in jax.tree_util.tree_leaves(params))),
        "steps": len(step_s), "elapsed_s": elapsed,
        "tokens_per_s_chip": tokens_per_s_chip,
        "step_ms": 1000 * statistics.median(step_s),
        "first_loss": losses[0], "ref_loss": ref_loss, "last_loss": losses[-1],
        "token_gap": token_gap,
        "losses_finite": all(math.isfinite(x) for x in losses),
        "compiles_in_window": compiles_in_window,
    }
    if tracer and tracer.t_stop is not None:
        report["trace"] = tracer.facts(ANNOTATIONS, job.get("keep_trace"))
    train.report(report)


def run(job) -> dict:
    """Driver side: fit, then turn the worker's report into the result
    the harness prints."""
    from benchmark import flops, spec
    from ray_tpu.air.config import RunConfig, ScalingConfig
    from ray_tpu.train.jax import JaxTrainer
    from ray_tpu.train.sharding import ShardingConfig

    run_ = job["job"]
    rules = run_.get("partition_rules")
    sharding_config = ShardingConfig(
        mesh=tuple(run_["mesh"]), mesh_shape=run_["mesh_shape"],
        partition_rules=None if rules is None else [(rx, tuple(spec)) for rx, spec in rules],
    )
    storage = tempfile.mkdtemp(prefix="bench_train_")
    try:
        result = JaxTrainer(
            worker_loop, train_loop_config=job,
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
            run_config=RunConfig(storage_path=storage),
            sharding_config=sharding_config,
        ).fit()
    finally:
        shutil.rmtree(storage, ignore_errors=True)
    if result.error is not None:
        raise result.error
    r = result.metrics
    print("[train] " + ", ".join(f"{k}={r[k]}" for k in (
        "mesh", "params", "steps", "elapsed_s", "step_ms", "first_loss", "ref_loss",
        "token_gap", "last_loss", "compiles_in_window", "timeline_s")), flush=True)

    loss_err = abs(r["first_loss"] - r["ref_loss"])
    checks = {
        "first_loss_matches_reference": loss_err < run_["ref_tol"],
        "losses_finite": r["losses_finite"],
        "loss_fell": r["last_loss"] < r["first_loss"],
        "no_compile_in_window": r["compiles_in_window"] == 0,
        "mesh_is_the_cell's": r["mesh"] == run_["mesh_shape"],
    }
    if run_.get("token_check"):
        checks["token_losses_match_reference"] = r["token_gap"]["max"] < run_["token_check"]["tol"]
    values = {
        "train_tokens_per_s_chip": r["tokens_per_s_chip"],
        "t_window_start": r["t_window_start"],
        "chip_owner_ready_s": r["t_owner_ready"] - job["t_init"],
        "step_ms": r["step_ms"],
        "first_loss_err": loss_err,
    }
    peak = spec.load_peaks().get(r["device"]["kind"])
    if peak:
        # from the median step, not the window's rate: in a traced run the
        # profiler's start and stop stand inside the window
        per_step = flops.train_flops_per_token(job["sizes"], run_["seq"]) * run_["batch"] * run_["seq"]
        values["mfu_pct"] = 100.0 * per_step / (r["step_ms"] / 1000) / r["chips"] / peak["bf16_flops_per_s"]
    return {
        "checks": checks, "attempted": r["steps"],
        "failed": 0 if r["losses_finite"] else r["steps"],
        "values": values, "device": r["device"], "trace": r.get("trace"), "stats": None,
    }

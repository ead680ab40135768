#!/usr/bin/env python
"""chip_smoke.py: the quickest proof that the system still starts on the chip.

Drives the two paths users come for through the entry points they would
call, once each, on one real TPU chip:

  runtime  ray_tpu.init() with nothing said about TPUs; the raylet must
           detect the chip and advertise a TPU resource.  A num_tpus=1
           task owns the chip while a num_tpus=0 task, started from
           inside it, runs on the CPU backend.
  train    JaxTrainer.fit() on GPT-2-medium at full width (bf16, B=8,
           T=1024, no remat): finite falling loss, the Pallas kernel in
           the lowered step, first-step loss against a plain float32
           reference with einsum attention, in the same worker.
  serve    serve.run(llm.build_app(...)) on the same model: eight greedy
           requests (four by handle, three over HTTP, one streamed).

With ``--chips 4`` it runs the sharded path and what it is compared with,
and no other phase: JaxTrainer with a 2x2 batch x model mesh over four
chips held by one worker, against the one-device layout.

This process never imports JAX: a parent that touched JAX would hold the
chip its workers need.  Device facts come back from the worker that holds
the lease.  Any phase that fails makes the exit code non-zero, and so does
a process of the cluster that is still there after shutdown; the last
line of stdout is the result object and is printed only when every phase
passed on a TPU.

The phase functions take their sizes as a dict so that a test can call
them at a tiny size on the CPU; as a command the script runs the real
sizes below and fails without a chip.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
import traceback
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))

import ray_tpu  # noqa: E402  (fails, as it must, where the repo is absent)
from ray_tpu.util.compile_cache import count_cache_entries, place_compile_cache  # noqa: E402

TRAIN = {
    "model": "medium", "dtype": "bfloat16", "batch": 8, "seq": 1024,
    "warmup": 2, "steps": 5, "lr": 3e-4,
    # |bf16 step loss - float32 reference loss| at the initial parameters
    "ref_tol": 0.05,
}
SERVE = {
    "model": "medium", "dtype": "bfloat16", "max_batch_size": 8,
    "block_size": 16, "pool_tokens": 8 * 1024,
    "prompt_len": (16, 512), "max_tokens": (32, 128), "http_port": 18431,
}
SHARDED = {
    "model": "medium", "dtype": "bfloat16", "batch": 8, "seq": 1024,
    "steps": 3, "lr": 3e-4, "mesh_shape": {"batch": 2, "model": 2},
    # scripts/sharded_train_smoke.py holds float32 on the CPU to 1e-4; in
    # bf16 the two layouts round differently, so the bound follows the
    # dtype (see check_sharded)
    "parity_tol": {"float32": 1e-4, "bfloat16": 2e-2},
}


class SmokeFailure(AssertionError):
    pass


def require(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


_PIDS = set()  # every process of the cluster that a phase reported


def _pids_in(facts):
    if isinstance(facts, dict):
        for key, value in facts.items():
            if key == "pid":
                yield value
            else:
                yield from _pids_in(value)
    elif isinstance(facts, (list, tuple)):
        for value in facts:
            yield from _pids_in(value)


def say(phase, **facts):
    _PIDS.update(_pids_in(facts))  # for the check after shutdown
    print(f"[{phase}] " + json.dumps(facts, sort_keys=True, default=str), flush=True)


# ----------------------------------------------------------------------
# worker-side bodies (the only code here that imports JAX)
# ----------------------------------------------------------------------
def _jax_probe():
    """Initialise JAX in this process and run a small jitted matmul."""
    import jax
    import jax.numpy as jnp

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    y = jax.block_until_ready(jax.jit(lambda a: a @ a)(x))
    dev = jax.devices()
    return {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev), "pid": os.getpid(),
        "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS"),
        "matmul_00": float(y[0, 0]),
    }


@ray_tpu.remote(num_tpus=0)
def cpu_probe():
    return _jax_probe()


@ray_tpu.remote(num_tpus=1)
def chip_probe():
    """Own the chip, then run a num_tpus=0 task while owning it."""
    mine = _jax_probe()
    other = ray_tpu.get(cpu_probe.remote())
    return mine, other


def _model_cfg(config):
    import jax.numpy as jnp

    from ray_tpu.models import gpt2

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config["dtype"]]
    cfg = getattr(gpt2.GPT2Config, config["model"])(dtype=dtype, remat=False)
    require(config["seq"] <= cfg.max_seq_len, "seq longer than the model's context")
    return cfg


def _reference_loss(params, cfg, tokens, targets):
    """Next-token loss of `params` through the benchmark's plain float32
    forward with einsum attention: independent of the Flax module, of
    the compute dtype and of the Pallas kernel."""
    from benchmark.reference import token_losses

    return float(token_losses(params, tokens, targets, cfg.n_layer, cfg.n_head).mean())


def train_loop(config):
    """train_loop_per_worker of the train phase: the trainer's
    ShardingConfig (a one-device mesh) through the sharding plan, one
    repeated batch."""
    import statistics

    import jax
    import numpy as np

    import ray_tpu.train.sharding as sharding
    from ray_tpu import train
    from ray_tpu.models import gpt2

    cfg = _model_cfg(config)
    B, T = config["batch"], config["seq"]
    dev = jax.devices()
    plan = sharding.plan_from_context()
    opt = gpt2.make_adamw(lr=config["lr"])
    params, opt_state = plan.shard_init(
        lambda rng: gpt2.init_params(cfg, rng), opt, rng=jax.random.PRNGKey(config["seed"])
    )
    toks = np.random.default_rng(config["seed"]).integers(
        0, cfg.vocab_size, (B, T + 1), dtype=np.int32
    )
    tokens, targets = toks[:, :-1], toks[:, 1:]
    ref_loss = _reference_loss(params, cfg, tokens, targets)
    step = plan.jit_train_step(gpt2.make_train_step(cfg, opt), params, opt_state)
    kernel_in_step = "tpu_custom_call" in step.lower(
        params, opt_state, tokens, targets
    ).as_text()

    losses, barrier_s, fetch_s = [], [], []
    for _ in range(config["warmup"] + config["steps"]):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        jax.block_until_ready(loss)
        t1 = time.perf_counter()
        losses.append(float(jax.device_get(loss)))
        t2 = time.perf_counter()
        barrier_s.append(t1 - t0)
        fetch_s.append(t2 - t1)
    timed = barrier_s[config["warmup"]:]
    step_s = statistics.median(timed)
    stats = dev[0].memory_stats() or {}
    train.report({
        "platform": dev[0].platform, "kind": dev[0].device_kind, "count": len(dev),
        "pid": os.getpid(), "params": gpt2.num_params(params),
        "vocab_size": cfg.vocab_size, "n_layer": cfg.n_layer, "d_model": cfg.d_model,
        "losses": losses, "ref_loss_f32": ref_loss,
        "kernel_in_step": kernel_in_step,
        # first call = trace + compile + first run; later calls are steps
        "compile_s": barrier_s[0], "step_s": step_s, "step_s_all": timed,
        "tokens_per_s": B * T / step_s,
        # were block_until_ready no barrier, the scalar fetch after it
        # would have to wait for the step and show here
        "fetch_after_barrier_s": statistics.median(fetch_s[config["warmup"]:]),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_limit": stats.get("bytes_limit"),
    })


def sharded_loop(config):
    """train_loop_per_worker of --chips 4: the trainer's ShardingConfig
    (batch x model) on every device of this worker, then the one-device
    layout on the same seed and data."""
    import gc

    import jax
    import numpy as np

    import ray_tpu.train.sharding as sharding
    from ray_tpu import train
    from ray_tpu.models import gpt2

    cfg = _model_cfg(config)
    B, T, steps = config["batch"], config["seq"], config["steps"]
    dev = jax.devices()
    data = np.random.default_rng(config["seed"]).integers(
        0, cfg.vocab_size, (steps, B, T + 1), dtype=np.int32
    )

    def bytes_by_device(tree):
        out = {d.id: 0 for d in dev}
        for leaf in jax.tree_util.tree_leaves(tree):
            for shard in leaf.addressable_shards:
                out[shard.device.id] += shard.data.nbytes
        return out

    def run(plan, inspect):
        opt = gpt2.make_adamw(config["lr"])
        params, opt_state = plan.shard_init(
            lambda rng: gpt2.init_params(cfg, rng), opt, rng=jax.random.PRNGKey(config["seed"])
        )
        facts = {
            "mesh": dict(plan.mesh.shape),
            "param_bytes": bytes_by_device(params),
            "opt_bytes": bytes_by_device(opt_state),
        }
        step = plan.jit_train_step(gpt2.make_train_step(cfg, opt), params, opt_state)
        if inspect:
            # the partitioner puts the collectives in when it compiles;
            # the step below then finds this compile in the cache
            text = step.lower(
                params, opt_state, data[0][:, :-1], data[0][:, 1:]
            ).compile().as_text()
            facts["collectives"] = {
                op: text.count(op)
                for op in ("all-reduce", "all-gather", "reduce-scatter",
                           "collective-permute", "all-to-all")
            }
            facts["kernel_in_step"] = "tpu_custom_call" in text
        losses, step_s = [], []
        for toks in data:
            t0 = time.perf_counter()
            params, opt_state, loss = step(params, opt_state, toks[:, :-1], toks[:, 1:])
            losses.append(float(jax.block_until_ready(loss)))
            step_s.append(time.perf_counter() - t0)
        facts["losses"], facts["step_s"] = losses, step_s
        facts["bytes_in_use"] = {
            d.id: (d.memory_stats() or {}).get("bytes_in_use") for d in dev
        }
        return facts

    sharded = run(sharding.plan_from_context(), inspect=True)
    gc.collect()
    one = run(sharding.build_plan(
        sharding.ShardingConfig(
            mesh=("batch",), mesh_shape={"batch": 1}, partition_rules=[(r".*", ())],
        ),
        devices=dev[:1],
    ), inspect=False)
    train.report({
        "platform": dev[0].platform, "kind": dev[0].device_kind, "count": len(dev),
        "pid": os.getpid(), "sharded": sharded, "one_device": one,
        "parity_err": max(abs(a - b) for a, b in zip(sharded["losses"], one["losses"])),
    })


# ----------------------------------------------------------------------
# phases (driver side, no JAX)
# ----------------------------------------------------------------------
def _fit(loop, config, scaling, **trainer_kw):
    from ray_tpu.air.config import RunConfig
    from ray_tpu.train.jax import JaxTrainer

    storage = tempfile.mkdtemp(prefix="chip_smoke_train_")
    try:
        result = JaxTrainer(
            loop, train_loop_config=config, scaling_config=scaling,
            run_config=RunConfig(storage_path=storage), **trainer_kw,
        ).fit()
    finally:
        shutil.rmtree(storage, ignore_errors=True)
    if result.error is not None:
        raise result.error
    return result.metrics


def phase_runtime():
    """One cluster for the whole run: the chip changes hands between
    leases of the same raylet."""
    from ray_tpu._native.arena import load_library
    from ray_tpu._private.accelerators.tpu import TPUAcceleratorManager
    from ray_tpu._private.worker import get_global_worker

    load_library()  # built from shm_arena.cpp; a failed build raises here
    ray_tpu.init()
    facts = {
        "cluster_resources": ray_tpu.cluster_resources(),
        "detected": TPUAcceleratorManager._detect(),
        "object_store": (
            "native_arena" if get_global_worker().store.arena is not None else "file"
        ),
    }
    say("runtime", **facts)
    return facts


def check_runtime(facts):
    require(facts["cluster_resources"].get("TPU", 0) >= 1,
            "no TPU resource: the raylet detected no chip")
    require(facts["object_store"] == "native_arena", "object store is not the native arena")


def phase_leases():
    owner, other = ray_tpu.get(chip_probe.remote(), timeout=600)
    facts = {"num_tpus_1": owner, "num_tpus_0_while_chip_held": other}
    say("runtime", **facts)
    return facts


def check_leases(facts):
    owner, other = facts["num_tpus_1"], facts["num_tpus_0_while_chip_held"]
    require(owner["platform"] == "tpu", f"the num_tpus=1 task ran on {owner['platform']}")
    require(other["platform"] == "cpu", f"the num_tpus=0 task ran on {other['platform']}")
    require(owner["pid"] != other["pid"], "both tasks ran in one process")


def phase_train(config):
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train.sharding import ShardingConfig

    facts = _fit(
        train_loop, config, ScalingConfig(num_workers=1, use_tpu=True),
        sharding_config=ShardingConfig(
            mesh=("batch",), mesh_shape={"batch": 1}, partition_rules=[(r".*", ())],
        ),
    )
    say("train", **facts)
    return facts


def check_train(facts, config):
    import math

    losses = facts["losses"]
    require(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
    require(losses[-1] < losses[0], f"loss did not fall on a repeated batch: {losses}")
    err = abs(losses[0] - facts["ref_loss_f32"])
    require(err < config["ref_tol"],
            f"first-step loss {losses[0]} vs float32 reference {facts['ref_loss_f32']}: "
            f"|diff| {err} >= {config['ref_tol']}")
    say("train", first_step_vs_reference=err, tolerance=config["ref_tol"])


def check_train_on_chip(facts):
    require(facts["platform"] == "tpu", f"the trainer's worker ran on {facts['platform']}")
    require(facts["kernel_in_step"],
            "no tpu_custom_call in the lowered step: the Pallas kernel was not chosen")
    require(facts["fetch_after_barrier_s"] < 0.1 * facts["step_s"],
            "block_until_ready returned before the step was done: "
            f"{facts['fetch_after_barrier_s']}s fetch after a {facts['step_s']}s step")


def _make_requests(config, seed, vocab_size):
    """Six prompts from the seed; eight requests over them."""
    import random

    rng = random.Random(seed)
    lo, hi = config["prompt_len"]
    mlo, mhi = config["max_tokens"]
    prompts = [
        {"prompt": [rng.randrange(vocab_size) for _ in range(rng.randint(lo, hi))],
         "max_tokens": rng.randint(mlo, mhi)}
        for _ in range(6)
    ]
    a, b, c, d, e, f = prompts
    return {"handle": [a, a, b, c], "http": [d, e, f], "stream": b}


def _session_processes():
    """Every process of this session with its JAX_PLATFORMS and whether
    libtpu is mapped (i.e. it initialised the TPU backend)."""
    from ray_tpu._private.node import session_pids
    from ray_tpu._private.worker import get_global_worker

    out = []
    for pid in session_pids(get_global_worker().session_info["session_dir"]):
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                head = b"head_main" in f.read()
            with open(f"/proc/{pid}/environ", "rb") as f:
                env = dict(kv.split(b"=", 1) for kv in f.read().split(b"\0") if b"=" in kv)
            with open(f"/proc/{pid}/maps") as f:
                libtpu = "libtpu" in f.read()
        except OSError:
            continue
        out.append({"pid": pid, "role": "head" if head else "worker",
                    "libtpu_mapped": libtpu,
                    "JAX_PLATFORMS": env.get(b"JAX_PLATFORMS", b"").decode() or None})
    return out


def phase_serve(config, seed):
    from ray_tpu import serve
    from ray_tpu.serve import llm

    llm_config = llm.LLMConfig(
        model=config["model"], dtype=config["dtype"],
        max_batch_size=config["max_batch_size"], block_size=config["block_size"],
        # the pool holds pool_tokens slots plus the reserved scratch block 0
        num_blocks=config["pool_tokens"] // config["block_size"] + 1,
        name="chip_smoke_llm",
    )
    reqs = _make_requests(config, seed, llm_config.model_config().vocab_size)
    port = config["http_port"]
    t_start = time.perf_counter()
    try:
        handle = serve.run(
            llm.build_app(llm_config, num_replicas=1, route_prefix="/llm"),
            name="chip_smoke_llm_app", http_port=port,
        )
        t_ready = time.perf_counter()
        pending = [handle.remote(r) for r in reqs["handle"]]
        by_handle = [p.result(timeout=900) for p in pending]
        t_handle = time.perf_counter()

        by_http = []
        for r in reqs["http"]:
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/llm", data=json.dumps(r).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=900) as resp:
                require(resp.status == 200, f"HTTP {resp.status}")
                by_http.append(json.loads(resp.read()))
        t_http = time.perf_counter()

        events = list(handle.options(stream=True).generate.remote(reqs["stream"]))
        streamed = [e["token"] for e in events if "token" in e]
        t_stream = time.perf_counter()

        stats = handle.stats.remote().result(timeout=60)
        deadline = time.monotonic() + 30
        while stats["kv_blocks_in_use"] and time.monotonic() < deadline:
            time.sleep(0.2)
            stats = handle.stats.remote().result(timeout=60)
        processes = _session_processes()
    finally:
        serve.shutdown()
    n_tokens = sum(r["num_tokens"] for r in by_handle + by_http) + len(streamed)
    facts = {
        "replica": {k: stats[k] for k in (
            "platform", "device_kind", "pid", "steps", "total_tokens",
            "kv_blocks_in_use", "kv_blocks_total")},
        "prompt_lens": {k: [len(r["prompt"]) for r in (v if isinstance(v, list) else [v])]
                        for k, v in reqs.items()},
        "asked": {"handle": [r["max_tokens"] for r in reqs["handle"]],
                  "http": [r["max_tokens"] for r in reqs["http"]],
                  "stream": reqs["stream"]["max_tokens"]},
        "returned": {"handle": [r["num_tokens"] for r in by_handle],
                     "http": [r["num_tokens"] for r in by_http],
                     "stream": len(streamed)},
        "same_prompt_twice_identical": by_handle[0]["tokens"] == by_handle[1]["tokens"],
        "stream_equals_one_shot": streamed == by_handle[2]["tokens"],
        "stream_done_event": bool(events and events[-1].get("done")),
        "deploy_s": t_ready - t_start, "handle_s": t_handle - t_ready,
        "http_s": t_http - t_handle, "stream_s": t_stream - t_http,
        "tokens_returned": n_tokens,
        "processes": processes,
    }
    say("serve", **facts)
    return facts


def check_serve(facts):
    require(facts["returned"] == facts["asked"],
            f"requests did not return their max_tokens: {facts['returned']} != {facts['asked']}")
    require(facts["same_prompt_twice_identical"], "the same prompt gave different tokens")
    require(facts["stream_equals_one_shot"], "streamed tokens differ from the one-shot answer")
    require(facts["stream_done_event"], "the stream ended without its done event")
    require(facts["replica"]["kv_blocks_in_use"] == 0, "KV blocks leaked")


def check_serve_on_chip(facts):
    replica = facts["replica"]
    require(replica["platform"] == "tpu", f"the replica ran on {replica['platform']}")
    owners = [p for p in facts["processes"] if p["libtpu_mapped"]]
    require([p["pid"] for p in owners] == [replica["pid"]],
            f"processes with the TPU backend: {owners}; the replica is pid {replica['pid']}")


def phase_sharded(config):
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train.sharding import ShardingConfig

    facts = _fit(
        sharded_loop, config, ScalingConfig(num_workers=1, use_tpu=True),
        sharding_config=ShardingConfig(
            mesh=("batch", "model"), mesh_shape=config["mesh_shape"]
        ),
    )
    say("sharded", **facts)
    return facts


def check_sharded(facts, config):
    sharded = facts["sharded"]
    n = 1
    for size in config["mesh_shape"].values():
        n *= size
    require(facts["count"] == n, f"worker sees {facts['count']} devices, mesh needs {n}")
    require(sharded["mesh"] == config["mesh_shape"], f"mesh is {sharded['mesh']}")
    for name in ("param_bytes", "opt_bytes"):
        require(len(sharded[name]) == n and all(sharded[name].values()),
                f"{name} per device: {sharded[name]}")
    # the model axis splits the weights: no device may hold them all
    require(max(sharded["param_bytes"].values()) < max(facts["one_device"]["param_bytes"].values()),
            "a device of the mesh holds every parameter")
    require(any(sharded["collectives"].values()), "no collective in the compiled sharded step")
    tol = config["parity_tol"][config["dtype"]]
    require(facts["parity_err"] < tol,
            f"loss parity with the one-device layout: {facts['parity_err']} >= {tol}")
    say("sharded", parity_err=facts["parity_err"], tolerance=tol)


def _stop_cluster():
    """Shut the cluster down and say which of its processes are still
    there afterwards (none may be): whatever still belongs to the session,
    and every process a phase reported, in whatever state.  A chip owner
    that was killed is a zombie with threads for seconds while the kernel
    takes its device memory apart; only its pid still names it then.
    Worker logs are all that says why a phase failed on a machine that is
    thrown away: copy them to where the chip tool brings them back."""
    from ray_tpu._private.node import session_pids
    from ray_tpu._private.worker import get_global_worker

    if not ray_tpu.is_initialized():
        return []
    session = get_global_worker().session_info["session_dir"]
    ray_tpu.shutdown()
    try:
        dest = os.path.join(REPO, "chiprun_out", "chip_smoke_logs")
        shutil.rmtree(dest, ignore_errors=True)
        shutil.copytree(os.path.join(session, "logs"), dest)
    except Exception:  # noqa: BLE001 - never the reason a run fails
        traceback.print_exc()
    dying = {pid for pid in _PIDS if os.path.exists(f"/proc/{pid}")}
    return sorted(dying | set(session_pids(session)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache = place_compile_cache(REPO)
    say("cache", dir=cache, entries_before=count_cache_entries(cache))
    t0 = time.perf_counter()
    device = None
    try:
        check_runtime(phase_runtime())
        if args.chips == 4:
            config = dict(SHARDED, seed=args.seed)
            facts = phase_sharded(config)
            require(facts["platform"] == "tpu", f"the worker ran on {facts['platform']}")
            check_sharded(facts, config)
        else:
            check_leases(phase_leases())
            config = dict(TRAIN, seed=args.seed)
            facts = phase_train(config)
            check_train_on_chip(facts)
            check_train(facts, config)
            serve_facts = phase_serve(SERVE, args.seed)
            check_serve_on_chip(serve_facts)
            check_serve(serve_facts)
            require(serve_facts["replica"]["device_kind"] == facts["kind"],
                    "trainer and replica report different devices")
        require(facts["count"] == args.chips,
                f"{facts['count']} devices where --chips {args.chips} was asked")
        device = {"platform": facts["platform"], "kind": facts["kind"], "count": facts["count"]}
    except Exception:  # noqa: BLE001 - the boundary: report, exit non-zero
        traceback.print_exc()
    finally:
        t_stop = time.perf_counter()
        left = _stop_cluster()
        say("shutdown", left_running=left, shutdown_s=time.perf_counter() - t_stop)
        say("cache", dir=cache, entries_after=count_cache_entries(cache),
            wall_s=time.perf_counter() - t0)
    if device is None or left:
        print("[chip_smoke] FAILED", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

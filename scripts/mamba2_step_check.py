"""The Mamba-2 decode kernel alone on the chip: device ms a call.

    python scripts/mamba2_step_check.py [--repo DIR] [--label parent]
        [--families nemotron,granite] [--running 64] [--iters 20]

For each served Mamba-2 family (a head's sizes from `benchmark/configs/`,
the lanes from its cell in `benchmark/workloads/`),
`ray_tpu.ops.pallas_mamba2.mamba2_decode_step` in a jit of its own over
one layer's lane-state array, the states donated and handed from call to
call as the engine hands them.  `--running` lanes of the cell's run (all
of them by default), the idle ones spread among them.  Device ms a call
is the `mamba2_decode_step tpu_custom_call` events of a profiler trace
of `--iters` calls (the benchmark's own reduction) and the share of the
roof is the least time of the call (`flops_ssm.ssm_step_work`'s count
for the running lanes: a state in and out once at the chip's HBM rate)
over it: what `mamba2_decode_step_roofline.*` reads in a cell's traced
window.  One call's outputs (x in float32, so that y is not rounded)
and states are held against `ops.mamba2.ssm_step` on the same chip, the largest distance by (lane,
head).

`--repo` names another checkout to take `ray_tpu` from, so that parent
and change are timed in one call.  Prints a table, then one JSON object,
and writes it to `chiprun_out/mamba2_step_check.<label>.json`.  Needs the
TPU: in interpret mode a time says nothing.  No benchmark cell and no
test runs this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# family: its configuration, its cell, and the configuration's keys of heads, head size, state size, groups
FAMILIES = {
    "nemotron": ("nemotron-3-nano", "nemotron-3-nano.serve.reason-backlog",
                 ("mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups")),
    "granite": ("granite-4.0-h-small", "granite-4.0-h-small.serve.rag-backlog",
                ("mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups")),
}


def family_shape(name: str) -> dict:
    """{"lanes", "H", "P", "N", "G"} of a family, from the benchmark's files."""
    config, cell, keys = FAMILIES[name]
    with open(os.path.join(ROOT, "benchmark", "configs", f"{config}.json")) as fh:
        cfg = json.load(fh)
    with open(os.path.join(ROOT, "benchmark", "workloads", f"{cell}.json")) as fh:
        work = json.load(fh)
    return {"lanes": work["engine"]["max_batch_size"], **dict(zip("HPNG", (cfg[k] for k in keys)))}


def lane_inputs(shape: dict, running: int, seed: int):
    """(x, dt, A, B, C, D, state, active) of one decode step at `shape`:
    x in bf16 as the models hand it, the rest float32; `running` of the
    lanes active, evenly spread."""
    import jax.numpy as jnp
    import numpy as np

    lanes, H, P, N, G = (shape[k] for k in ("lanes", "H", "P", "N", "G"))
    rng = np.random.default_rng(seed)

    def f(*s):
        return jnp.asarray(rng.standard_normal(s, dtype=np.float32))

    dt = jnp.asarray(np.log1p(np.exp(rng.standard_normal((lanes, H), dtype=np.float32))))
    A = -jnp.asarray(rng.uniform(1.0, 16.0, H).astype(np.float32))
    active = np.zeros(lanes, bool)
    active[np.linspace(0, lanes - 1, running).round().astype(int)] = True
    return (f(lanes, H, P).astype(jnp.bfloat16), dt, A, f(lanes, G, N), f(lanes, G, N), f(H),
            f(lanes, H, P, N), jnp.asarray(active))


def kernel_ms(step, args, iters: int) -> tuple[float, float]:
    """(device ms a call of the kernel, of every other operation of the
    jit: the wrapper's transposes and sort) from a trace of `iters`
    calls that hand the donated states on."""
    import jax

    from benchmark import trace_reduce

    run = jax.jit(step, donate_argnums=(6,))
    *head, state, active = args
    _, state = run(*head, state + 0.0, active)  # compiles; the caller keeps its states
    jax.block_until_ready(state)
    with tempfile.TemporaryDirectory() as logdir:
        with jax.profiler.trace(logdir):
            for _ in range(iters):
                _, state = run(*head, state, active)
            jax.block_until_ready(state)
        planes = trace_reduce.load(trace_reduce.find_xplane(logdir))
    ops = trace_reduce.device_ops(planes)
    kernel = other = 0.0
    for name, _, dur in ops[min(ops)]:
        if trace_reduce.family(name).startswith("mamba2_decode_step"):
            kernel += dur / 1e6 / iters
        else:
            other += dur / 1e6 / iters
    return kernel, other


def distances(got, want, active) -> dict:
    """The largest |got - want| of a running lane's outputs and states,
    over (lane, head) maxima, beside the largest value compared."""
    import numpy as np

    on = np.asarray(active)
    out = {}
    for name, a, b in zip(("y", "state"), got, want):
        a, b = np.asarray(a, np.float32)[on], np.asarray(b, np.float32)[on]
        by = np.abs(a - b).reshape(*a.shape[:2], -1).max(axis=-1)  # [lane, head]
        out[name] = {"max_abs": float(by.max(initial=0.0)), "ref_max_abs": float(np.abs(b).max(initial=0.0))}
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", default=ROOT)
    ap.add_argument("--label", default="change")
    ap.add_argument("--families", default=",".join(FAMILIES))
    ap.add_argument("--running", type=int, default=0, help="running lanes; 0: every lane of the cell")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.repo))
    sys.path.insert(1, ROOT)  # benchmark/ is this checkout's

    import jax

    from benchmark import flops, flops_ssm
    from ray_tpu.ops import mamba2
    from ray_tpu.ops.pallas_mamba2 import mamba2_decode_step

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": f"needs a TPU, found {dev.platform}"}))
        return 1
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as fh:
        peak = json.load(fh)[dev.device_kind]
    result = {"label": args.label, "repo": os.path.abspath(args.repo), "iters": args.iters,
              "device": {"platform": dev.platform, "kind": dev.device_kind}, "rows": []}
    print(f"{'family':9}{'lanes':>6}{'running':>8}{'H':>5}{'G':>3}{'P':>4}{'N':>5}{'state MB':>9}"
          f"{'ms':>9}{'roof ms':>9}{'roof %':>8}{'other ms':>9}{'|dy|':>10}{'|dS|':>10}")
    for fam in args.families.split(","):
        shape = family_shape(fam)
        running = min(args.running or shape["lanes"], shape["lanes"])
        inputs = lane_inputs(shape, running, args.seed)
        ms, other = kernel_ms(mamba2_decode_step, inputs, args.iters)
        # the trace's calls moved the states on: the distance is of one call from the same start,
        # x in float32 so that y comes back unrounded
        fine = (inputs[0].astype("float32"), *inputs[1:])
        dist = distances(mamba2_decode_step(*fine), jax.jit(mamba2.ssm_step)(*fine), inputs[-1])
        sizes = {"mamba_num_heads": shape["H"], "mamba_head_dim": shape["P"],
                 "ssm_state_size": shape["N"], "n_groups": shape["G"]}
        least = flops.least_seconds(flops_ssm.ssm_step_work(sizes, running), peak)
        row = {"family": fam, **shape, "running": running, "ms": ms, "other_ms": other,
               "roof_ms": least["seconds"] * 1e3, "roof_bound": least["bound"],
               "roof_pct": 100 * least["seconds"] * 1e3 / ms, "distance": dist}
        result["rows"].append(row)
        state_mb = shape["H"] * shape["P"] * shape["N"] * 4 / 2**20
        print(f"{fam:9}{shape['lanes']:6d}{running:8d}{shape['H']:5d}{shape['G']:3d}{shape['P']:4d}{shape['N']:5d}"
              f"{state_mb:9.1f}{ms:9.4f}{row['roof_ms']:9.4f}{row['roof_pct']:8.1f}{other:9.4f}"
              f"{dist['y']['max_abs']:10.2e}{dist['state']['max_abs']:10.2e}", flush=True)
        del inputs, fine
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/mamba2_step_check.{args.label}.json", "w") as fh:
        json.dump(result, fh, indent=1)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())

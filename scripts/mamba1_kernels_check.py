"""The two Mamba-1 kernels alone on the chip: device ms a call beside the
time of their copies, and their answers against their plain forms.

    python scripts/mamba1_kernels_check.py [--running 256,100,1] [--chunks 2048,32] [--iters 20]

At the sizes of `benchmark/configs/jamba2-3b.json` and the lanes of
`benchmark/workloads/jamba2-3b.serve.think-backlog.json`:

- `ray_tpu.ops.pallas_mamba1.mamba1_decode_step` in a jit of its own over
  one layer's lane-state array, the states donated and handed from call
  to call as the engine hands them, with `--running` of the lanes
  running (evenly spread: at 100 of 256 every block of eight lanes holds
  a running one, so every block is copied);
- `mamba1_chunk_scan` on a chunk of `--chunks` positions (all real but
  the last seven, so that pads are exercised) from a lane's state.

Device ms a call is the kernel's `tpu_custom_call` events of a profiler
trace of `--iters` calls (the benchmark's own reduction); `other ms` is
the rest of the jit (the wrapper's casts, `dt x`, the columns' layout).
`copies ms` is the least time by `benchmark/flops_jamba.py` (its bytes
at the chip's HBM rate: `peaks.json` has no vector-unit peak, which is
what bounds the chunk kernel).  One call's outputs (x in float32, so
that y is not rounded) and states are held against `ops.mamba1` on the
same chip.

Prints a table, then one JSON object, and writes it to
`chiprun_out/mamba1_kernels_check.json`.  Needs the TPU: in interpret
mode a time says nothing.  No benchmark cell and no test runs this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG, CELL = "jamba2-3b", "jamba2-3b.serve.think-backlog"


def cell_shape() -> tuple[dict, int]:
    """(the configuration's file, the cell's lanes)."""
    with open(os.path.join(ROOT, "benchmark", "configs", f"{CONFIG}.json")) as fh:
        cfg = json.load(fh)
    with open(os.path.join(ROOT, "benchmark", "workloads", f"{CELL}.json")) as fh:
        work = json.load(fh)
    return cfg, work["engine"]["max_batch_size"]


def inputs(rows: int, N: int, D: int, seed: int):
    """(x, dt, A, B, C, D) of `rows` tokens: x in bf16 as the model hands
    it, the rest float32; the steps log-uniform in [1e-3, 1e-1] and ``A
    = -(n + 1)``, as the family's weights give them."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)

    def f(*s):
        return jnp.asarray(rng.standard_normal(s, dtype=np.float32))

    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (rows, D))).astype(np.float32))
    A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32)[:, None], (N, D))
    return f(rows, D).astype(jnp.bfloat16), dt, A, f(rows, N), f(rows, N), f(D)


def traced_ms(run, carry, iters: int, kernel: str) -> tuple[float, float]:
    """(device ms a call of the kernel, of every other operation of the
    jit) from a trace of `iters` calls of ``carry = run(carry)``."""
    import jax

    from benchmark import trace_reduce

    carry = run(carry)  # compiles
    jax.block_until_ready(carry)
    with tempfile.TemporaryDirectory() as logdir:
        with jax.profiler.trace(logdir):
            for _ in range(iters):
                carry = run(carry)
            jax.block_until_ready(carry)
        planes = trace_reduce.load(trace_reduce.find_xplane(logdir))
    ops = trace_reduce.device_ops(planes)
    mine = other = 0.0
    for name, _, dur in ops[min(ops)]:
        if trace_reduce.family(name).startswith(kernel):
            mine += dur / 1e6 / iters
        else:
            other += dur / 1e6 / iters
    return mine, other


def distance(got, want, rows=slice(None)) -> dict:
    import numpy as np

    out = {}
    for name, a, b in zip(("y", "state"), got, want):
        a, b = np.asarray(a, np.float32)[rows], np.asarray(b, np.float32)[rows]
        out[name] = {"max_abs": float(np.abs(a - b).max(initial=0.0)), "ref_max_abs": float(np.abs(b).max(initial=0.0))}
    return out


def ints(text: str) -> list:
    return [int(v) for v in text.split(",") if v]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--running", default="256,100,1")
    ap.add_argument("--chunks", default="2048,32")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import flops, flops_jamba
    from ray_tpu.ops import mamba1, pallas_mamba1 as kernels

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": f"needs a TPU, found {dev.platform}"}))
        return 1
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as fh:
        peak = json.load(fh)[dev.device_kind]
    cfg, lanes = cell_shape()
    N, D = cfg["mamba_d_state"], cfg["mamba_expand"] * cfg["hidden_size"]
    result = {"iters": args.iters, "device": {"platform": dev.platform, "kind": dev.device_kind},
              "lanes": lanes, "N": N, "D": D, "rows": []}
    rng = np.random.default_rng(args.seed + 1)
    print(f"{'kernel':20}{'case':>16}{'ms':>9}{'copies ms':>10}{'copies %':>9}{'other ms':>9}{'|dy|':>10}{'|dS|':>10}")

    def show(row):
        result["rows"].append(row)
        print(f"{row['kernel']:20}{row['case']:>16}{row['ms']:9.4f}{row['roof_ms']:10.4f}"
              f"{row['roof_pct']:9.1f}{row['other_ms']:9.4f}{row['distance']['y']['max_abs']:10.2e}"
              f"{row['distance']['state']['max_abs']:10.2e}", flush=True)

    # ---- decode
    head = inputs(lanes, N, D, args.seed)
    state0 = jnp.asarray(rng.standard_normal((lanes, N, D), dtype=np.float32))
    step = jax.jit(kernels.mamba1_decode_step, donate_argnums=(6,))
    for running in ints(args.running):
        on = np.zeros(lanes, bool)
        on[np.linspace(0, lanes - 1, min(running, lanes)).round().astype(int)] = True
        active = jnp.asarray(on)
        least = flops.least_seconds(flops_jamba.ssm1_step_work(cfg, int(on.sum())), peak)
        ms, other = traced_ms(lambda s: step(*head, s, active)[1], state0 + 0.0, args.iters, "mamba1_decode_step")
        fine = (head[0].astype(jnp.float32), *head[1:])
        dist = distance(kernels.mamba1_decode_step(*fine, state0, active),
                        jax.jit(mamba1.ssm1_step)(*fine, state0, active), on)
        show({"kernel": "mamba1_decode_step", "case": f"{int(on.sum())}/{lanes} lanes", "ms": ms, "other_ms": other,
              "roof_ms": least["seconds"] * 1e3, "roof_bound": least["bound"],
              "roof_pct": 100 * least["seconds"] * 1e3 / ms, "distance": dist})
    del head

    # ---- chunk
    for T in ints(args.chunks):
        head = inputs(T, N, D, args.seed + T)
        n_valid = jnp.int32(T - 7)
        least = flops.least_seconds(flops_jamba.ssm1_chunk_work(cfg, T - 7, 1), peak)
        ms, other = traced_ms(lambda s: kernels.mamba1_chunk_scan(*head, s, n_valid)[1], state0[0], args.iters,
                              "mamba1_chunk_scan")
        fine = (head[0].astype(jnp.float32), *head[1:])
        got = kernels.mamba1_chunk_scan(*fine, state0[0], n_valid)
        want_y, want_s = jax.jit(mamba1.selective_scan_chunk)(*fine, state0[0], n_valid)
        dist = distance((got[0][:T - 7], got[1]), (want_y[:T - 7], want_s))
        show({"kernel": "mamba1_chunk_scan", "case": f"{T} positions", "ms": ms, "other_ms": other,
              "roof_ms": least["seconds"] * 1e3, "roof_bound": least["bound"],
              "roof_pct": 100 * least["seconds"] * 1e3 / ms, "distance": dist})
        del head
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/mamba1_kernels_check.json", "w") as fh:
        json.dump(result, fh, indent=1)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())

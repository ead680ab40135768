"""The flash kernels alone on the chip: times and distances.

    python scripts/flash_kernel_check.py [--repo DIR] [--shape B,T,H,D]

Times the forward, dq and dk/dv kernels apart (one jit each, the other
backward kernel dropped as dead code, `--iters` calls ended by one
`block_until_ready`, best and median of `--repeats`), and holds the
forward and the three gradients against `reference_causal_attention` on
float32 upcasts at `highest` matmul precision: largest absolute
distance, and the distance's norm over the reference's.  The same
reference computed in the arrays' own dtype is measured beside it: a
kernel should lie no further from float32 than that.

`--repo` names another checkout (the parent commit under `_scratch/`) to
take `ray_tpu` from, so that both sides run the same script in one call.
Prints one JSON object and writes it to
`chiprun_out/flash_kernel_check.<label>.json`.  Needs the TPU: on
another backend the kernels run only in interpret mode, whose time says
nothing.  No benchmark cell runs this.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time


def device_ms_by_op(fn, xs, iters: int) -> dict:
    """Device milliseconds a call of each operation family (the kernels
    apart from the transposes and `delta` around them), from a profiler
    trace of `iters` calls, reduced as the benchmark reduces its own."""
    import tempfile

    import jax

    from benchmark import trace_reduce

    with tempfile.TemporaryDirectory() as logdir:
        with jax.profiler.trace(logdir):
            for _ in range(iters):
                y = fn(*xs)
            jax.block_until_ready(y)
        planes = trace_reduce.load(trace_reduce.find_xplane(logdir))
    ops = trace_reduce.device_ops(planes)
    by = {}
    for name, _, dur in ops[min(ops)]:
        fam = trace_reduce.family(name)
        by[fam] = by.get(fam, 0.0) + dur / 1e6 / iters
    return dict(sorted(by.items(), key=lambda kv: -kv[1]))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--label", default="change")
    ap.add_argument("--shape", default="8,1024,16,64")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-distances", action="store_true")
    ap.add_argument("--chunk", type=int, default=0,
                    help="sweep only: the sub-tile the shape function prefers (its module constant)")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.repo))

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import reference_causal_attention
    from ray_tpu.ops import pallas_attention
    from ray_tpu.ops.pallas_attention import flash_attention

    if args.chunk:
        pallas_attention._CHUNK = args.chunk
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": f"needs a TPU, found {dev.platform}"}))
        return 1
    shape = tuple(int(x) for x in args.shape.split(","))
    dtype = jnp.dtype(args.dtype)
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
    q, k, v, g = (jax.random.normal(kk, shape, jnp.float32).astype(dtype) for kk in keys)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True)

    # the backward alone: the residuals are arguments of the jit, and the
    # kernel whose output is not returned is dead code
    out, vjp = jax.vjp(flash, q, k, v)
    programs = {
        "forward": (jax.jit(flash), (q, k, v)),
        "dq": (jax.jit(lambda f, g: f(g)[0]), (vjp, g)),
        "dkv": (jax.jit(lambda f, g: f(g)[1:]), (vjp, g)),
        "backward": (jax.jit(lambda f, g: f(g)), (vjp, g)),
    }
    result = {
        "label": args.label, "repo": os.path.abspath(args.repo), "shape": shape,
        "dtype": str(dtype), "device": {"platform": dev.platform, "kind": dev.device_kind},
        "iters": args.iters, "ms": {}, "custom_calls": {},
    }
    if hasattr(pallas_attention, "flash_tiles"):
        result["block_chunk"] = pallas_attention.flash_tiles(shape[1], shape[3], dtype.itemsize)
    pairs = shape[0] * shape[2]
    for name, (fn, xs) in programs.items():
        result["custom_calls"][name] = fn.lower(*xs).compile().as_text().count(
            'custom_call_target="tpu_custom_call"')
        jax.block_until_ready(fn(*xs))
        times = []
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            for _ in range(args.iters):
                y = fn(*xs)
            jax.block_until_ready(y)
            times.append((time.perf_counter() - t0) / args.iters * 1e3)
        result["ms"][name] = {
            "best": min(times), "median": statistics.median(times),
            "us_per_pair_best": min(times) * 1e3 / pairs,
            "device_ms_by_op": device_ms_by_op(fn, xs, args.iters),
        }

    if not args.no_distances:
        def distances(got, want):
            out = {}
            for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
                a, b = a.astype(jnp.float32), b.astype(jnp.float32)
                out[name] = {
                    "max_abs": float(jnp.abs(a - b).max()),
                    "rel_norm": float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)),
                    "ref_max_abs": float(jnp.abs(b).max()),
                }
            return out

        def with_grads(fn, q, k, v, g):
            out, f = jax.vjp(fn, q, k, v)
            return (out,) + tuple(f(g.astype(out.dtype)))

        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda *xs: with_grads(reference_causal_attention, *xs))(
                *(x.astype(jnp.float32) for x in (q, k, v, g)))
        result["kernel_vs_float32"] = distances(with_grads(flash, q, k, v, g), want)
        result["reference_in_dtype_vs_float32"] = distances(
            jax.jit(lambda *xs: with_grads(reference_causal_attention, *xs))(q, k, v, g), want)

    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/flash_kernel_check.{args.label}.json", "w") as fh:
        json.dump(result, fh, indent=1)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())

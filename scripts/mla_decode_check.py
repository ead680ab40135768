"""The latent decode kernel alone on the chip: device ms a call, and
which of its copies and its arithmetic a call waits for.

    python scripts/mla_decode_check.py [--repo label=DIR ...] [--label change]
        [--sets cell,short] [--ablate whole,arithmetic,copies] [--iters 24]

`ray_tpu.ops.pallas_mla_paged_attention.mla_paged_decode_attention_kernel`
in a jit of its own at the shape of `mistral-small-4.serve.longctx-backlog`
(the sizes from `benchmark/configs/mistral-small-4.json`, lanes, pages
and pool from `benchmark/workloads/`): 48 lanes over a pool of six
layers' latent rows, pages of 64 handed out in a shuffled order, the
layer going round as the engine's six calls a step do.  Two sets of
lanes: `cell`, lengths at the quantiles of the cell's prompt mix, each
somewhere in an output of its mix (about 520k positions a call); and
`short`, every lane 2-3k.  Device ms a call is the
`mla_paged_decode_attention tpu_custom_call` events of a profiler trace
of `--iters` calls (the benchmark's own reduction), the share of the
roof the least time of the call by `benchmark.flops_mla.mla_decode_work`
over it: what `mla_paged_decode_attention_roofline.mla` reads in the
cell's traced window.

Two ablations, made HERE and not in the kernel's module, say which of
the two a call waits for: `arithmetic` traces the kernel with its page
copies taken out (the block's matmuls and softmax over the zeroed
buffers, nothing read from the pool) and `copies` with the block's
arithmetic taken out (every page copied and waited for, nothing
computed).  A call at the sum of the two hides neither under the
other; a call at the larger is at its pace.  An ablated call's output
is wrong by design and is never compared.

One whole call's output is held against `ops.attention`'s gather path
on the same chip (the largest distance, beside the largest value).

`--repo label=DIR`, once or more, names checkouts to take `ray_tpu`
from: each is timed in a process of its own, one after the other in the
same call, and the table shows them side by side.  Without it this
checkout is timed under `--label`.  Prints a table, then one JSON
object, and writes it to `chiprun_out/mla_decode_check.<label>.json`.
Needs the TPU: in interpret mode a time says nothing.  No benchmark
cell and no test runs this.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import subprocess
import sys
import tempfile
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "mistral-small-4"
CELL = "mistral-small-4.serve.longctx-backlog"
KERNEL = "mla_paged_decode_attention"
ABLATIONS = ("whole", "arithmetic", "copies")


def cell_shape() -> dict:
    """The kernel's shape in the cell, from the benchmark's files."""
    with open(os.path.join(ROOT, "benchmark", "configs", f"{CONFIG}.json")) as fh:
        cfg = json.load(fh)
    with open(os.path.join(ROOT, "benchmark", "workloads", f"{CELL}.json")) as fh:
        work = json.load(fh)
    eng = work["engine"]
    return {"config": cfg, "traffic": work["traffic"], "lanes": eng["max_batch_size"], "block_size": eng["block_size"],
            "pool_tokens": eng["pool_tokens"], "max_model_len": eng["max_model_len"],
            "layers": cfg["num_hidden_layers"], "heads": cfg["num_attention_heads"],
            "v_width": cfg["kv_lora_rank"], "row": cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"],
            "width": work["checks"]["cached_row_columns"]}


def lane_lengths(shape: dict, which: str, seed: int) -> list:
    """Cached positions a lane.  `cell`: a prompt at each quantile of the
    cell's mix, a `seed`-drawn part of an output of its mix decoded;
    `short`: 2-3k a lane."""
    import numpy as np

    from benchmark import traffic

    rng = np.random.default_rng(seed)
    lanes, tr = shape["lanes"], shape["traffic"]
    if which == "short":
        return rng.integers(2048, 3072, lanes).tolist()
    prompts = np.asarray(traffic.lognormal_lengths(lanes, tr["prompt_len"]))
    outs = rng.permutation(np.asarray(traffic.lognormal_lengths(lanes, tr["max_tokens"])))
    lengths = np.minimum(prompts + (outs * rng.random(lanes)).astype(int), tr["max_total_tokens"] - 1)
    return rng.permutation(lengths).tolist()


def inputs(shape: dict, lengths: list, seed: int):
    """(q, row_self, pages, block_tables, lengths) as the engine's decode
    step hands them: bf16, a row's columns past the latent's zero, every
    lane's pages drawn from the pool in a shuffled order."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    lanes, bs, W = shape["lanes"], shape["block_size"], shape["width"]
    keys = jax.random.split(jax.random.PRNGKey(seed % 2**31), 3)
    live = (jnp.arange(W) < shape["row"]).astype(jnp.bfloat16)
    pages = jax.random.normal(keys[0], (shape["layers"], shape["pool_tokens"] + bs, W), jnp.bfloat16) * live
    q = 0.25 * jax.random.normal(keys[1], (lanes, shape["heads"], W), jnp.bfloat16) * live
    row_self = jax.random.normal(keys[2], (lanes, W), jnp.bfloat16) * live
    per_lane = -(-shape["max_model_len"] // bs)
    order = np.random.default_rng(seed).permutation(np.arange(1, shape["pool_tokens"] // bs + 1))
    tables, at = np.zeros((lanes, per_lane), np.int32), 0
    for lane, n in enumerate(lengths):
        held = -(-n // bs)
        tables[lane, :held] = order[at:at + held]
        at += held
    return q, row_self, pages, jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)


@contextlib.contextmanager
def ablated(module, what: str):
    """`module`'s kernel traced without its page copies (`arithmetic`) or
    without its block's arithmetic (`copies`): the walk it is handed
    makes copies that start and wait for nothing, or folds nothing."""
    if what == "whole":
        yield
        return
    real = module.paged_walk
    pltpu = real.pltpu
    make = pltpu.make_async_copy

    class NoCopy:
        def start(self):
            pass

        wait = start

    def no_fold(total, item, **kw):
        def bare(j):
            blk, first, _ = item(j)
            return blk, first, lambda slot: None

        return real.walk(total, bare, **kw)

    try:
        if what == "copies":
            module.paged_walk = types.SimpleNamespace(**{**vars(real), "walk": no_fold})
        else:
            pltpu.make_async_copy = lambda *a, **k: NoCopy()
        yield
    finally:
        module.paged_walk = real
        pltpu.make_async_copy = make


def kernel_ms(call, args, layers: int, iters: int) -> float:
    """Device ms a call of the kernel from a trace of `iters` calls, the
    layer going round."""
    import jax
    import jax.numpy as jnp

    from benchmark import trace_reduce

    q, row_self, pages, tables, lengths = args

    def run(layer):  # the pool an argument: a jit that closed over it would hold it as a constant
        return call(q, row_self, pages, jnp.int32(layer), tables, lengths)

    jax.block_until_ready(run(0))  # compiles
    with tempfile.TemporaryDirectory() as logdir:
        with jax.profiler.trace(logdir):
            outs = [run(i % layers) for i in range(iters)]
            jax.block_until_ready(outs)
        planes = trace_reduce.load(trace_reduce.find_xplane(logdir))
    ops = trace_reduce.device_ops(planes)
    durs = [dur for name, _, dur in ops[min(ops)] if trace_reduce.family(name).startswith(KERNEL)]
    if len(durs) != iters:
        raise RuntimeError(f"{len(durs)} {KERNEL} events in a trace of {iters} calls")
    return sum(durs) / 1e6 / iters


def time_checkout(args) -> int:
    """Time the `ray_tpu` of `args.repo` in this process."""
    sys.path.insert(0, os.path.abspath(args.repo))
    sys.path.insert(1, ROOT)  # benchmark/ is this checkout's

    import functools

    import jax
    import numpy as np

    from benchmark import flops, flops_mla
    from ray_tpu.ops import attention
    from ray_tpu.ops import pallas_mla_paged_attention as module

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": f"needs a TPU, found {dev.platform}"}))
        return 1
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as fh:
        peak = json.load(fh)[dev.device_kind]
    shape = cell_shape()
    sizes = dict(block_size=shape["block_size"], v_width=shape["v_width"])
    result = {"label": args.label, "repo": os.path.abspath(args.repo), "iters": args.iters,
              "block_positions": getattr(module, "_BLOCK_POSITIONS", None),
              "device": {"platform": dev.platform, "kind": dev.device_kind}, "rows": []}
    for which in args.sets.split(","):
        lengths = lane_lengths(shape, which, args.seed)
        data = inputs(shape, lengths, args.seed)
        least = flops.least_seconds(flops_mla.mla_decode_work(shape["config"], sum(lengths), shape["lanes"]), peak)
        row = {"set": which, "positions": sum(lengths), "shortest": min(lengths), "longest": max(lengths),
               "roof_ms": least["seconds"] * 1e3, "roof_bound": least["bound"], "ms": {}}
        for what in args.ablate.split(","):
            # a jit keeps what it traced of a function: a new partial of the one under the kernel's own is traced anew
            call = jax.jit(functools.partial(module.mla_paged_decode_attention_kernel.__wrapped__, **sizes))
            with ablated(module, what):
                row["ms"][what] = kernel_ms(call, data, shape["layers"], args.iters)
        row["roof_pct"] = 100 * row["roof_ms"] / row["ms"]["whole"] if "whole" in row["ms"] else None
        q, row_self, pages, tables, lens = data
        got = module.mla_paged_decode_attention_kernel(q, row_self, pages, 1, tables, lens, **sizes)
        try:  # the entry takes the kernel on a TPU: its gather path is what it does elsewhere
            backend, jax.default_backend = jax.default_backend, lambda: "cpu"
            want = jax.jit(functools.partial(attention.mla_paged_decode_attention, **sizes))(
                q, row_self, pages, 1, tables, lens)
        finally:
            jax.default_backend = backend
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        row["distance"] = {"max_abs": float(np.abs(got - want).max()), "ref_max_abs": float(np.abs(want).max())}
        result["rows"].append(row)
        del data, q, row_self, pages, tables, lens
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/mla_decode_check.{args.label}.json", "w") as fh:
        json.dump(result, fh, indent=1)
    return 0


def table(results: list) -> None:
    print(f"{'checkout':12}{'block':>6}{'set':>7}{'positions':>10}{'ms':>9}{'roof ms':>9}{'roof %':>8}"
          f"{'arithmetic':>11}{'copies':>9}{'|d|':>10}{'|ref|':>8}")
    for res in results:
        for row in res["rows"]:
            ms = row["ms"]

            def cell(what, width):
                return f"{ms[what]:{width}.4f}" if what in ms else f"{'-':>{width}}"

            roof = f"{row['roof_pct']:8.1f}" if row["roof_pct"] is not None else f"{'-':>8}"
            print(f"{res['label']:12}{res['block_positions'] or 0:6d}{row['set']:>7}{row['positions']:10d}"
                  f"{cell('whole', 9)}{row['roof_ms']:9.4f}{roof}{cell('arithmetic', 11)}{cell('copies', 9)}"
                  f"{row['distance']['max_abs']:10.2e}{row['distance']['ref_max_abs']:8.3f}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", action="append", default=[],
                    help="label=DIR of a checkout to time, once or more; default: this one under --label")
    ap.add_argument("--label", default="change")
    ap.add_argument("--sets", default="cell,short")
    ap.add_argument("--ablate", default=",".join(ABLATIONS))
    ap.add_argument("--iters", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--one", action="store_true", help=argparse.SUPPRESS)  # a child: time --repo DIR as --label
    args = ap.parse_args()
    if args.one:
        args.repo = args.repo[0]
        return time_checkout(args)
    # the chip is one process's at a time: this one stays off JAX and times each checkout in a child
    results = []
    for spec in args.repo or [f"{args.label}={ROOT}"]:
        label, _, repo = spec.rpartition("=")
        label = label or args.label
        rc = subprocess.run([sys.executable, os.path.abspath(__file__), "--one", "--repo", repo, "--label", label,
                             "--sets", args.sets, "--ablate", args.ablate, "--iters", str(args.iters),
                             "--seed", str(args.seed)]).returncode
        if rc:
            return rc
        with open(f"chiprun_out/mla_decode_check.{label}.json") as fh:
            results.append(json.load(fh))
    table(results)
    print(json.dumps(results if len(results) > 1 else results[0]))
    return 0


if __name__ == "__main__":
    sys.exit(main())

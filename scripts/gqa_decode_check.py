"""The grouped-query decode kernel alone on the chip: device ms a call at
four cells' shapes, and which of its copies and its arithmetic a call
waits for.

    python scripts/gqa_decode_check.py [--repo label=DIR ...] [--label change]
        [--sets longthink,think,reason,mixed] [--ablate whole,arithmetic,copies] [--iters 24]

`ray_tpu.ops.pallas_gqa_paged_attention.gqa_paged_decode_attention_kernel`
in a jit of its own, the pattern of `scripts/mla_decode_check.py`.  A set
is a cell: its heads from `benchmark/configs/`, its lanes, pages, pool
and mix from `benchmark/workloads/`; pages of 64 handed out in a
shuffled order, the layer going round as the engine's calls a step do:

    longthink  zaya1-8b.serve.longthink-backlog       48 lanes, 2 K/V heads, R 4
    think      jamba2-3b.serve.think-backlog         256 lanes, 1 K/V head,  R 20
    reason     nemotron-3-nano.serve.reason-backlog  128 lanes, 2 K/V heads, R 16
    mixed      mellum2-12b-a2.5b.serve.mixed-backlog  32 lanes, 4 K/V heads, R 8

A lane's length is what a lane of the cell's steady backlog holds: a
request of the mix met with the chance of the steps it stays (its
`max_tokens`), a `seed`-drawn part of its answer decoded.  Device ms a
call is the `gqa_paged_decode_attention tpu_custom_call` events of a
profiler trace of `--iters` calls (the benchmark's own reduction), the
share of the roof the least time of the call by
`benchmark.flops_mellum.gqa_decode_work` over it: what
`gqa_paged_decode_attention_roofline` reads in a cell's traced window.
`us/block` is a call over the compute blocks of 512 positions the
lanes hold, whatever the kernel's own block is, so that two checkouts
are read on one scale; `whole %` is the share of the kernel's OWN blocks
(`block_positions`) that hold all their pages.

The ablations `arithmetic` (the kernel traced with its page copies
taken out) and `copies` (with the block's arithmetic taken out) are
`mla_decode_check.ablated`, made in these scripts and not in the
kernel's module: a call at the sum of the two hides neither under the
other, a call at the larger is at its pace.  An ablated call's output
is wrong by design and is never compared.

One whole call's output is held against `ops.attention`'s gather path
on the same chip, a few lanes at a time (the gathered context of every
lane at once is larger than the chip): the largest distance, beside
the largest value.

`--repo label=DIR`, once or more, names checkouts to take `ray_tpu`
from: each is timed in a process of its own, one after the other in the
same call, and the table shows them side by side.  Without it this
checkout is timed under `--label`.  Prints a table, then one JSON
object, and writes it to `chiprun_out/gqa_decode_check.<label>.json`.
Needs the TPU: in interpret mode a time says nothing.  No benchmark
cell and no test runs this.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

from mla_decode_check import ablated

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KERNEL = "gqa_paged_decode_attention"
ABLATIONS = ("whole", "arithmetic", "copies")
SETS = {
    "longthink": ("zaya1-8b", "zaya1-8b.serve.longthink-backlog"),
    "think": ("jamba2-3b", "jamba2-3b.serve.think-backlog"),
    "reason": ("nemotron-3-nano", "nemotron-3-nano.serve.reason-backlog"),
    "mixed": ("mellum2-12b-a2.5b", "mellum2-12b-a2.5b.serve.mixed-backlog"),
}
# the layers of a set's pool: those the cell's decode step calls the kernel for
PAGED_LAYERS = {"longthink": 20, "think": 2, "reason": 3, "mixed": 3}
READ_BLOCK = 512  # positions: the scale of `us/block`


def cell_shape(which: str) -> dict:
    """The kernel's shape in the set's cell, from the benchmark's files."""
    config, cell = SETS[which]
    with open(os.path.join(ROOT, "benchmark", "configs", f"{config}.json")) as fh:
        cfg = json.load(fh)
    with open(os.path.join(ROOT, "benchmark", "workloads", f"{cell}.json")) as fh:
        work = json.load(fh)
    eng = work["engine"]
    cfg.setdefault("head_dim", 128)
    return {"config": cfg, "cell": cell, "traffic": work["traffic"], "lanes": eng["max_batch_size"],
            "block_size": eng["block_size"], "pool_tokens": eng["pool_tokens"], "max_model_len": eng["max_model_len"],
            "layers": PAGED_LAYERS[which], "kv_heads": cfg["num_key_value_heads"],
            "rep": cfg["num_attention_heads"] // cfg["num_key_value_heads"], "d_head": cfg["head_dim"]}


def lane_lengths(shape: dict, seed: int) -> list:
    """Cached positions a lane of the cell's steady backlog: a request of
    the mix's pool drawn with the chance of its `max_tokens` (the steps
    it holds a lane), a `seed`-drawn part of its answer decoded."""
    import numpy as np

    from benchmark import traffic

    rng = np.random.default_rng(seed)
    lanes, tr = shape["lanes"], shape["traffic"]
    n = tr["pool_requests"]
    prompts = rng.permutation(np.asarray(traffic.lognormal_lengths(n, tr["prompt_len"])))
    outs = rng.permutation(np.asarray(traffic.lognormal_lengths(n, tr["max_tokens"])))
    held = rng.choice(n, size=lanes, p=outs / outs.sum())
    lengths = prompts[held] + (outs[held] * rng.random(lanes)).astype(int)
    lengths = np.minimum(lengths, tr["max_total_tokens"] - 1)
    # what the pool holds: the longest lanes give way until every lane's pages fit
    bs, pages = shape["block_size"], shape["pool_tokens"] // shape["block_size"]
    while (-(-lengths // bs)).sum() > pages:
        lengths[np.argmax(lengths)] //= 2
    return lengths.tolist()


def inputs(shape: dict, lengths: list, seed: int):
    """(q, k_self, v_self, k_pages, v_pages, block_tables, lengths) as the
    engine's decode step hands them: bf16, every lane's pages drawn from
    the pool in a shuffled order."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    lanes, bs = shape["lanes"], shape["block_size"]
    G, R, Dh = shape["kv_heads"], shape["rep"], shape["d_head"]
    keys = jax.random.split(jax.random.PRNGKey(seed % 2**31), 5)
    rows = shape["pool_tokens"] + bs
    k_pages = jax.random.normal(keys[0], (shape["layers"], rows, G * Dh), jnp.bfloat16)
    v_pages = jax.random.normal(keys[1], (shape["layers"], rows, G * Dh), jnp.bfloat16)
    q = jax.random.normal(keys[2], (lanes, G, R, Dh), jnp.bfloat16)
    k_self = jax.random.normal(keys[3], (lanes, G, Dh), jnp.bfloat16)
    v_self = jax.random.normal(keys[4], (lanes, G, Dh), jnp.bfloat16)
    per_lane = -(-shape["max_model_len"] // bs)
    order = np.random.default_rng(seed).permutation(np.arange(1, shape["pool_tokens"] // bs + 1))
    tables, at = np.zeros((lanes, per_lane), np.int32), 0
    for lane, n in enumerate(lengths):
        held = -(-n // bs)
        tables[lane, :held] = order[at:at + held]
        at += held
    return q, k_self, v_self, k_pages, v_pages, jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)


def kernel_ms(call, args, layers: int, iters: int) -> float:
    """Device ms a call of the kernel from a trace of `iters` calls, the
    layer going round."""
    import jax
    import jax.numpy as jnp

    from benchmark import trace_reduce

    q, k_self, v_self, k_pages, v_pages, tables, lengths = args

    def run(layer):  # the pools arguments: a jit that closed over them would hold them as constants
        return call(q, k_self, v_self, k_pages, v_pages, jnp.int32(layer), tables, lengths)

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


def gathered(attention, data, block_size: int):
    """`ops.attention`'s gather path over the same inputs, a few lanes at
    a time: a lane's gathered context, every K/V head repeated for its
    query heads, is `pages x block_size x heads x Dh` values."""
    import functools

    import jax
    import numpy as np

    q, k_self, v_self, k_pages, v_pages, tables, lens = data
    B, G, R, Dh = q.shape
    step = max(1, (1 << 28) // (tables.shape[1] * block_size * G * R * Dh))
    try:  # the entry takes the kernel on a TPU: its gather path is what it does elsewhere
        backend, jax.default_backend = jax.default_backend, lambda: "cpu"
        ref = jax.jit(functools.partial(attention.gqa_paged_decode_attention, block_size=block_size))
        return np.concatenate([
            np.asarray(ref(q[at:at + step], k_self[at:at + step], v_self[at:at + step], k_pages, v_pages, 1,
                           tables[at:at + step], lens[at:at + step]), np.float32)
            for at in range(0, B, step)])
    finally:
        jax.default_backend = backend


def time_checkout(args) -> int:
    """Time the `ray_tpu` of `args.repo` in this process."""
    sys.path.insert(0, os.path.abspath(args.repo))
    sys.path.insert(1, ROOT)  # benchmark/ is this checkout's

    import functools

    import jax
    import numpy as np

    from benchmark import flops, flops_mellum
    from ray_tpu.ops import attention
    from ray_tpu.ops import pallas_gqa_paged_attention as module

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": f"needs a TPU, found {dev.platform}"}))
        return 1
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as fh:
        peak = json.load(fh)[dev.device_kind]
    result = {"label": args.label, "repo": os.path.abspath(args.repo), "iters": args.iters,
              "device": {"platform": dev.platform, "kind": dev.device_kind}, "rows": []}
    for which in args.sets.split(","):
        shape = cell_shape(which)
        bs = shape["block_size"]
        lengths = lane_lengths(shape, args.seed)
        data = inputs(shape, lengths, args.seed)
        least = flops.least_seconds(flops_mellum.gqa_decode_work(shape["config"], sum(lengths), shape["lanes"]), peak)
        # the kernel's own block: a constant of the module, or what it reads off the pool
        block_of = getattr(module, "block_positions", None)
        own = block_of(data[3]) if block_of else module._BLOCK_POSITIONS
        row = {"set": which, "cell": shape["cell"], "lanes": shape["lanes"], "kv_heads": shape["kv_heads"],
               "rep": shape["rep"], "positions": sum(lengths), "shortest": min(lengths), "longest": max(lengths),
               "block_positions": own, "read_blocks": sum(-(-n // READ_BLOCK) for n in lengths),
               "blocks": sum(-(-n // own) for n in lengths), "blocks_whole": sum(-(-n // bs) * bs // own for n in lengths),
               "roof_ms": least["seconds"] * 1e3, "roof_bound": least["bound"], "ms": {}}
        for what in args.ablate.split(","):
            # a jit keeps what it traced of a function, and the kernel calls its own jit again for a group it
            # pads to a tile: every cache is dropped, and a new partial of the function under the jit traced anew
            jax.clear_caches()
            call = jax.jit(functools.partial(module.gqa_paged_decode_attention_kernel.__wrapped__, block_size=bs))
            with ablated(module, what):
                row["ms"][what] = kernel_ms(call, data, shape["layers"], args.iters)
        row["roof_pct"] = 100 * row["roof_ms"] / row["ms"]["whole"] if "whole" in row["ms"] else None
        jax.clear_caches()  # the last ablation's trace
        q, k_self, v_self, k_pages, v_pages, tables, lens = data
        got = np.asarray(module.gqa_paged_decode_attention_kernel(
            q, k_self, v_self, k_pages, v_pages, 1, tables, lens, block_size=bs), np.float32)
        want = gathered(attention, data, bs)
        row["distance"] = {"max_abs": float(np.abs(got - want).max()), "ref_max_abs": float(np.abs(want).max())}
        np.save(f"chiprun_out/gqa_decode_check.{args.label}.{which}.npy", got)
        result["rows"].append(row)
        del data, q, k_self, v_self, k_pages, v_pages, tables, lens
    with open(f"chiprun_out/gqa_decode_check.{args.label}.json", "w") as fh:
        json.dump(result, fh, indent=1)
    return 0


def table(results: list) -> None:
    print(f"{'checkout':10}{'set':>10}{'block':>6}{'whole %':>8}{'positions':>10}{'ms':>9}{'us/block':>9}{'roof ms':>9}"
          f"{'roof %':>8}{'arithmetic':>11}{'copies':>9}{'|d|':>10}{'|ref|':>8}")
    for res in results:
        for row in res["rows"]:
            ms = row["ms"]

            def cell(what, width):
                return f"{ms[what]:{width}.4f}" if what in ms else f"{'-':>{width}}"

            roof = f"{row['roof_pct']:8.1f}" if row["roof_pct"] is not None else f"{'-':>8}"
            per = f"{1e3 * ms['whole'] / row['read_blocks']:9.3f}" if "whole" in ms else f"{'-':>9}"
            print(f"{res['label']:10}{row['set']:>10}{row['block_positions']:6d}"
                  f"{100 * row['blocks_whole'] / row['blocks']:8.1f}{row['positions']:10d}"
                  f"{cell('whole', 9)}{per}{row['roof_ms']:9.4f}{roof}{cell('arithmetic', 11)}{cell('copies', 9)}"
                  f"{row['distance']['max_abs']:10.2e}{row['distance']['ref_max_abs']:8.3f}")


def same_outputs(results: list) -> dict:
    """Whether the checkouts' whole calls gave the same bits, a set: the
    first checkout's output against each other's."""
    import numpy as np

    first, same = results[0], {}
    for res in results[1:]:
        for row in res["rows"]:
            a, b = (f"chiprun_out/gqa_decode_check.{r['label']}.{row['set']}.npy" for r in (first, res))
            if os.path.exists(a):
                same[f"{first['label']}=={res['label']}.{row['set']}"] = bool(np.array_equal(np.load(a), np.load(b)))
    return same


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", action="append", default=[],
                    help="label=DIR of a checkout to time, once or more; default: this one under --label")
    ap.add_argument("--label", default="change")
    ap.add_argument("--sets", default=",".join(SETS))
    ap.add_argument("--ablate", default=",".join(ABLATIONS))
    ap.add_argument("--iters", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--one", action="store_true", help=argparse.SUPPRESS)  # a child: time --repo DIR as --label
    args = ap.parse_args()
    os.makedirs("chiprun_out", exist_ok=True)
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
        with open(f"chiprun_out/gqa_decode_check.{label}.json") as fh:
            results.append(json.load(fh))
    table(results)
    same = same_outputs(results)
    print(json.dumps({"results": results, "same_bits": same} if len(results) > 1 else results[0]))
    return 0


if __name__ == "__main__":
    sys.exit(main())

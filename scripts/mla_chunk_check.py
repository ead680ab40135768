"""The latent family's chunk attention alone on the chip: device ms a
call of `ops.mla.expanded_attention` without a mask, the share of the
bf16 peak its arithmetic reaches, and how far it lies from the XLA loop.

    python scripts/mla_chunk_check.py [--repo label=DIR ...] [--label change]
        [--cases tile,chunk] [--tiles 4096x512x4,1024x256x2] [--iters 8]

`ray_tpu.ops.mla.expanded_attention` in a jit of its own at the widths
of `mistral-small-4.serve.longctx-backlog` (the sizes from
`benchmark/configs/mistral-small-4.json`, the context's rows from
`benchmark/workloads/`: 576 pages of 64 and the chunk's room, as
`models.layers.chunk_slots` lays them out).  Two sets of cases: `tile`,
ONE tile of 1,024 queries whose last position is 2,048 / 8,192 / 32,768
(4, 16 and 64 key blocks); and `chunk`, the cell's chunk buckets where
its prompts put them (4,096 tokens from positions 0, 4,096 and 28,672;
2,048, 512, 64 and 8 tokens behind 8,192).

Device ms a call is the device's busy time in a profiler trace of
`--iters` calls over the calls (everything the jit runs: the loop's
`while` and its fusions on a checkout without the kernel, the
`mla_chunk_attention` custom call and the weights' re-layout on one
with it; the kernel's own events are shown beside it).  The share of the
peak is the LOOP's arithmetic at `ops.mla.Q_BLOCK` and `K_BLOCK` (a
block's expansion `2 K kv H (nope + v)` once a tile, its two matmuls
`2 H tq K (nope + rope + v)`) over the call's time at `benchmark/
peaks.json`'s bf16 rate: the same numerator for every checkout, so a
kernel that pays for its keys another way gets no credit for it.

One call's output a case is held against the loop's on the same chip
(`jax.default_backend` answering "cpu" while the entry is traced: the
path it takes off the TPU): the largest distance over the real rows,
beside the largest value.

`--repo label=DIR`, once or more, names checkouts to take `ray_tpu`
from: each is timed in a process of its own, one after the other in the
same call, and the table shows them side by side.  `--tiles QxCxG`, once
or more with commas, times a checkout that has the kernel at other
tiles than its own (queries a grid step x queries a chunk x chunks a
group), set HERE on the module before it is traced.  Prints a table, then one JSON object,
and writes it to `chiprun_out/mla_chunk_check.<label>.json`.  Needs the
TPU: in interpret mode a time says nothing.  No benchmark cell and no
test runs this.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "mistral-small-4"
CELL = "mistral-small-4.serve.longctx-backlog"
KERNEL = "mla_chunk_attention"
# (name, chunk tokens, first position): a tile by its reach, a bucket where the cell's prompts put it
CASES = {
    "tile": [("reach2048", 1024, 1024), ("reach8192", 1024, 7168), ("reach32768", 1024, 31744)],
    "chunk": [("4096@0", 4096, 0), ("4096@4096", 4096, 4096), ("4096@28672", 4096, 28672),
              ("2048@8192", 2048, 8192), ("512@8192", 512, 8192), ("64@8192", 64, 8192), ("8@8192", 8, 8192)],
}


def cell_shape() -> dict:
    """The attention's widths and the context's rows in the cell, from the benchmark's files."""
    with open(os.path.join(ROOT, "benchmark", "configs", f"{CONFIG}.json")) as fh:
        cfg = json.load(fh)
    with open(os.path.join(ROOT, "benchmark", "workloads", f"{CELL}.json")) as fh:
        work = json.load(fh)
    return {"heads": cfg["num_attention_heads"], "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "kv": cfg["kv_lora_rank"], "dv": cfg["v_head_dim"], "width": work["checks"]["cached_row_columns"],
            "positions": work["engine"]["max_model_len"]}


def loop_flops(shape: dict, T: int, start: int, q_block: int, k_block: int) -> int:
    """The arithmetic of the XLA loop over a chunk of T real tokens at `start`."""
    H, nope, rope, kv, dv = (shape[k] for k in ("heads", "nope", "rope", "kv", "dv"))
    tq = min(T, q_block)
    total = 0
    for first in range(0, T, tq):
        blocks = -(-min(start + first + tq, start + T) // k_block)
        total += blocks * (2 * k_block * kv * H * (nope + dv) + 2 * H * tq * k_block * (nope + rope + dv))
    return total


def inputs(shape: dict, T: int, start: int, k_block: int, seed: int):
    """(q_nope, q_rope, ctx, wukv) as a layer's chunk hands them: bf16,
    the queries at the size the family's scale leaves them, a row's
    columns past the rotated part zero, rows past the chunk's zero."""
    import jax
    import jax.numpy as jnp

    H, nope, rope, kv, dv, W = (shape[k] for k in ("heads", "nope", "rope", "kv", "dv", "width"))
    C = -(-(shape["positions"] + T) // k_block) * k_block
    keys = jax.random.split(jax.random.PRNGKey(seed % 2**31), 4)
    live = (jnp.arange(W) < kv + rope) & (jnp.arange(C) < start + T)[:, None]
    ctx = jax.random.normal(keys[0], (C, W), jnp.bfloat16) * live.astype(jnp.bfloat16)
    q_nope = 0.25 * jax.random.normal(keys[1], (T, H, nope), jnp.bfloat16)
    q_rope = 0.25 * jax.random.normal(keys[2], (T, H, rope), jnp.bfloat16)
    wukv = kv ** -0.5 * jax.random.normal(keys[3], (kv, H * (nope + dv)), jnp.bfloat16)
    return q_nope, q_rope, ctx, wukv


def call_ms(call, args, iters: int) -> tuple:
    """(device ms a call, the kernel's own ms a call) from a trace of `iters` calls."""
    import jax

    from benchmark import trace_reduce

    jax.block_until_ready(call(*args))  # compiles
    with tempfile.TemporaryDirectory() as logdir:
        with jax.profiler.trace(logdir):
            jax.block_until_ready([call(*args) for _ in range(iters)])
        planes = trace_reduce.load(trace_reduce.find_xplane(logdir))
    ops = trace_reduce.device_ops(planes)
    events = ops[min(ops)]
    busy = trace_reduce.union_seconds((s, s + d) for _, s, d in events)
    kernel = sum(d for name, _, d in events if trace_reduce.family(name).startswith(KERNEL))
    return busy / 1e6 / iters, kernel / 1e6 / iters


def time_checkout(args) -> int:
    """Time the `ray_tpu` of `args.repo` in this process."""
    sys.path.insert(0, os.path.abspath(args.repo))
    sys.path.insert(1, ROOT)  # benchmark/ is this checkout's

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import mla

    try:
        from ray_tpu.ops import pallas_mla_chunk_attention as module
    except ImportError:  # a checkout from before the kernel: the loop alone
        module = None

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": f"needs a TPU, found {dev.platform}"}))
        return 1
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as fh:
        peak = json.load(fh)[dev.device_kind]["bf16_flops_per_s"]
    shape = cell_shape()
    cfg = types.SimpleNamespace(qk_nope_head_dim=shape["nope"], qk_rope_head_dim=shape["rope"],
                                kv_lora_rank=shape["kv"], v_head_dim=shape["dv"], latent_row=shape["width"])
    tiles = [None] + ([tuple(map(int, t.split("x"))) for t in args.tiles.split(",")] if args.tiles and module else [])
    own = (module._Q_TILE, module._CHUNK, module._GROUP) if module else None
    result = {"label": args.label, "repo": os.path.abspath(args.repo), "iters": args.iters, "kernel": module is not None,
              "device": {"platform": dev.platform, "kind": dev.device_kind}, "rows": []}

    def entry(q_nope, q_rope, ctx, wukv, start, n_valid):
        return mla.expanded_attention(q_nope, q_rope, ctx, wukv, start, n_valid, cfg)

    for name, T, start in [c for which in args.cases.split(",") for c in CASES[which]]:
        data = inputs(shape, T, start, mla.K_BLOCK, args.seed)
        at = (jnp.int32(start), jnp.int32(T))
        flop = loop_flops(shape, T, start, mla.Q_BLOCK, mla.K_BLOCK)
        try:  # the entry takes the kernel on a TPU: its loop is what it does elsewhere
            backend, jax.default_backend = jax.default_backend, lambda: "cpu"
            want = np.asarray(jax.jit(entry)(*data, *at), np.float32)
        finally:
            jax.default_backend = backend
        for tile in tiles:
            if tile:
                module._Q_TILE, module._CHUNK, module._GROUP = tile
                jax.clear_caches()  # the kernel's own jit would answer from the trace at its last tiles
            try:
                call = jax.jit(lambda *a: entry(*a))  # a new function: traced anew at these tiles
                ms, kernel_ms = call_ms(call, (*data, *at), args.iters)
                got = np.asarray(call(*data, *at), np.float32)
            finally:
                if tile:
                    module._Q_TILE, module._CHUNK, module._GROUP = own
                    jax.clear_caches()
            result["rows"].append({
                "case": name, "tokens": T, "start": start, "tiles": "x".join(map(str, tile or own or ())) or "loop",
                "ms": ms, "kernel_ms": kernel_ms, "gflop": flop / 1e9, "peak_pct": 100 * flop / peak / (ms / 1e3),
                "distance": {"max_abs": float(np.abs(got - want).max()), "ref_max_abs": float(np.abs(want).max())}})
        del data, want
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/mla_chunk_check.{args.label}.json", "w") as fh:
        json.dump(result, fh, indent=1)
    return 0


def table(results: list) -> None:
    print(f"{'checkout':12}{'tiles':>10}{'case':>12}{'GFLOP':>9}{'ms':>9}{'kernel ms':>10}{'peak %':>8}{'|d|':>10}{'|ref|':>8}")
    for res in results:
        for row in res["rows"]:
            print(f"{res['label']:12}{row['tiles']:>10}{row['case']:>12}{row['gflop']:9.1f}{row['ms']:9.4f}"
                  f"{row['kernel_ms']:10.4f}{row['peak_pct']:8.1f}{row['distance']['max_abs']:10.2e}"
                  f"{row['distance']['ref_max_abs']:8.3f}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", action="append", default=[],
                    help="label=DIR of a checkout to time, once or more; default: this one under --label")
    ap.add_argument("--label", default="change")
    ap.add_argument("--cases", default="tile,chunk")
    ap.add_argument("--tiles", default="", help="QxCxG[,QxCxG...]: other tiles than the kernel's own, where a checkout has it")
    ap.add_argument("--iters", type=int, default=8)
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
                             "--cases", args.cases, "--tiles", args.tiles, "--iters", str(args.iters),
                             "--seed", str(args.seed)]).returncode
        if rc:
            return rc
        with open(f"chiprun_out/mla_chunk_check.{label}.json") as fh:
            results.append(json.load(fh))
    table(results)
    print(json.dumps(results if len(results) > 1 else results[0]))
    return 0


if __name__ == "__main__":
    sys.exit(main())

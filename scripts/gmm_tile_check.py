"""The grouped matmul alone on the chip: device ms a call by tiling.

    python scripts/gmm_tile_check.py [--repo DIR] [--families nemotron,...]
        [--tokens decode,1024,2048] [--tilings "chosen;clamp;128,896,1856"]

For each served expert family (its sizes from `benchmark/configs/` and
its cell's lanes and chunk from `benchmark/workloads/`), the up and the
down matmul of one expert layer at the rows of a decode step (lanes x
experts a token) and of a prompt chunk (tokens x experts a token), through
`ray_tpu.ops.moe.moe_gmm` under each tiling of `--tilings`:

  chosen      what `moe_gmm` picks from the shapes (`tiling=None`)
  clamp       (rows by `_tile_rows`, min(2048, k), min(1024, n)): the
              choice before PR 40, whose last k step masks its remainder
  tm,tk,tn    as given; 0 for tm takes `_tile_rows`, 0 for tk or tn the
              whole extent

A pair's expert is drawn uniformly from ALL the experts the router ranges
over; where the chip holds a share of them, the other pairs' rows lie
behind the groups as `moe_experts` leaves them.  Device ms is the
`moe_gmm tpu_custom_call` events of a profiler trace of `--iters` calls
(the benchmark's own reduction), and the share of the roof is the least
time of the call (`flops_moe`'s count for ONE matrix: the weights of the
experts hit once, the held rows in and out once; the larger of bytes
over the chip's HBM rate and operations over its bf16 peak) over it.  A
tiling the compiler refuses (VMEM) is reported as such and the sweep
goes on.

`--repo` names another checkout to take `ray_tpu` from.  Prints a table,
then one JSON object, and writes it to
`chiprun_out/gmm_tile_check.<label>.json`.  Needs the TPU: in interpret
mode a time says nothing.  No benchmark cell and no test runs this.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# family: its configuration and cell, the keys of an expert's width and of
# the experts held, the experts the router ranges over, whether gated
FAMILIES = {
    "olmoe": ("olmoe-1b-7b", "olmoe-1b-7b.serve.backlog-wide", "intermediate_size", "num_experts", 64, True),
    "mistral": ("mistral-small-4", "mistral-small-4.serve.longctx-backlog",
                "moe_intermediate_size", "n_routed_experts", 128, True),
    "nemotron": ("nemotron-3-nano", "nemotron-3-nano.serve.reason-backlog",
                 "moe_intermediate_size", "n_routed_experts", 128, False),
    "mellum": ("mellum2-12b-a2.5b", "mellum2-12b-a2.5b.serve.mixed-backlog",
               "moe_intermediate_size", "num_experts", 64, True),
}
CLAMP_K, CLAMP_N = 2048, 1024


def family_shapes(name: str) -> dict:
    """{"held", "of", "top_k", "lanes", "chunk", "matmuls": {"up": (k, n,
    transposed), "down": ...}} of a family, from the benchmark's files."""
    config, cell, width, held, of, gated = FAMILIES[name]
    with open(os.path.join(ROOT, "benchmark", "configs", f"{config}.json")) as fh:
        cfg = json.load(fh)
    with open(os.path.join(ROOT, "benchmark", "workloads", f"{cell}.json")) as fh:
        work = json.load(fh)
    d, f = cfg["hidden_size"], cfg[width]
    return {
        "held": cfg[held], "of": of, "top_k": cfg["num_experts_per_tok"],
        "lanes": work["engine"]["max_batch_size"],
        "chunk": work["engine"].get("prefill_chunk", work["traffic"]["prompt_len"]["hi"]),
        # gated: gate and up side by side, [E, d, 2f]; else up alone and transposed, [E, f, d]
        "matmuls": {"up": (d, 2 * f, False) if gated else (d, f, True), "down": (f, d, False)},
    }


def device_ms(fn, args, iters: int) -> tuple[float, float]:
    """(ms a call of the kernel, ms a call of every other device
    operation of the jit: the group metadata, a pad) from a trace."""
    from flash_kernel_check import device_ms_by_op  # the sibling script's reduction

    by = device_ms_by_op(fn, args, iters)
    kernel = sum(ms for fam, ms in by.items() if fam.startswith("moe_gmm"))
    return kernel, sum(by.values()) - kernel


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", default=ROOT)
    ap.add_argument("--label", default="change")
    ap.add_argument("--families", default=",".join(FAMILIES))
    ap.add_argument("--tokens", default="decode,chunk",
                    help="token counts: 'decode' the cell's lanes, 'chunk' its largest prompt chunk")
    ap.add_argument("--matmuls", default="up,down")
    ap.add_argument("--tilings", default="chosen;clamp", help="';' between tilings")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.repo))
    sys.path.insert(1, ROOT)  # benchmark/ is this checkout's
    sys.path.insert(2, os.path.join(ROOT, "scripts"))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import moe

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": f"needs a TPU, found {dev.platform}"}))
        return 1
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as fh:
        peak = json.load(fh)[dev.device_kind]
    rng = np.random.default_rng(args.seed)
    result = {"label": args.label, "repo": os.path.abspath(args.repo), "iters": args.iters,
              "device": {"platform": dev.platform, "kind": dev.device_kind}, "rows": []}
    print(f"{'family':9}{'matmul':6}{'rows':>7}{'held':>6}{'hit':>4}  {'(k, n)':14}{'tiling':>20}"
          f"{'tile MB':>8}{'ms':>8}{'roof %':>8}{'other ms':>9}")
    for fam in args.families.split(","):
        shapes = family_shapes(fam)
        E = shapes["held"]
        for tokens in args.tokens.split(","):
            tokens = {"decode": shapes["lanes"], "chunk": shapes["chunk"]}.get(tokens) or int(tokens)
            m = tokens * shapes["top_k"]
            expert = rng.integers(0, shapes["of"], m)
            sizes = np.bincount(expert[expert < E], minlength=E).astype(np.int32)
            held, hit = int(sizes.sum()), int((sizes > 0).sum())
            group_sizes = jnp.asarray(sizes)
            for which in args.matmuls.split(","):
                k, n, transposed = shapes["matmuls"][which]
                rows = jax.random.normal(jax.random.PRNGKey(args.seed), (m, k), jnp.bfloat16)
                w = 0.02 * jax.random.normal(jax.random.PRNGKey(args.seed + 1),
                                             (E, n, k) if transposed else (E, k, n), jnp.bfloat16)
                least_s = max((hit * k * n + held * (k + n)) * 2 / peak["hbm_bytes_per_s"],
                              2.0 * held * k * n / peak["bf16_flops_per_s"])
                for spec in args.tilings.split(";"):
                    if spec == "chosen":
                        tiling = None
                        shown = getattr(moe, "gmm_tiling", lambda *a: "?")(m, k, n)
                    elif spec == "clamp":
                        shown = tiling = (moe._tile_rows(m), min(CLAMP_K, k), min(CLAMP_N, n))
                    else:
                        tm, tk, tn = (int(x) for x in spec.split(","))
                        shown = tiling = (tm or moe._tile_rows(m), tk or k, tn or n)
                    row = {"family": fam, "matmul": which, "rows": m, "held_rows": held, "experts_hit": hit,
                           "k": k, "n": n, "transposed": transposed, "spec": spec, "tiling": shown}
                    try:
                        fn = functools.partial(moe.moe_gmm, tiling=tiling, transposed=transposed)
                        jax.block_until_ready(fn(rows, w, group_sizes))
                        ms, other = device_ms(fn, (rows, w, group_sizes), args.iters)
                        row.update(ms=ms, other_ms=other, roof_pct=100 * least_s * 1e3 / ms)
                    except Exception as e:  # the compiler's refusal of a tiling is a row, not the end
                        row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
                    result["rows"].append(row)
                    tile_mb = shown[1] * shown[2] * 2 / 2**20 if isinstance(shown, tuple) else float("nan")
                    tail = (f"{row['ms']:8.4f}{row['roof_pct']:8.1f}{row['other_ms']:9.4f}"
                            if "ms" in row else "  " + row["error"][:80])
                    print(f"{fam:9}{which:6}{m:7d}{held:6d}{hit:4d}  {str((k, n)):14}{str(shown):>20}"
                          f"{tile_mb:8.2f}{tail}", flush=True)
                del rows, w
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/gmm_tile_check.{args.label}.json", "w") as fh:
        json.dump(result, fh, indent=1)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())

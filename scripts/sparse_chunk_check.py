"""A prompt chunk's block-sparse attention alone: what its tiles' selection
costs beside their key loop, compiled and on the chip.

    python scripts/sparse_chunk_check.py listing [--repo DIR]
    python scripts/sparse_chunk_check.py time [--repo DIR] [--label change]
        [--starts 0,4096,8192,28672] [--iters 5] [--w-block N]

`ray_tpu.ops.block_sparse.sparse_chunk_attention` in a jit of its own at
the shapes of a `minicpm-sala.serve.longdoc-backlog` chunk (SHAPE below:
4,096 queries of 2 K/V heads x 16, a context with room for 37,888
positions, bf16), `start` a traced scalar as the engine passes it: one
program whatever the chunk's position.

`listing` needs no chip: the function is compiled for a described v5e and
the compiler's `estimated_cycles` of every instruction under a named
scope are summed by the scope and the branch it lies in: `sala.select`
(a `cond`'s branches apart, of which a tile of 512 queries runs one; a
loop's body counts once however often it runs) against `sala.sparse`
(the loop over key blocks: its body runs once a block of 1,024 keys).
An estimate of the compiler's, not a time.  It also holds the one thing
the selection's speed rests on that no test can see: the top-k's `sort`
under `sala.select` has to sort with a query a lane (`SORT_WANTED`:
along dimension 0 of a `{1,0:T(8,128)}` array; across lanes it costs
2.5 ms a tile for 0.2).  Exit code 1 where a checkout that selects by a
tile's position compiles to another.

`time` needs the chip: device ms a call at each `--starts`, the union of
the device's busy intervals over a profiler trace of `--iters` calls, and
the operation families under it (a `while` encloses its body's `fusion`s:
they do not add up); the counts the function returns and a hash of `o`, so
that two checkouts' outputs can be told equal bit for bit.  Run once a
checkout in ONE call, the parent's first (`--repo _scratch/parent --label
parent`): a run that finds the other label's file prints both side by side.
`--w-block` sets the windows a step of the selection scores
(`block_sparse._W_BLOCK`, 512) for that run.

Prints a table, then one JSON object, and writes it to
`chiprun_out/sparse_chunk_check.<label>.json`.  No benchmark cell and no
test runs this.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a chunk's queries, K/V heads, query heads a K/V head, head size; the
# context's room: 2,112 pages of 16 (max_model_len 33,792) and the chunk's
SHAPE = {"T": 4096, "G": 2, "R": 16, "d": 128, "C": 37888}


def chunk_attention(repo: str):
    """(the jitted function of (q, ctx_k, ctx_v, ck, start, n_valid), the
    preset's config, the module `ops/block_sparse.py`) of the checkout at
    `repo`."""
    sys.path.insert(0, os.path.abspath(repo))
    import jax

    from ray_tpu.models.minicpm_sala import MiniCPMSalaConfig
    from ray_tpu.ops import block_sparse

    sp = MiniCPMSalaConfig.minicpm_sala_16l()
    fn = jax.jit(lambda *a: block_sparse.sparse_chunk_attention(*a, sp))
    return fn, sp, block_sparse


def cycles_by_scope(text: str) -> dict:
    """{(scope, branch): [instructions, estimated cycles]} over the compiled
    text's instructions that carry an estimate and lie under a named scope;
    the branch is the innermost `branch_<i>_fun` after the scope."""
    out: dict = {}
    for ln in text.splitlines():
        cycles = re.search(r'estimated_cycles":"(\d+)"', ln)
        name = re.search(r'op_name="([^"]*)"', ln)
        scope = name and re.search(r"/(sala\.\w+)/(.*)", name.group(1))
        if not cycles or not scope:
            continue
        branches = re.findall(r"branch_\d+_fun", scope.group(2))
        tally = out.setdefault((scope.group(1), "/".join(branches)), [0, 0])
        tally[0] += 1
        tally[1] += int(cycles.group(1))
    return out


SORT_WANTED = re.compile(r"= \(?f32\[\d+,\d+\]\{1,0:T\(8,128\)[^ ]* .*\bsort\(.*dimensions=\{0\}")


def sorts_across_lanes(text: str) -> list:
    """The `sort` instructions under `sala.select` that are not SORT_WANTED."""
    sorts = [ln.strip() for ln in text.splitlines() if " sort(" in ln and "sala.select" in ln]
    return [ln for ln in sorts if not SORT_WANTED.search(ln)] or ([] if sorts else ["no sort under sala.select"])


def listing(args) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    fn, sp, block_sparse = chunk_attention(args.repo)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    T, G, R, d, C = (SHAPE[k] for k in "TGRdC")

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    compiled = fn.lower(arr((T, G, R, d), jnp.bfloat16), arr((C, G, d), jnp.bfloat16), arr((C, G, d), jnp.bfloat16),
                        arr((C // sp.kernel_stride, G, d), jnp.float32), arr((), jnp.int32),
                        arr((), jnp.int32)).compile()
    text = compiled.as_text()
    print(f"sparse_chunk_attention {SHAPE}, compiled for {topo.devices[0].device_kind}: "
          f"temporaries {compiled.memory_analysis().temp_size_in_bytes:,} B, {len(text):,} characters")
    print(f"{'scope':14}{'branch':36}{'instructions':>13}{'estimated cycles':>18}")
    for (scope, branch), (n, cycles) in sorted(cycles_by_scope(text).items()):
        print(f"{scope:14}{branch or '-':36}{n:13d}{cycles:18,d}")
    if args.text:
        with open(args.text, "w") as fh:
            fh.write(text)
    bad = sorts_across_lanes(text) if hasattr(block_sparse, "_W_BLOCK") else []  # the parent's sort is its own
    for ln in bad:
        print(f"NOT a sort with a query a lane: {ln[:200]}")
    return 1 if bad else 0


def time_starts(args) -> int:
    fn, sp, block_sparse = chunk_attention(args.repo)
    if args.w_block:
        block_sparse._W_BLOCK = args.w_block
    sys.path.insert(1, ROOT)  # benchmark/ is this checkout's
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import trace_reduce

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": f"needs a TPU, found {dev.platform}"}))
        return 1
    T, G, R, d, C = (SHAPE[k] for k in "TGRdC")
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    q = jax.random.normal(keys[0], (T, G, R, d), jnp.bfloat16)
    ctx_k, ctx_v = (jax.random.normal(k, (C, G, d), jnp.bfloat16) for k in keys[1:])
    ck = jax.jit(lambda k: block_sparse.compress_keys(k, sp))(ctx_k)
    result = {"label": args.label, "repo": os.path.abspath(args.repo), "iters": args.iters, "shape": SHAPE,
              "device": {"platform": dev.platform, "kind": dev.device_kind}, "rows": []}
    print(f"{'start':>7}{'ms':>10}  {'counts':44}{'o':18}families (ms a call)")
    for start in (int(s) for s in args.starts.split(",")):
        xs = (q, ctx_k, ctx_v, ck, jnp.int32(start), jnp.int32(T))
        o, *counts = jax.block_until_ready(fn(*xs))
        with tempfile.TemporaryDirectory() as logdir:
            with jax.profiler.trace(logdir):
                for _ in range(args.iters):
                    y = fn(*xs)
                jax.block_until_ready(y)
            planes = trace_reduce.load(trace_reduce.find_xplane(logdir))
        facts = trace_reduce.reduce(planes)  # the benchmark's own reduction
        by = {fam: s * 1e3 / args.iters for fam, s in list(facts["op_seconds"].items())[:6]}
        busy_ms = facts["busy_s_device0"] * 1e3 / args.iters
        row = {"start": start, "ms": busy_ms, "counts": np.concatenate([np.ravel(c) for c in counts]).tolist(),
               "o_sha": hashlib.sha256(np.asarray(o.astype(jnp.float32)).tobytes()).hexdigest()[:16],
               "device_ms_by_family": by}
        result["rows"].append(row)
        print(f"{start:7d}{busy_ms:10.3f}  {str(row['counts']):44}{row['o_sha']:18}"
              + " ".join(f"{k}={v:.2f}" for k, v in by.items()), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/sparse_chunk_check.{args.label}.json", "w") as fh:
        json.dump(result, fh, indent=1)
    other = "parent" if args.label != "parent" else "change"
    if os.path.exists(f"chiprun_out/sparse_chunk_check.{other}.json"):
        with open(f"chiprun_out/sparse_chunk_check.{other}.json") as fh:
            theirs = {r["start"]: r for r in json.load(fh)["rows"]}
        print(f"\n{'start':>7}{other + ' ms':>12}{args.label + ' ms':>12}  same o")
        for row in result["rows"]:
            if row["start"] in theirs:
                t = theirs[row["start"]]
                print(f"{row['start']:7d}{t['ms']:12.3f}{row['ms']:12.3f}  {t['o_sha'] == row['o_sha']}")
    print(json.dumps(result))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="what", required=True)
    ls = sub.add_parser("listing")
    ls.add_argument("--repo", default=ROOT)
    ls.add_argument("--text", default="", help="write the compiled text here")
    tm = sub.add_parser("time")
    tm.add_argument("--repo", default=ROOT)
    tm.add_argument("--label", default="change")
    tm.add_argument("--starts", default="0,4096,8192,28672")
    tm.add_argument("--iters", type=int, default=5)
    tm.add_argument("--seed", type=int, default=0)
    tm.add_argument("--w-block", type=int, default=0, help="windows a step of the selection (default: the module's)")
    args = ap.parse_args()
    return listing(args) if args.what == "listing" else time_starts(args)


if __name__ == "__main__":
    sys.exit(main())

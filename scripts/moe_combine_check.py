"""Where an expert layer's combine spends its bytes, and what it costs on the chip.

    python scripts/moe_combine_check.py listing [--repo DIR] [--preset P]
        [--program serve_prefill] [--scope moe.combine] [--min-mb 1]
    python scripts/moe_combine_check.py time [--repo DIR] [--label change]
        [--shapes granite.chunk,...] [--iters 10]

`listing` needs no chip: a preset's `serve_prefill` (or `serve_decode`) at
its cell's shapes (`scripts/serve_program_hashes.py`: CELLS) is compiled
for a described v5e and every instruction of the entry computation whose
`op_name` lies under `--scope` is listed with its result and the bytes it
reads and writes, in the order of the schedule; then a
total for each named scope of the program (`--scope ""` lists them all).
Bytes are of the arrays as the chip lays them out: a dim under a tile
`T(8,128)` is rounded up to whole tiles, so a `[2048, 10, 4096]` float32
counts its 16 rows a token.  A gather is charged the rows it fetches, not
the whole operand.  It is a reader by named scope for the COMPILED program;
the device trace has none (PERF.md section 7).

`time` needs the chip: `moe_experts` alone, each shape in a jit of its own,
at the four families' chunk shapes and decode shapes (SHAPES below), device
ms a call from a profiler trace reduced as the benchmark reduces its own:
the whole call, the two `moe_gmm` kernels, and the rest (routing, gathers,
the combine).  `y` is held against a float32 loop over the pairs in numpy
(largest distance) and hashed, so that two checkouts' results can be told
equal bit for bit; `computed` against the held pairs.  Run once a checkout
in ONE call, the parent's first (`--repo _scratch/parent --label parent`):
a run that finds the other label's file prints both side by side.

Prints a table, then one JSON object, and writes it to
`chiprun_out/moe_combine_check.<label>.json`.  No benchmark cell and no
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

# name: tokens, experts a token, d, an expert's width, experts held, experts
# routed over, gated.  The chunk shapes are the cells' largest chunk programs
# (OLMoE's and Nemotron-H's prompts are short: their largest common bucket),
# the decode shapes their lanes (benchmark/workloads/*.json: engine).
SHAPES = {
    "granite.chunk": (2048, 10, 4096, 768, 36, 72, True),
    "mistral.chunk": (4096, 4, 4096, 2048, 32, 128, True),
    "nemotron.chunk": (896, 6, 2688, 1856, 32, 128, False),
    "olmoe.chunk": (768, 8, 2048, 1024, 64, 64, True),
    "granite.decode": (32, 10, 4096, 768, 36, 72, True),
    "nemotron.decode": (128, 6, 2688, 1856, 32, 128, False),
    "mistral.decode": (48, 4, 4096, 2048, 32, 128, True),
    "olmoe.decode": (32, 8, 2048, 1024, 64, 64, True),
}

_ITEM = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "f32": 4, "s32": 4, "u32": 4,
         "s64": 8, "u64": 8, "f64": 8}
_ARRAY = re.compile(r"\b(%s)\[([0-9,]*)\](?:\{([^}]*)\})?" % "|".join(_ITEM))
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")
_NO_TRAFFIC = ("bitcast", "get-tuple-element", "constant", "parameter", "tuple", "copy-start", "copy-done")


def laid_out_bytes(shapes: str) -> int:
    """Bytes of every array named in an HLO result type, each dim under the
    layout's first tile rounded up to whole tiles."""
    total = 0
    for dtype, dims, layout in _ARRAY.findall(shapes):
        dims = [int(x) for x in dims.split(",") if x]
        tile = re.search(r"T\(([0-9,]+)\)", layout or "")
        if tile and dims:
            order = [int(x) for x in layout.split(":")[0].split(",") if x] or list(range(len(dims)))[::-1]
            extents = [int(x) for x in tile.group(1).split(",")]
            # the tile's last extent covers the minor-most dim, and so on outwards
            for dim, extent in zip(order, reversed(extents)):
                dims[dim] = -(-dims[dim] // extent) * extent
            second = re.search(r"T\([0-9,]+\)\(([0-9]+),1\)", layout)
            if second and len(extents) > 1 and len(order) > 1:  # rows packed in pairs (bf16) or fours
                rows = extents[-2] * int(second.group(1))
                dims[order[1]] = -(-dims[order[1]] // rows) * rows
        n = 1
        for x in dims:
            n *= x
        total += n * _ITEM[dtype]
    return total


def entry_instructions(text: str) -> list[tuple[str, str, str, list[str], str]]:
    """(name, result type, opcode, operand names, op_name) of the entry
    computation's instructions, in schedule order."""
    lines = text.splitlines()
    start = next(i for i, ln in enumerate(lines) if ln.startswith("ENTRY"))
    out = []
    for ln in lines[start + 1:]:
        if ln.startswith("}"):
            break
        m = _INSTRUCTION.match(ln)
        if m:
            scope = re.search(r'op_name="([^"]*)"', ln)
            operands = re.findall(r"%([\w.\-]+)", m.group(4).split(")", 1)[0])
            out.append((m.group(1), m.group(2), m.group(3), operands, scope.group(1) if scope else ""))
    return out


def scope_of(op_name: str) -> str:
    """The innermost `family.part` named scope of an op_name ('' if none)."""
    parts = [p for p in op_name.split("/") if re.fullmatch(r"[a-z0-9_]+\.[a-z0-9_.]+", p)]
    return parts[-1] if parts else ""


def traffic(text: str) -> list[dict]:
    """One row an instruction of the entry computation that moves bytes."""
    instructions = entry_instructions(text)
    result = {name: shape for name, shape, *_ in instructions}
    rows = []
    for name, shape, opcode, operands, op_name in instructions:
        if opcode in _NO_TRAFFIC:
            continue
        written = laid_out_bytes(shape)
        read = sum(laid_out_bytes(result.get(o, "")) for o in operands)
        if op_name.endswith("/gather") and operands:  # fetches the rows it writes, not the operand whole
            read = read - laid_out_bytes(result.get(operands[0], "")) + written
        rows.append({"name": name, "opcode": opcode, "result": shape, "read": read, "written": written,
                     "scope": scope_of(op_name), "op": op_name.rsplit("/", 1)[-1]})
    return rows


def listing(args) -> int:
    repo = os.path.abspath(args.repo)
    os.chdir(repo)  # serve_program_hashes takes ray_tpu from the working directory
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    import serve_program_hashes as programs

    lowered = programs.lowered(args.preset)
    if lowered is None:
        print(json.dumps({"error": f"{repo} has no preset {args.preset}"}))
        return 1
    compiled = lowered[args.program].compile()
    text = compiled.as_text()
    rows = traffic(text)
    print(f"{args.preset} {args.program}, compiled for {programs.topo.devices[0].device_kind}: "
          f"temporaries {compiled.memory_analysis().temp_size_in_bytes:,} B")
    print(f"{'instruction':34}{'op':22}{'result':52}{'read MB':>9}{'written MB':>11}")
    for r in rows:
        if args.scope in r["scope"] and r["read"] + r["written"] >= args.min_mb * 1e6:
            print(f"{r['name'][:33]:34}{r['op'][:21]:22}{r['result'][:51]:52}{r['read'] / 1e6:9.1f}{r['written'] / 1e6:11.1f}")
    by_scope: dict[str, list[int]] = {}
    for r in rows:
        tally = by_scope.setdefault(r["scope"] or "(no scope)", [0, 0])
        tally[0] += 1
        tally[1] += r["read"] + r["written"]
    print(f"\n{'scope':28}{'instructions':>13}{'GB moved':>10}")
    for scope, (n, moved) in sorted(by_scope.items(), key=lambda kv: -kv[1][1]):
        print(f"{scope:28}{n:13d}{moved / 1e9:10.3f}")
    if args.text:
        with open(args.text, "w") as fh:
            fh.write(text)
    return 0


def reference(h, top_p, top_e, wgu, wd, gated):
    """The layer by a plain loop over the held experts (0 .. len(wgu) - 1) in
    float32, numpy, rounded to bfloat16 where the program rounds."""
    import numpy as np

    h = h.astype(np.float32)
    y = np.zeros_like(h)
    for e in range(len(wgu)):
        tok, j = np.nonzero(top_e == e)
        if not len(tok):
            continue
        x = h[tok]
        if gated:
            gate, up = np.split(bf16(x @ wgu[e].astype(np.float32)), 2, axis=-1)
            mid = bf16(gate / (1 + np.exp(-gate)) * up)
        else:
            mid = bf16(np.square(np.maximum(bf16(x @ wgu[e].astype(np.float32).T), 0)))
        np.add.at(y, tok, bf16(mid @ wd[e].astype(np.float32)) * top_p[tok, j][:, None])
    return y


def bf16(x):
    """x rounded to bfloat16, in float32."""
    import ml_dtypes
    import numpy as np

    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


def time_shapes(args) -> int:
    sys.path.insert(0, os.path.abspath(args.repo))
    sys.path.insert(1, ROOT)  # benchmark/ is this checkout's
    sys.path.insert(2, os.path.join(ROOT, "scripts"))

    import jax
    import jax.numpy as jnp
    import numpy as np
    from flash_kernel_check import device_ms_by_op  # the sibling script's reduction

    from ray_tpu.ops import moe

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": f"needs a TPU, found {dev.platform}"}))
        return 1
    result = {"label": args.label, "repo": os.path.abspath(args.repo), "iters": args.iters,
              "device": {"platform": dev.platform, "kind": dev.device_kind}, "rows": []}
    print(f"{'shape':17}{'pairs':>7}{'held':>7}{'computed':>9}{'ms':>9}{'gmm ms':>9}{'rest ms':>9}{'far':>10}  y")
    for name in args.shapes.split(","):
        T, k, d, f, E, of, gated = SHAPES[name]
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
        h = jax.random.normal(keys[0], (T, d), jnp.bfloat16)
        scores = jax.random.normal(keys[1], (T, of), jnp.float32)
        top_p, top_e = jax.lax.top_k(jax.nn.softmax(scores), k)
        wgu = 0.02 * jax.random.normal(keys[2], (E, d, 2 * f) if gated else (E, f, d), jnp.bfloat16)
        wd = 0.02 * jax.random.normal(keys[3], (E, f, d), jnp.bfloat16)
        held = None if E == of else (0, E)
        fn = jax.jit(lambda *a: moe.moe_experts(*a, held=held, gated=gated))
        xs = (h, top_p, top_e.astype(jnp.int32), wgu, wd)
        y, counters = jax.block_until_ready(fn(*xs))
        by = device_ms_by_op(fn, xs, args.iters)
        gmm = sum(ms for fam, ms in by.items() if fam.startswith("moe_gmm"))
        want = reference(*(np.asarray(x) for x in xs), gated)
        got = np.asarray(y.astype(jnp.float32))
        row = {"shape": name, "pairs": T * k, "held_pairs": int((np.asarray(top_e) < E).sum()),
               "computed": int(counters[0]), "ms": sum(by.values()), "gmm_ms": gmm, "rest_ms": sum(by.values()) - gmm,
               "far": float(np.abs(got - want).max()), "largest": float(np.abs(want).max()),
               "y_sha": hashlib.sha256(got.tobytes()).hexdigest()[:16],
               "device_ms_by_op": by}
        result["rows"].append(row)
        print(f"{name:17}{row['pairs']:7d}{row['held_pairs']:7d}{row['computed']:9d}{row['ms']:9.4f}{gmm:9.4f}"
              f"{row['rest_ms']:9.4f}{row['far']:10.2e}  {row['y_sha']}", flush=True)
        del h, wgu, wd, xs, y
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/moe_combine_check.{args.label}.json", "w") as fh:
        json.dump(result, fh, indent=1)
    other = "parent" if args.label != "parent" else "change"
    if os.path.exists(f"chiprun_out/moe_combine_check.{other}.json"):
        with open(f"chiprun_out/moe_combine_check.{other}.json") as fh:
            theirs = {r["shape"]: r for r in json.load(fh)["rows"]}
        print(f"\n{'shape':17}{other + ' ms':>11}{args.label + ' ms':>11}{'rest ms':>9}{'rest ms':>9}  same y")
        for row in result["rows"]:
            if row["shape"] in theirs:
                t = theirs[row["shape"]]
                print(f"{row['shape']:17}{t['ms']:11.4f}{row['ms']:11.4f}{t['rest_ms']:9.4f}{row['rest_ms']:9.4f}"
                      f"  {t['y_sha'] == row['y_sha']}")
    print(json.dumps(result))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="what", required=True)
    ls = sub.add_parser("listing")
    ls.add_argument("--repo", default=ROOT)
    ls.add_argument("--preset", default="granite_4_0_h_small_10l_ep2")
    ls.add_argument("--program", default="serve_prefill", choices=("serve_prefill", "serve_decode"))
    ls.add_argument("--scope", default="moe.combine")
    ls.add_argument("--min-mb", type=float, default=1.0)
    ls.add_argument("--text", default="", help="write the compiled text here")
    tm = sub.add_parser("time")
    tm.add_argument("--repo", default=ROOT)
    tm.add_argument("--label", default="change")
    tm.add_argument("--shapes", default=",".join(SHAPES))
    tm.add_argument("--iters", type=int, default=10)
    tm.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    return listing(args) if args.what == "listing" else time_shapes(args)


if __name__ == "__main__":
    sys.exit(main())

"""GLM-5's two decode kernels alone on the chip, and the choice between
them: device ms a call, share of the roof, distance from the
``jax.numpy`` paths; and (``--mode chunk``) the index and the choice of
one tile of a prompt chunk's queries, in the forms the choice could take.

    python scripts/dsa_decode_check.py [--mode decode|chunk] [--contexts 4096,16384,36000] [--iters 24] [--seed 0]

At the shape of ``glm-5.serve.longrepo-backlog`` (the sizes from
``benchmark/configs/glm-5.json``; lanes, pages and pool from
``benchmark/workloads/``): 20 lanes over pools of six layers' latent rows
and index keys, pages of 64 handed out in a shuffled order, the layer
going round as the engine's six calls a step do.  A set of lanes for
each of ``--contexts``: every lane within 64 positions under it.

- ``dsa_index_paged_scores``: the kernel's events in a profiler trace of
  ``--iters`` calls (the benchmark's own reduction), the least time by
  ``benchmark.flops_dsa.index_scores_work``, and its largest distance
  from ``ops.attention``'s gather path over the cached positions.
- the choice (``ops.dsa.keep_mask`` over the lanes' scores, XLA):
  wall ms a call, device-bound, beside them.
- ``mla_sparse_paged_decode_attention``: the same for the kernel, by
  ``flops_dsa.sparse_decode_work`` (the CHOSEN rows' bytes: the kernel
  copies every page a lane holds and masks, so its share of the roof is
  at most the share of positions chosen); ``around_ms`` is the rest of
  the dispatch's device time a call (the mask's cast and reshape).

``--mode chunk``: one tile of ``ops.dsa.Q_TILE`` queries at the cell's
widths over the ``C`` a chunk program has (``max_model_len`` and room for
a chunk: 40,960 columns) whose last query stands at each of ``--reaches``
(2,048 / 4,096 / 16,384 / 36,864): device ms a call of the index scores
(``chunk_index_scores``: the ``[512, 32, 2048]`` float32 tiles), of the
choice over all ``C`` columns (``keep_mask`` without a reach), and of
each form that takes the reach: ``loop``, every pass a loop over the key
blocks (``keep_mask(..., blocks)``: the ties' running count a product
with a triangle of ones), ``loop.cumsum`` (the same with ``jnp.cumsum``
a block), and ``switch``, a ``lax.switch`` over static widths of the
full-width code; every form's mask held equal to the full-width one.
(Loops over slabs of 4,096 and 8,192 columns read what 2,048 read and
more: PERF.md section 5, PR 58.)  Then a decode step's choice
``[lanes, max_model_len]`` at ``--contexts``, with and without the
longest lane's reach.

Prints a table, then one JSON object, and writes it to
``chiprun_out/dsa_decode_check.json`` (``dsa_chunk_check.json``).  Needs
the TPU: in interpret mode a time says nothing.  No benchmark cell and no
test runs this.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
CONFIG = "glm-5"
CELL = "glm-5.serve.longrepo-backlog"


def cell_shape() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs", f"{CONFIG}.json")) as fh:
        cfg = json.load(fh)
    with open(os.path.join(ROOT, "benchmark", "workloads", f"{CELL}.json")) as fh:
        eng = json.load(fh)["engine"]
    return {"config": cfg, "lanes": eng["max_batch_size"], "block_size": eng["block_size"],
            "pool_tokens": eng["pool_tokens"], "max_model_len": eng["max_model_len"],
            "layers": cfg["num_hidden_layers"], "heads": cfg["num_attention_heads"],
            "v_width": cfg["kv_lora_rank"], "row": cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"], "width": 640,
            "index_heads": cfg["index_n_heads"], "index_dim": cfg["index_head_dim"], "topk": cfg["index_topk"],
            "prefill_chunk": eng["prefill_chunk"]}


def traced_ops(run, iters):
    """[[name, start_ns, dur_ns]] of device 0's operations in a trace of
    `iters` calls of run(i)."""
    import jax

    from benchmark import trace_reduce

    jax.block_until_ready(run(0))  # compiles
    with tempfile.TemporaryDirectory() as logdir:
        with jax.profiler.trace(logdir):
            jax.block_until_ready([run(i) for i in range(iters)])
        planes = trace_reduce.load(trace_reduce.find_xplane(logdir))
    ops = trace_reduce.device_ops(planes)
    return ops[min(ops)]


def traced(run, iters, pattern):
    """(device ms a call of the operations whose name starts with
    `pattern`, device ms a call of everything) from a trace of `iters`
    calls of run(i)."""
    from benchmark import trace_reduce

    ops = traced_ops(run, iters)
    named = [dur for name, _, dur in ops if trace_reduce.family(name).startswith(pattern)]
    if len(named) != iters:
        raise RuntimeError(f"{len(named)} {pattern} events in a trace of {iters} calls")
    return sum(named) / 1e6 / iters, sum(dur for *_, dur in ops) / 1e6 / iters


def device_ms(run, iters):
    """Device ms a call: the union of every device operation's interval
    in a trace of `iters` calls of run(i), over `iters`."""
    from benchmark import trace_reduce

    ops = traced_ops(run, iters)
    return trace_reduce.union_seconds([(start, start + dur) for _, start, dur in ops]) / 1e6 / iters


SWITCH_WIDTHS = (4096, 8192, 16384, 24576)  # and C: the static widths of the ``switch`` form


def switched_keep_mask(scores, valid, k, blocks):
    """The ``switch`` form: the full-width code on the narrowest of a
    handful of static widths that holds ``blocks`` key blocks (``valid``
    is False beyond them); ``valid`` itself where they hold at most k."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import dsa

    C = scores.shape[1]
    widths = [w for w in SWITCH_WIDTHS if w < C] + [C]

    def narrow(w):
        return lambda: jnp.pad(dsa.keep_mask(scores[:, :w], valid[:, :w], k), ((0, 0), (0, C - w)))

    reach = blocks * dsa.KEY_BLOCK
    at = jnp.where(dsa.counts_over(blocks, k), 1 + jnp.searchsorted(jnp.asarray(widths), reach), 0)
    return jax.lax.switch(at, [lambda: valid] + [narrow(w) for w in widths])


def chunk_mode(shape, reaches, contexts, iters, seed, timer=device_ms) -> dict:
    """The rows of ``--mode chunk`` (the module's docstring)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import dsa

    Hi, Di, k, lanes = shape["index_heads"], shape["index_dim"], shape["topk"], shape["lanes"]
    n, block = dsa.Q_TILE, dsa.KEY_BLOCK
    C = -(-(shape["max_model_len"] + shape["prefill_chunk"]) // block) * block
    keys = jax.random.split(jax.random.PRNGKey(seed % 2**31), 4)
    q_i = jax.random.normal(keys[0], (n, Hi, Di), jnp.bfloat16)
    w = jax.random.normal(keys[1], (n, Hi), jnp.float32) * (Hi * Di) ** -0.5
    k_ctx = jax.random.normal(keys[2], (C, Di), jnp.bfloat16)
    pos = jnp.arange(C)
    index = jax.jit(lambda b: dsa.chunk_index_scores(q_i, w, k_ctx, b))

    def plain_running(ties):
        return jnp.cumsum(ties, axis=-1, dtype=jnp.int32)

    forms = {"full": lambda s, v, b: dsa.keep_mask(s, v, k),
             "loop": lambda s, v, b: dsa.keep_mask(s, v, k, b),
             "loop.cumsum": lambda s, v, b: jax.lax.cond(
                 dsa.counts_over(b, k), lambda: dsa._keep_within(s, v, k, b, running=plain_running), lambda: v),
             "switch": lambda s, v, b: switched_keep_mask(s, v, k, b)}
    forms = {name: jax.jit(fn) for name, fn in forms.items()}
    rows = []
    for reach in reaches:
        blocks = jnp.int32(-(-reach // block))
        valid = pos[None, :] <= (reach - n + jnp.arange(n))[:, None]
        scores = index(blocks)
        row = {"reach": reach, "index_ms": timer(lambda i: index(blocks), iters)}
        want = np.asarray(forms["full"](scores, valid, blocks))
        for name, fn in forms.items():
            row[f"{name}_ms"] = timer(lambda i, fn=fn: fn(scores, valid, blocks), iters)
            row[f"{name}_equal"] = bool((np.asarray(fn(scores, valid, blocks)) == want).all())
        rows.append(row)
    # a decode step's choice: lanes within 64 positions under a context, the table's whole width
    width = shape["max_model_len"]
    rng = np.random.default_rng(seed)
    steps = []
    lane_scores = jax.random.normal(keys[3], (lanes, width), jnp.float32)
    full, bound = forms["full"], forms["loop"]
    for context in contexts:
        lengths = jnp.asarray(context - rng.integers(0, 64, lanes), jnp.int32)
        valid = jnp.arange(width)[None, :] <= lengths[:, None]
        blocks = -(-(lengths.max() + 1) // block)
        steps.append({"context": context, "blocks": int(blocks),
                      "full_ms": timer(lambda i: full(lane_scores, valid, blocks), iters),
                      "bound_ms": timer(lambda i: bound(lane_scores, valid, blocks), iters),
                      "bound_equal": bool((np.asarray(bound(lane_scores, valid, blocks))
                                           == np.asarray(full(lane_scores, valid, blocks))).all())})
    return {"tile": n, "columns": C, "topk": k, "rows": rows, "decode": steps}


def on_the_gather_path(fn, *args):
    """fn(*args) traced with ``jax.default_backend`` saying "cpu": an
    entry of ``ops.attention`` takes its kernel on a TPU, and its
    ``jax.numpy`` path is what it does elsewhere."""
    import jax

    backend, jax.default_backend = jax.default_backend, lambda: "cpu"
    try:
        return jax.jit(fn)(*args)
    finally:
        jax.default_backend = backend


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("decode", "chunk"), default="decode")
    ap.add_argument("--contexts", default="4096,16384,36000")
    ap.add_argument("--reaches", default="2048,4096,16384,36864")
    ap.add_argument("--iters", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import flops, flops_dsa
    from ray_tpu.ops import attention, dsa
    from ray_tpu.ops import pallas_dsa as module

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": f"needs a TPU, found {dev.platform}"}))
        return 1
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as fh:
        peak = json.load(fh)[dev.device_kind]
    shape = cell_shape()
    if args.mode == "chunk":
        result = {"device": {"platform": dev.platform, "kind": dev.device_kind}, "iters": args.iters,
                  **chunk_mode(shape, [int(r) for r in args.reaches.split(",")],
                               [int(c) for c in args.contexts.split(",")], args.iters, args.seed)}
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/dsa_chunk_check.json", "w") as fh:
            json.dump(result, fh, indent=1)
        names = [key[:-3] for key in result["rows"][0] if key.endswith("_ms")]
        print(f"{'reach':>8}" + "".join(f"{name + ' ms':>14}" for name in names) + "   equal")
        for r in result["rows"]:
            print(f"{r['reach']:8d}" + "".join(f"{r[name + '_ms']:14.4f}" for name in names)
                  + f"   {all(v for key, v in r.items() if key.endswith('_equal'))}")
        print(f"{'context':>8}{'blocks':>8}{'full ms':>12}{'bound ms':>12}   equal")
        for r in result["decode"]:
            print(f"{r['context']:8d}{r['blocks']:8d}{r['full_ms']:12.4f}{r['bound_ms']:12.4f}   {r['bound_equal']}")
        print(json.dumps(result))
        return 0
    lanes, bs, L, W = shape["lanes"], shape["block_size"], shape["layers"], shape["width"]
    Hi, Di, H, k = shape["index_heads"], shape["index_dim"], shape["heads"], shape["topk"]
    keys = jax.random.split(jax.random.PRNGKey(args.seed % 2**31), 8)
    slots = shape["pool_tokens"] + bs
    live = (jnp.arange(W) < shape["row"]).astype(jnp.bfloat16)
    rows = jax.random.normal(keys[0], (L, slots, W), jnp.bfloat16) * live
    index_k = jax.random.normal(keys[1], (L, slots, Di), jnp.bfloat16)
    q_i = jax.random.normal(keys[2], (lanes, Hi, Di), jnp.bfloat16)
    w = jax.random.normal(keys[3], (lanes, Hi), jnp.float32) * (Hi * Di) ** -0.5
    k_self = jax.random.normal(keys[4], (lanes, Di), jnp.bfloat16)
    q = 0.25 * jax.random.normal(keys[5], (lanes, H, W), jnp.bfloat16) * live
    row_self = jax.random.normal(keys[6], (lanes, W), jnp.bfloat16) * live
    per_lane = -(-shape["max_model_len"] // bs)
    rng = np.random.default_rng(args.seed)
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind}, "iters": args.iters, "rows": []}
    sizes = dict(block_size=bs)
    for context in (int(c) for c in args.contexts.split(",")):
        lengths = (context - rng.integers(0, 64, lanes)).astype(np.int32)
        # twenty lanes at the longest context are more pages than the pool has: the order goes round
        order = rng.permutation(np.arange(1, shape["pool_tokens"] // bs + 1))
        tables, at = np.zeros((lanes, per_lane), np.int32), 0
        for lane, n in enumerate(lengths):
            held = -(-int(n) // bs)
            tables[lane, :held] = np.take(order, np.arange(at, at + held), mode="wrap")
            at += held
        tables, lens = jnp.asarray(tables), jnp.asarray(lengths)
        positions_scored = int(lengths.sum())

        index = jax.jit(functools.partial(attention.dsa_index_paged_scores, **sizes))
        index_ms, _ = traced(lambda i: index(q_i, w, k_self, index_k, jnp.int32(i % L), tables, lens),
                             args.iters, "dsa_index_paged_scores")
        scores = index(q_i, w, k_self, index_k, 1, tables, lens)
        want = on_the_gather_path(functools.partial(attention.dsa_index_paged_scores, **sizes),
                                  q_i, w, k_self, index_k, 1, tables, lens)
        cached = np.arange(per_lane * bs)[None, :] <= lengths[:, None]
        d_index = float(np.abs(np.asarray(scores) - np.asarray(want))[cached].max())

        valid = jnp.asarray(cached)
        choose = jax.jit(lambda s: dsa.keep_mask(s, valid, k))
        keep = jax.block_until_ready(choose(scores))
        t = time.perf_counter()
        jax.block_until_ready([choose(scores) for _ in range(args.iters)])
        choice_ms = (time.perf_counter() - t) * 1e3 / args.iters

        attend = jax.jit(functools.partial(attention.mla_sparse_paged_decode_attention, **sizes,
                                           v_width=shape["v_width"]))
        attend_ms, all_ms = traced(
            lambda i: attend(q, row_self, rows, jnp.int32(i % L), tables, keep, lens),
            args.iters, "mla_sparse_paged_decode_attention")
        got = attend(q, row_self, rows, 1, tables, keep, lens)
        want = on_the_gather_path(
            functools.partial(attention.mla_sparse_paged_decode_attention, **sizes, v_width=shape["v_width"]),
            q, row_self, rows, 1, tables, keep, lens)
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        attended = int((np.asarray(keep) & (np.arange(per_lane * bs)[None, :] < lengths[:, None])).sum())
        least_i = flops.least_seconds(flops_dsa.index_scores_work(shape["config"], positions_scored, lanes), peak)
        least_a = flops.least_seconds(flops_dsa.sparse_decode_work(shape["config"], attended, lanes), peak)
        result["rows"].append({
            "context": context, "positions_scored": positions_scored, "positions_attended": attended,
            "index_ms": index_ms, "index_roof_ms": least_i["seconds"] * 1e3,
            "index_roof_pct": 100 * least_i["seconds"] * 1e3 / index_ms, "index_distance": d_index,
            "choice_ms": choice_ms,
            "attend_ms": attend_ms, "around_ms": all_ms - attend_ms, "attend_roof_ms": least_a["seconds"] * 1e3,
            "attend_roof_pct": 100 * least_a["seconds"] * 1e3 / attend_ms,
            "attend_distance": {"max_abs": float(np.abs(got - want).max()), "ref_max_abs": float(np.abs(want).max())},
        })
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/dsa_decode_check.json", "w") as fh:
        json.dump(result, fh, indent=1)
    print(f"{'context':>8}{'index ms':>10}{'roof %':>8}{'|d|':>10}{'choice ms':>11}{'attend ms':>11}{'roof %':>8}"
          f"{'around ms':>11}{'|d|':>10}")
    for r in result["rows"]:
        print(f"{r['context']:8d}{r['index_ms']:10.4f}{r['index_roof_pct']:8.1f}{r['index_distance']:10.2e}"
              f"{r['choice_ms']:11.4f}{r['attend_ms']:11.4f}{r['attend_roof_pct']:8.1f}{r['around_ms']:11.4f}"
              f"{r['attend_distance']['max_abs']:10.2e}")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The Jamba2 cell's checks read on the chip in ONE process, without a
cluster (``scripts/mellum2_check_readings.py`` for this family): the
builder's readings for ``checks.logit_why`` of
``benchmark/workloads/jamba2-3b.serve.think-backlog.json``.

    python scripts/jamba2_check_readings.py [--seed N] [--tiny] [--only bf16,state_bf16,...]

Builds ``LLMEngine`` at the cell's sizes (the preset, lanes, pool, pages,
``max_model_len``), serves the cell's set-up requests (``checks.prompt_lens``,
``checks.max_tokens`` each, greedy, ids from the seed) through the engine's
own API, then calls the runner's own ``_rep_reference`` on what came back:
as configured (bf16), then with the reference told another model
(``state_bf16``, ``inner_norms_off``, ``no_dt_bias``,
``attention_one_layer_early``: ``serve_jamba2.wrong_reference``), then
with the program's weights rounded to float8_e4m3's mantissa (last: it
leaves them rounded).  Prints one JSON object a reading and writes all of
them to ``chiprun_out/jamba2_check_readings.<seed>.json``.  No benchmark cell
and no test runs this; ``--tiny`` rehearses it on the CPU.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

READINGS = ("bf16", "state_bf16", "inner_norms_off", "no_dt_bias", "attention_one_layer_early", "e4m3")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=2345678901)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--only", default=",".join(READINGS))
    args = ap.parse_args(argv)

    import jax

    from benchmark import spec, traffic
    from benchmark.runners import serve_jamba2 as runner
    from ray_tpu.serve.llm import LLMConfig, LLMEngine
    from ray_tpu.serve.llm.engine import FINISHED
    from ray_tpu.util.compile_cache import place_compile_cache

    place_compile_cache(ROOT)
    cell = spec.load_cell("jamba2-3b.serve.think-backlog")
    config = spec.load_config(cell["config"])
    eng_cfg, chk = cell["engine"], cell["checks"]
    lens, new, vocab = chk["prompt_lens"], chk["max_tokens"], config["vocab_size"]
    kw = dict(model=config["preset"], dtype=config["dtype"], max_batch_size=eng_cfg["max_batch_size"],
              block_size=eng_cfg["block_size"], num_blocks=eng_cfg["pool_tokens"] // eng_cfg["block_size"] + 1,
              max_model_len=eng_cfg["max_model_len"])
    if args.tiny:
        kw.update(model="jamba2_tiny", dtype="float32", max_batch_size=4, block_size=4, num_blocks=257,
                  max_model_len=256)
        lens, new, vocab = [3, 8, 21], 20, 256
    t0 = time.time()
    eng = LLMEngine(LLMConfig(seed=args.seed % (2**31 - 2), max_queue=64, **kw))
    print(f"[engine] {jax.devices()[0].device_kind} built in {time.time() - t0:.1f} s", flush=True)

    async def serve(requests):
        async def one(req):
            r = await eng.add_request(req["prompt"], max_tokens=req["max_tokens"])
            toks = []
            while True:
                ev = await r.out.get()
                if ev is FINISHED:
                    return toks
                toks.append(ev["token"])

        out = await asyncio.gather(*[one(r) for r in requests])
        await eng.stop()
        return out

    reqs = traffic.fixed_requests(lens, new, vocab, args.seed + 11)
    answers = asyncio.run(serve(reqs))
    sequences = [r["prompt"] + a for r, a in zip(reqs, answers)]
    print(f"[served] {[len(s) for s in sequences]} in {time.time() - t0:.1f} s", flush=True)
    rep = types.SimpleNamespace(callable=types.SimpleNamespace(engine=eng))
    out = {"seed": args.seed, "device": jax.devices()[0].device_kind}
    for name in args.only.split(","):
        t1 = time.time()
        ref = runner._rep_reference(rep, sequences, lens, None if name == "bf16" else name)
        ref.pop("by_position")
        ref["seconds"] = round(time.time() - t1, 1)
        out[name] = ref
        print(json.dumps({name: ref}), flush=True)
    stats = jax.devices()[0].memory_stats() or {}
    out["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
    print(json.dumps({"memory_peak_bytes": out["memory_peak_bytes"]}), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", f"jamba2_check_readings.{args.seed}.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

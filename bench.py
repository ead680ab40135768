"""Headline benchmark: GPT-2 training throughput, tokens/sec/chip.

This is the north-star metric from BASELINE.json ("Ray Train GPT-2
tokens/sec/chip").  The reference publishes no TPU numbers
(BASELINE.md: published = {}), so vs_baseline normalizes against the
reference's NCCL/GPU-era equivalent: ~51k tokens/sec/chip for GPT-2-small
with torch DDP on an A100-class device (6*N*tok/s at ~40% MFU of 312
TFLOPs bf16).  A v5e chip (197 TFLOPs bf16) at the same MFU would be
~0.63 of that; vs_baseline > 0.63 therefore means better MFU than the
reference stack.

Two throughput measurements, each in its own subprocess so exactly one
process owns the chip at a time (this parent never imports JAX):
  framework — the step inside JaxTrainer.fit(): a 1-worker group that
              holds the node's TPU as a lease.  This is `value`, the
              honest "what a user gets" figure.
  raw       — the SAME jitted train step driven directly (no framework),
              after the framework's cluster is gone.

The script measures a chip or fails: a run that does not land on a TPU
raises, and no record is printed for it.  See PERF_ANALYSIS.md for the
shape-limited roofline study.  ROADMAP.md Queue 1 item 1 replaces this
script and its five siblings with one benchmark.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

GPU_BASELINE_TOKENS_PER_SEC = 51000.0

# Shared measurement body: build the sharded GPT-2 train state, warm up,
# time `steps` steps.  Defines tok_s_chip + platform.  Used verbatim by
# both the raw and the in-framework runs so the overhead comparison
# compares exactly the same work.
_MEASURE_BODY = """
import time
import jax
import jax.numpy as jnp
import numpy as np
from ray_tpu.models import gpt2
from ray_tpu.parallel import create_mesh

platform = jax.devices()[0].platform
if platform != "tpu":
    raise RuntimeError(f"bench.py measures a TPU; JAX found {platform!r}")
n_dev = len(jax.devices())
cfg = gpt2.GPT2Config(max_seq_len=1024, remat=False)  # fits HBM at 124M/B16/T1024
B, T, steps = 16, 1024, 30

mesh = create_mesh({"dp": n_dev}, jax.devices())
opt = gpt2.make_adamw(lr=3e-4)
params, opt_state, specs = gpt2.make_sharded_train_state(cfg, mesh, opt)
step = gpt2.make_sharded_train_step(cfg, mesh, opt)
rng = np.random.default_rng(0)
toks = rng.integers(0, cfg.vocab_size, (B, T + 1), dtype=np.int32)
tokens, targets = jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])
for _ in range(3):
    params, opt_state, loss = step(params, opt_state, tokens, targets)
jax.block_until_ready(loss)
t0 = time.perf_counter()
for _ in range(steps):
    params, opt_state, loss = step(params, opt_state, tokens, targets)
jax.block_until_ready(loss)
dt = time.perf_counter() - t0
tok_s_chip = B * T * steps / dt / n_dev
"""

_RAW_SNIPPET = f"""
import json
{_MEASURE_BODY}
print("BENCH_RESULT " + json.dumps({{"tok_s_chip": tok_s_chip, "platform": platform}}))
"""

_FRAMEWORK_SNIPPET = f"""
import json
import ray_tpu
from ray_tpu import train
from ray_tpu.train import JaxTrainer, ScalingConfig

_BODY = {_MEASURE_BODY!r}

def train_loop(config):
    ns = {{}}
    exec(_BODY, ns)
    train.report({{"tok_s_chip": ns["tok_s_chip"], "platform": ns["platform"]}})

ray_tpu.init()
result = JaxTrainer(
    train_loop, scaling_config=ScalingConfig(num_workers=1, use_tpu=True)
).fit()
if result.error is not None:
    raise result.error
print("BENCH_RESULT " + json.dumps({{
    "tok_s_chip": result.metrics["tok_s_chip"], "platform": result.metrics["platform"],
}}))
ray_tpu.shutdown()
"""


def _child(argv: list, marker: str, *, timeout: float) -> dict:
    """Run one chip-owning stage as a subprocess and parse the JSON of
    its first stdout line that starts with `marker`; its failure is ours."""
    proc = subprocess.run(
        argv, capture_output=True, text=True, timeout=timeout,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    for line in proc.stdout.splitlines():
        if line.startswith(marker):
            return json.loads(line[line.index("{"):])
    raise RuntimeError(
        f"{argv[1]} (rc={proc.returncode}) produced no result:\n"
        f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}"
    )


def _run(snippet: str, *, timeout: float) -> dict:
    return _child([sys.executable, "-c", snippet], "BENCH_RESULT ", timeout=timeout)


def _run_ppo_bench(timeout: float) -> dict:
    """North-star metric #2 (RLlib PPO env-steps/s) via bench_rllib.py in
    its own subprocess (one chip owner at a time)."""
    rec = _child([sys.executable, "bench_rllib.py"], "{", timeout=timeout)
    return {
        "ppo_cartpole_env_steps_per_sec": rec["cartpole"]["env_steps_per_sec"],
        "ppo_pong_scale_env_steps_per_sec": rec["pong_scale"]["env_steps_per_sec"],
    }


def _record(fw: dict, raw: dict, extra: dict) -> dict:
    per_chip = fw["tok_s_chip"]
    rec = {
        "metric": "gpt2_small_train_tokens_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(per_chip / GPU_BASELINE_TOKENS_PER_SEC, 4),
        "on_tpu": True,
        "platform": fw["platform"],
        "raw_tokens_per_sec_per_chip": round(raw["tok_s_chip"], 1),
        "framework_overhead_pct": round(
            100 * (1.0 - per_chip / raw["tok_s_chip"]), 2),
    }
    rec.update(extra)
    return rec


def main():
    fw = _run(_FRAMEWORK_SNIPPET, timeout=900.0)
    raw = _run(_RAW_SNIPPET, timeout=600.0)
    extra: dict = {}
    if not os.environ.get("BENCH_SKIP_PPO"):
        extra.update(_run_ppo_bench(timeout=900.0))
    print(json.dumps(_record(fw, raw, extra)), flush=True)


if __name__ == "__main__":
    main()

"""ResNet-50 / CIFAR-10 training throughput on TPU (BASELINE.json
configs[1]: "Ray Train JaxTrainer ResNet-50 / CIFAR-10 (single v5e-8)").

The reference publishes no TPU numbers (BASELINE.md: published = {});
``vs_baseline`` normalizes MFU against the ~40% MFU the reference's
GPU-era torch-DDP ResNet stack typically achieves, i.e. vs_baseline =
measured_mfu / 0.40 — > 1.0 means better hardware utilization than the
reference stack, independent of chip generation.

FLOPs per step come from XLA's own cost model
(compiled.cost_analysis()["flops"]), not a hand formula, so MFU reflects
the program actually executed (bf16 convs, BatchNorm, SGD update).
Peak is taken as 197 TFLOPs bf16 (v5e); on CPU fallback MFU is omitted.

Prints ONE JSON line (same contract as bench.py).  Run standalone or
via BENCH_RESNET=1 environments; kept out of bench.py's critical path
so the flagship GPT-2 number never waits on this.
"""

from __future__ import annotations

import json
import time

# bf16 peak per chip by jax device_kind (Google Cloud documentation,
# "TPU v5e"); a device that is not listed is an error, not a default
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}
REFERENCE_STACK_MFU = 0.40


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from bench_common import provenance
    from ray_tpu.models import resnet

    peak = PEAK_BF16_FLOPS[provenance()["device_kind"]]
    n_dev = len(jax.devices())
    cfg = resnet.ResNetConfig.resnet50()
    B, steps = 512, 30

    variables = resnet.init_variables(cfg, image_shape=(1, 32, 32, 3))
    params, batch_stats = variables["params"], variables["batch_stats"]
    opt = optax.sgd(0.1, momentum=0.9)
    opt_state = opt.init(params)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((B, 32, 32, 3), np.float32))
    y = jnp.asarray(rng.integers(0, cfg.num_classes, B, np.int32))

    # AOT-compile once; cost_analysis reads the SAME executable that runs
    step = (
        jax.jit(resnet.make_train_step(cfg, opt), donate_argnums=(0, 1, 2))
        .lower(params, batch_stats, opt_state, x, y)
        .compile()
    )
    cost = step.cost_analysis()
    flops_per_step = float(cost.get("flops", 0.0)) if cost else 0.0

    for _ in range(3):
        params, batch_stats, opt_state, loss = step(params, batch_stats, opt_state, x, y)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, batch_stats, opt_state, loss = step(params, batch_stats, opt_state, x, y)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0

    images_s_chip = B * steps / dt / n_dev
    rec = {
        "metric": "resnet50_cifar10_train_images_per_sec_per_chip",
        "value": round(images_s_chip, 1),
        "unit": "images/s/chip",
        # platform provenance first-class: bench_gate refuses
        # cross-platform comparisons keyed on on_tpu
        **provenance(),
        "batch_size": B,
        "flops_per_step": flops_per_step,
    }
    mfu = flops_per_step * steps / dt / n_dev / peak
    rec["mfu"] = round(mfu, 4)
    rec["vs_baseline"] = round(mfu / REFERENCE_STACK_MFU, 4)
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()

"""Kimi-Linear's delta rule alone on the chip: the decode kernel, the
chunked form, the chunk kernel and the latent walk at the cell's shape;
device ms a call, share of the roof, distance from the ``jax.numpy``
paths.

    python scripts/kda_check.py [--lanes 256] [--context 4096] [--chunk 2048] [--iters 12] [--seed 0]

At the shape of ``kimi-linear-48b-a3b.serve.rollout-backlog`` (sizes from
``benchmark/configs/kimi-linear-48b-a3b.json``):

- ``kda_decode_step`` over ``--lanes`` states of ``[32, 128, 128]``
  float32, all running and with every fourth lane idle: wall ms a call
  of ``--iters`` chained calls (the state donated from call to call, as
  the engine's), the least time by ``benchmark.flops_kda.kda_step_work``,
  and the largest distance of state and output from ``ops.kda.kda_step``.
- ``kda_chunk`` (plain XLA) over ``--chunk`` positions: wall ms a call
  and its distance from the recurrence a position at a time
  (``kda_step`` under ``lax.scan``); its three parts alone (the two
  decayed products, the triangular solve, the carried scan) and the
  float32 temporaries its compiled text keeps outside VMEM.
- ``kda_chunk_scan`` (``ops/pallas_kda_chunk.py``) beside ``kda_chunk`` at
  every bucket of 64 to ``--chunk`` tokens: ms a layer (sixteen calls in
  one program, the state carried from call to call), both distances
  from the recurrence for both, and the kernel's share of the least time
  by ``benchmark.flops_kda.kda_chunk_work`` (the bf16 peak's: six-pass
  float32 products cannot reach it).
- ``mla_paged_decode_attention`` over ``--lanes`` lanes of ``--context``
  cached rows of 640 columns, 32 heads, in calls of
  ``pallas_mla_paged_attention.lanes_a_call`` lanes: wall ms a layer and
  the least time by ``benchmark.flops_mla.mla_decode_work``.

Wall times are device-bound (one dispatch, blocked on its result, the
dispatch's own 0.1 ms or so in them).  Prints one JSON object and writes
it to ``chiprun_out/kda_check.json``.  Needs the TPU: in interpret mode a
time says nothing.  No benchmark cell and no test runs this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def chunk_parts(kda, timed, q, k, v, a, beta, state):
    """``ops.kda.kda_chunk``'s three parts, each a program of its own over
    operands made before it: the two decayed products (the system's ``A``
    and the outputs' ``P``), the triangular solve, the carried scan.  ms a
    call; their sum is more than the whole's where XLA fuses across them."""
    import jax
    import jax.numpy as jnp

    T, H, dk = q.shape
    nb, cb = T // kda.BLOCK, kda.BLOCK

    def blocks(x):
        return jnp.moveaxis(x.astype(jnp.float32).reshape(nb, cb, *x.shape[1:]), 2, 1)

    qb, kb, vb, ab = blocks(q), blocks(k), blocks(v), blocks(a)
    bb = blocks(beta)[..., None]
    g = jnp.cumsum(ab, axis=-2)
    eg = jnp.exp(g)

    @jax.jit
    def products(qb, kb, g):
        return kda._decayed_products(kb, kb, g, diagonal=False), kda._decayed_products(qb, kb, g, diagonal=True)

    @jax.jit
    def solve(system, rhs):
        one = lambda m, r: jax.scipy.linalg.solve_triangular(m, r, lower=True, unit_diagonal=True)
        return jax.vmap(jax.vmap(one))(system, rhs)

    ms_products, (a_kk, p) = timed(products, qb, kb, g, n=4)
    system = jnp.eye(cb, dtype=jnp.float32) + bb * a_kk
    rhs = bb * jnp.concatenate([vb, kb * eg], axis=-1)
    ms_solve, tv_tk = timed(solve, system, rhs, n=4)
    ms_scan, _ = timed(jax.jit(kda._carried), state, tv_tk[..., :dk], tv_tk[..., dk:], p, qb * eg,
                       kb * jnp.exp(g[..., -1:, :] - g), g[..., -1, :], n=4)
    return {"products": ms_products, "solve": ms_solve, "scan": ms_scan}


def hbm_temporaries(text, least_mb=32):
    """The float32 arrays of at least `least_mb` MB that a compiled
    program's text names outside VMEM (``S(1)`` marks VMEM): shape -> MB,
    each shape once."""
    import re

    found = {}
    for m in re.finditer(r"f32\[([0-9,]+)\]\{[^}]*\}", text):
        if "S(1)" in m.group(0):
            continue
        size = 4
        for dim in m.group(1).split(","):
            size *= int(dim)
        if size >= least_mb * 2**20:
            found[m.group(1)] = size / 2**20
    return found


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--lanes", type=int, default=256)
    ap.add_argument("--context", type=int, default=4096)
    ap.add_argument("--chunk", type=int, default=2048)
    ap.add_argument("--iters", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import flops, flops_kda, flops_mla, spec
    from ray_tpu.ops import kda, pallas_kda, pallas_kda_chunk
    from ray_tpu.ops import pallas_mla_paged_attention as mla_kernel
    from ray_tpu.ops.attention import mla_paged_decode_attention

    config = spec.load_config("kimi-linear-48b-a3b")
    peak = spec.load_peaks()[jax.devices()[0].device_kind]
    lin = config["linear_attn_config"]
    H, d, B = lin["num_heads"], lin["head_dim"], args.lanes
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 8)
    out = {"device": jax.devices()[0].device_kind, "lanes": B}

    def timed(fn, *a, n=args.iters):
        r = fn(*a)
        jax.block_until_ready(r)
        t = time.perf_counter()
        for _ in range(n):
            r = fn(*a)
        jax.block_until_ready(r)
        return (time.perf_counter() - t) / n * 1e3, r

    # the decode kernel
    def unit(x):
        return (x / jnp.linalg.norm(x, axis=-1, keepdims=True)).astype(jnp.bfloat16)

    q, k = unit(jax.random.normal(ks[0], (B, H, d))), unit(jax.random.normal(ks[1], (B, H, d)))
    v = jax.random.normal(ks[2], (B, H, d)).astype(jnp.bfloat16)
    a = jax.random.uniform(ks[3], (B, H, d), minval=-2.0, maxval=-1e-3)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, H)))
    state0 = jax.random.normal(ks[5], (B, H, d, d))
    for name, active in (("all", jnp.ones(B, bool)), ("three_in_four", jnp.arange(B) % 4 != 3)):
        want_o, want_s = kda.kda_step(q, k, v, a, beta, state0, active)
        step = jax.jit(pallas_kda.kda_decode_step, donate_argnums=(5,))
        got_o, got_s = step(q, k, v, a, beta, state0 + 0.0, active)
        dist_o = float(jnp.abs(jnp.where(active[:, None, None], got_o.astype(jnp.float32) - want_o.astype(jnp.float32), 0)).max())
        dist_s = float(jnp.abs(got_s - want_s).max())
        state = state0 + 0.0
        jax.block_until_ready(state)
        t = time.perf_counter()
        for _ in range(args.iters):
            _, state = step(q, k, v, a, beta, state, active)
        jax.block_until_ready(state)
        ms = (time.perf_counter() - t) / args.iters * 1e3
        least = flops.least_seconds(flops_kda.kda_step_work(config, int(active.sum())), peak)["seconds"] * 1e3
        out["kda_decode_step." + name] = {"ms": ms, "least_ms": least, "roofline_pct": 100 * least / ms,
                                          "distance_o": dist_o, "distance_state": dist_s}

    # the chunked form: whole, and its three parts alone
    T = args.chunk
    qc, kc = unit(jax.random.normal(ks[0], (T, H, d))), unit(jax.random.normal(ks[1], (T, H, d)))
    vc = jax.random.normal(ks[2], (T, H, d)).astype(jnp.bfloat16)
    ac = jax.random.uniform(ks[3], (T, H, d), minval=-2.0, maxval=-1e-3)
    bc = jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    s0 = jax.random.normal(ks[6], (H, d, d))
    n = T - 37

    @jax.jit
    def recurrence(q, k, v, a, beta, s):
        def one(s, xs):
            o, s = kda.kda_step(*(x[None] for x in xs), s[None])
            return s[0], o[0]

        return jax.lax.scan(one, s, (q, k, v, a, beta))

    LAYERS = 16  # calls in one program, each from the state the last left: a small bucket's call is shorter than its dispatch

    def against_recurrence(fn, T):
        """ms a call over the first T positions (of LAYERS calls in one
        program), and the distance of one call's real rows and its state
        from the recurrence a position at a time."""
        n = T - 37
        xs = (qc[:T], kc[:T], vc[:T], ac[:T], bc[:T])

        @jax.jit
        def layers(q, k, v, a, beta, s, n):
            return jax.lax.fori_loop(0, LAYERS, lambda _, o_s: fn(q, k, v, a, beta, o_s[1], n), (v, s))

        ms, _ = timed(layers, *xs, s0, jnp.int32(n), n=4)
        o, s_end = fn(*xs, s0, jnp.int32(n))
        s_ref, o_ref = recurrence(*(x[:n] for x in xs), s0)
        return {"ms": ms / LAYERS,
                "distance_o": float(jnp.abs(o[:n].astype(jnp.float32) - o_ref.astype(jnp.float32)).max()),
                "distance_state": float(jnp.abs(s_end - s_ref).max())}

    chunk = jax.jit(kda.kda_chunk)
    out["kda_chunk"] = {"tokens": T, **against_recurrence(chunk, T), "flops": flops_kda.kda_chunk_work(config, T)["flops"]}
    # the kernel beside the form, a bucket at a time
    out["kda_chunk_scan"] = {}
    for bucket in (b for b in (64, 128, 256, 512, 1024, 2048) if b <= T):
        assert pallas_kda_chunk.kernel_takes(bucket, H, d, d)
        least = flops.least_seconds(flops_kda.kda_chunk_work(config, bucket), peak)["seconds"] * 1e3
        kernel = against_recurrence(pallas_kda_chunk.kda_chunk_scan, bucket)
        out["kda_chunk_scan"][str(bucket)] = {"kernel": kernel, "xla": against_recurrence(chunk, bucket),
                                              "least_ms": least, "kernel_pct_of_least": 100 * least / kernel["ms"]}
    out["kda_chunk"]["parts_ms"] = chunk_parts(kda, timed, qc, kc, vc, ac, bc, s0)
    text = chunk.lower(qc, kc, vc, ac, bc, s0, jnp.int32(n)).compile().as_text()
    out["kda_chunk"]["hbm_temporaries_mb"] = hbm_temporaries(text)

    # the latent walk
    W, kv, bs = 640, config["kv_lora_rank"], 64
    pages = -(-(args.context + 64) // bs)
    slots = (B * pages + 1) * bs
    pool = (0.1 * jax.random.normal(ks[7], (1, slots, W))).astype(jnp.bfloat16)
    tables = jnp.asarray(np.random.default_rng(args.seed).permutation(B * pages).reshape(B, pages) + 1, jnp.int32)
    lengths = jnp.full((B,), args.context, jnp.int32) - jnp.arange(B, dtype=jnp.int32) % 64
    qa = (0.1 * jax.random.normal(ks[0], (B, 32, W))).astype(jnp.bfloat16)
    row = (0.1 * jax.random.normal(ks[1], (B, W))).astype(jnp.bfloat16)
    attend = jax.jit(lambda q, r, p, t, n: mla_paged_decode_attention(q, r, p, 0, t, n, block_size=bs, v_width=kv))
    ms, _ = timed(attend, qa, row, pool, tables, lengths)
    work = flops_mla.mla_decode_work(config, int(lengths.sum()), B)
    least = flops.least_seconds(work, peak)["seconds"] * 1e3
    out["mla_paged_decode_attention"] = {
        "context": args.context, "lanes_a_call": mla_kernel.lanes_a_call(B, 32, W, kv, jnp.bfloat16),
        "ms_a_layer": ms, "least_ms": least, "roofline_pct": 100 * least / ms}

    print(json.dumps(out, indent=1))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "kda_check.json"), "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())

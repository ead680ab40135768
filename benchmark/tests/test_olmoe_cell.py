"""The OLMoE cell on the CPU, beside ``test_benchmark.py``:

    python -m pytest benchmark/tests -q

The cell end to end at the ``olmoe_tiny`` preset behind ``run_cell``'s
rehearsal argument (traced and untraced), the runner's refusal of a
program without the family, and the arithmetic of the grouped matmul's
least work.
"""

import os
import random
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import flops, flops_moe, spec  # noqa: E402

CELL = "olmoe-1b-7b.serve.backlog-wide"
TINY = {"n_layer": 2, "n_embd": 64, "n_head": 4, "n_positions": 128, "vocab_size": 256,
        "vocab_rows": 256, "dtype": "float32"}
TINY_CONFIG = {"preset": "olmoe_tiny", "num_experts": 8, "num_experts_per_tok": 2,
               "intermediate_size": 32, "hidden_size": 64}
TINY_CELL = {
    "engine": {"max_batch_size": 4, "block_size": 8, "pool_tokens": 512, "max_queue": 256},
    "traffic": {"prompt_len": {"median": 16, "sigma": 0.8, "lo": 4, "hi": 60},
                "max_tokens": {"median": 8, "sigma": 0.5, "lo": 2, "hi": 24},
                "max_total_tokens": 128, "trace_seconds": 0.5, "clients": 8, "pool_requests": 64,
                "lead_in_s": 0.5},
    "checks": {"prompt_len": 12, "max_tokens": 6, "logit_margin": 1e-3, "logit_distance": 2e-4},
}
# what a traced run prints without a chip: the counters' metrics and the
# host clock's (the four that read the device trace find nothing on the
# CPU and are left out)
ON_THE_CPU = {"engine_step_ms.backlog", "lanes_busy_pct.backlog", "host_ms_per_step",
              "prefill_share_pct", "kv_gather_useful_pct", "moe_experts_hit_pct",
              "moe_imbalance", "prefill_pad_ratio", "deploy_ready_s.serve",
              "decode_overlap_pct.moe"}


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_end_to_end_at_tiny_size(monkeypatch, trace):
    from benchmark import run

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    out = run.run_cell(CELL, seed=3_000_000_019, seconds=2, trace=trace,
                       rehearsal={"sizes": TINY, "config": TINY_CONFIG, "cell": TINY_CELL})
    assert out is not None
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    if trace:
        assert set(out["metrics"]) >= ON_THE_CPU and "breakdown" in out
        assert 0 < out["metrics"]["moe_experts_hit_pct"]["value"] <= 100
        assert out["metrics"]["moe_imbalance"]["value"] >= 1
        assert out["metrics"]["lanes_busy_pct.backlog"]["value"] > 50
        assert out["metrics"]["prefill_pad_ratio"]["value"] >= 1
    else:
        assert set(out["metrics"]) == {"serve_out_tokens_per_s", "setup_s"}
        assert out["metrics"]["serve_out_tokens_per_s"]["value"] > 0


def test_runner_fails_at_once_where_the_program_has_no_such_family(monkeypatch):
    """The parent of PR 26 has no ``ray_tpu.models.olmoe``: the runner
    must raise before it deploys, not wait for a replica."""
    from benchmark.runners import serve_olmoe

    monkeypatch.setattr(serve_olmoe, "FAMILY", "ray_tpu.models.no_such_family")
    monkeypatch.setattr(serve_olmoe, "deploy", lambda job: pytest.fail("deployed"))
    with pytest.raises(RuntimeError, match="no ray_tpu.models.no_such_family"):
        serve_olmoe.run({"config": {"name": "olmoe-1b-7b"}})


def test_every_seed_takes_the_pool_from_the_head_of_the_same_order():
    """The cell's window is shorter than one cycle of its pool, so the
    runner takes back the rotation ``traffic.make_requests`` gives a
    seed: the same lengths in the same order, ids of the seed's own."""
    from benchmark import traffic
    from benchmark.runners.serve_olmoe import from_the_head

    tr = spec.load_cell(CELL)["traffic"]
    n = tr["pool_requests"]
    pools = {seed: traffic.make_requests(n, tr, 50304, seed) for seed in (5, 2_600_000_797, 3_100_000_741)}

    def sizes(pool):
        return [(len(r["prompt"]), r["max_tokens"]) for r in pool]

    assert len({tuple(sizes(p)) for p in pools.values()}) == 3  # as rotated, they differ
    heads = {seed: from_the_head(p, seed) for seed, p in pools.items()}
    assert len({tuple(sizes(p)) for p in heads.values()}) == 1
    assert all(sorted(sizes(heads[s])) == sorted(sizes(pools[s])) for s in pools)  # nothing lost
    assert heads[5][0]["prompt"] != heads[2_600_000_797][0]["prompt"]
    # and it is the order make_requests gives a seed it does not rotate
    unrotated = next(s for s in range(10_000) if random.Random(s).randrange(n) == 0)
    assert sizes(traffic.make_requests(n, tr, 50304, unrotated)) == sizes(heads[5])


def test_grouped_matmul_work_and_roofline_share_by_hand():
    config = spec.load_config("olmoe-1b-7b")
    # one decode program of 32 lanes, 12 layers, 63 of 64 experts hit a layer
    pairs, hit = 32 * 8 * 12, 63 * 12
    work = flops_moe.grouped_matmul_work(config, pairs, hit)
    assert work["flops"] == 2 * pairs * 3 * 2048 * 1024
    assert work["bytes"] == hit * 3 * 2048 * 1024 * 2 + pairs * (2 * 2048 + 3 * 1024) * 2
    peak = spec.load_peaks()["TPU v5 lite"]
    least = flops.least_seconds(work, peak)
    assert least["bound"] == "memory" and least["seconds"] == pytest.approx(0.01167, rel=1e-3)
    from benchmark.runners.serve_olmoe import gmm_roofline_pct

    # 100 such programs in 3 s of host time; the kernel ran 1.8 s of a 3 s trace
    start = {"t": 10.0, "moe_pairs": 0, "moe_experts_hit": 0}
    after = {"t": 13.0, "moe_pairs": 100 * pairs, "moe_experts_hit": 100 * hit}
    trace = {"devices": 1, "window_s": 3.0, "op_seconds": {"moe_gmm tpu_custom_call": 1.8, "fusion": 0.5}}
    assert gmm_roofline_pct(config, trace, start, after, peak) == pytest.approx(100 * 1.167 / 1.8, rel=1e-3)
    # nothing to read: no device in the trace, no kernel, a parent without the counters
    assert gmm_roofline_pct(config, {"devices": 0}, start, after, peak) is None
    assert gmm_roofline_pct(config, dict(trace, op_seconds={"fusion": 1.0}), start, after, peak) is None
    assert gmm_roofline_pct(config, trace, {"t": 10.0}, {"t": 13.0}, peak) is None

"""The MiniCPM-SALA cell on the CPU, beside ``test_benchmark.py``:

    python -m pytest benchmark/tests -q

The cell end to end at the ``minicpm_sala_tiny`` preset behind
``run_cell``'s rehearsal argument (traced and untraced), its metric
names against the entries of ``BENCHMARK.json``, the runner's refusal of
a program without the family, the warm-up's shapes, and the arithmetic of
the decode kernel's least work.
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import flops, flops_sala, spec  # noqa: E402
from benchmark.tests import rehearsal  # noqa: E402

CELL = "minicpm-sala.serve.longdoc-backlog"
L, S = "lightning-attn", "minicpm4"
TINY = {"n_layer": 4, "n_embd": 64, "n_head": 4, "n_positions": 512, "vocab_size": 256,
        "vocab_rows": 256, "dtype": "float32"}
TINY_CONFIG = {
    "preset": "minicpm_sala_tiny", "intermediate_size": 128, "num_key_value_heads": 2, "head_dim": 16,
    "num_attention_heads": 4, "lightning_nh": 4, "lightning_head_dim": 16, "dim_model_base": 16,
    "mixer_types": [L, S, L, S],
    "assumed": {"sparse_config": {"kernel_size": 8, "kernel_stride": 4, "block_size": 16, "init_blocks": 1,
                                  "window_size": 32, "topk": 4, "dense_len": 64}},
}
TINY_CELL = {
    "engine": {"max_batch_size": 4, "block_size": 8, "pool_tokens": 2048, "max_queue": 256,
               "max_model_len": 512, "prefill_chunk": 64},
    "traffic": {"prompt_len": {"median": 150, "sigma": 0.6, "lo": 64, "hi": 400},
                "max_tokens": {"median": 8, "sigma": 0.5, "lo": 2, "hi": 24},
                "max_total_tokens": 512, "trace_seconds": 0.5, "clients": 8, "pool_requests": 32,
                "lead_in": {"after_full_s": 0.5, "at_most_s": 30.0}},
    "checks": {"prompt_lens": [12, 150], "max_tokens": 6, "logit_margin": 1e-3, "logit_distance": 3e-4,
               "selection_agreement_min": 0.99},
}
# what a traced run prints without a chip: the counters' metrics and the
# host clock's (the three that read the device trace find nothing on the
# CPU and are left out)
ON_THE_CPU = {"engine_step_ms.backlog", "lanes_busy_pct.backlog", "host_ms_per_step",
              "prefill_share_pct", "prefill_pad_ratio", "decode_overlap_pct",
              "deploy_ready_s.serve", "sparse_kept_pct.sala", "select_tiles_pct.sala",
              "prefill_chunk_ms"}
FROM_THE_DEVICE = {"device_idle_pct.backlog", "sparse_paged_decode_attention_busy_pct.sala",
                   "sparse_paged_decode_attention_roofline.sala"}


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_end_to_end_at_tiny_size(monkeypatch, trace):
    from benchmark import run

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    out = run.run_cell(CELL, seed=3_000_000_019, seconds=3, trace=trace,
                       rehearsal={"sizes": TINY, "config": TINY_CONFIG, "cell": TINY_CELL})
    assert out is not None
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    if trace:
        assert set(out["metrics"]) >= ON_THE_CPU and "breakdown" in out
        assert 0 < out["metrics"]["sparse_kept_pct.sala"]["value"] < 100  # blocks were dropped
        assert out["metrics"]["prefill_pad_ratio"]["value"] >= 1
        assert out["metrics"]["prefill_chunk_ms"]["value"] > 0
    else:
        assert set(out["metrics"]) == {"serve_out_tokens_per_s", "setup_s"}
        assert out["metrics"]["serve_out_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("tree", rehearsal.TREES)
def test_the_cell_s_metrics_are_the_entries_of_benchmark_json(tree, tmp_path, monkeypatch):
    """About this cell alone, so that a later PR's cells and entries
    (``rehearsal.plant`` makes such an addition) need no edit here."""
    rehearsal.plant(tree, tmp_path, monkeypatch)
    bench = spec.load_benchmark()
    per_layer = {m["name"]: m for m in spec.metrics_of_cell(bench, "per_layer", CELL)}
    assert set(per_layer) >= ON_THE_CPU | FROM_THE_DEVICE
    for name in ON_THE_CPU | FROM_THE_DEVICE:
        assert CELL in per_layer[name]["workloads"] and spec.load_layer_metric(name)["reader"]
        assert per_layer[name]["moves"] == (
            "setup_s" if name.startswith("deploy_ready") else "serve_out_tokens_per_s")
    assert {m["name"] for m in spec.metrics_of_cell(bench, "end_to_end", CELL)} >= {
        "serve_out_tokens_per_s", "setup_s"}
    cell, wl = spec.load_cell(CELL), spec.entry(bench, "workloads", CELL)
    assert (cell["why"], cell["config"], cell["chips"]) == (wl["why"], wl["config"], 1)
    # the configuration: the published widths, depth and mixers alone reduced
    config = spec.load_config("minicpm-sala")
    assert spec.entry(bench, "configs", "minicpm-sala")["reduced"] == config["reduced"] == [
        "num_hidden_layers", "mixer_types"]
    assert (config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["lightning_nh"], config["lightning_head_dim"],
            config["intermediate_size"], config["vocab_size"]) == (4096, 32, 2, 128, 32, 128, 16384, 73448)
    assert len(config["mixer_types"]) == config["num_hidden_layers"] == 16
    assert config["mixer_types"].count(S) == 4
    # the traffic the issue names, and a pool of whole lanes
    tr, eng = cell["traffic"], cell["engine"]
    assert (tr["clients"], tr["pool_requests"], eng["max_batch_size"]) == (64, 128, 16)
    assert eng["pool_tokens"] == eng["max_batch_size"] * eng["max_model_len"] == 540672
    assert tr["max_total_tokens"] == eng["max_model_len"] == 33792


def test_runner_fails_at_once_where_the_program_has_no_such_family(monkeypatch):
    """The parent of PR 30 has no ``ray_tpu.models.minicpm_sala``: the
    runner must raise before it deploys, not wait for a replica."""
    from benchmark.runners import serve_minicpm_sala

    monkeypatch.setattr(serve_minicpm_sala, "FAMILY", "ray_tpu.models.no_such_family")
    monkeypatch.setattr(serve_minicpm_sala, "deploy", lambda job: pytest.fail("deployed"))
    with pytest.raises(RuntimeError, match="no ray_tpu.models.no_such_family"):
        serve_minicpm_sala.run({"config": {"name": "minicpm-sala"}})


def test_the_warm_up_sends_every_chunk_bucket():
    from benchmark.runners.serve_minicpm_sala import chunk_buckets

    assert chunk_buckets(4096) == [8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096]
    assert chunk_buckets(64) == [8, 16, 32, 64]


def test_decode_kernel_work_and_roofline_share_by_hand():
    config = spec.load_config("minicpm-sala")
    # one decode program: 16 lanes x 2 K/V heads x 4 sparse layers, 64 blocks of 64 positions each
    pairs = 16 * 2 * 4
    positions = pairs * 64 * 64
    work = flops_sala.sparse_decode_work(config, positions, pairs)
    assert work["flops"] == 2 * 2 * positions * 16 * 128
    assert work["bytes"] == positions * 2 * 128 * 2 + pairs * (2 * 16 * 128 + 2 * 128) * 4
    peak = spec.load_peaks()["TPU v5 lite"]
    least = flops.least_seconds(work, peak)
    assert least["bound"] == "memory" and least["seconds"] == pytest.approx(3.30e-4, rel=1e-2)
    from benchmark.runners.serve_minicpm_sala import kernel_roofline_pct

    # 200 such programs in the window; 40 of them in the trace, 160 calls taking 0.40 s
    before = {"kv_positions_gathered": 0, "steps": 0}
    after = {"kv_positions_gathered": 200 * positions, "steps": 200, "max_batch_size": 16}
    trace = {"devices": 1, "window_s": 5.0,
             "op_seconds": {"sparse_paged_decode_attention tpu_custom_call": 0.40, "fusion": 3.0},
             "op_counts": {"sparse_paged_decode_attention tpu_custom_call": 160, "fusion": 9000}}
    # a call's least time is a quarter of the program's 0.330 ms; a call took 2.5 ms
    assert kernel_roofline_pct(config, trace, before, after, peak) == pytest.approx(100 * 0.0825 / 2.5, rel=1e-2)
    # nothing to read: no device in the trace, no kernel, a program without the counter
    assert kernel_roofline_pct(config, {"devices": 0}, before, after, peak) is None
    assert kernel_roofline_pct(config, dict(trace, op_seconds={"fusion": 1.0}), before, after, peak) is None
    assert kernel_roofline_pct(config, trace, {"steps": 0}, {"steps": 200, "max_batch_size": 16}, peak) is None
    # the state's traffic is what the engine's state_bytes counts: 24 MiB a lane, read and written
    step = flops_sala.lightning_step_work(config, 16, 12)
    assert step["bytes"] == 2 * 16 * 12 * 32 * 128 * 128 * 4
    chunk = flops_sala.lightning_chunk_work(config, 4096, 12)
    assert chunk["flops"] == 12 * 4096 * 2 * 32 * (2 * 256 * 128 + 2 * 128 * 128)

"""The Mistral-Small-4 cell on the CPU, beside ``test_benchmark.py``:

    python -m pytest benchmark/tests -q

The cell end to end at the ``mistral_small_4_tiny`` preset behind
``run_cell``'s rehearsal argument (traced and untraced), its metric names
against the entries of ``BENCHMARK.json``, the configuration file against
the catalog's published keys, the runner's refusal of a program without
the family, and the arithmetic of the decode kernel's and the held
experts' least work.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import flops, flops_mla, spec  # noqa: E402
from benchmark.tests import rehearsal  # noqa: E402

CELL = "mistral-small-4.serve.longctx-backlog"
TINY = {"n_layer": 2, "n_embd": 64, "n_head": 4, "n_positions": 512, "vocab_size": 256,
        "vocab_rows": 256, "dtype": "float32"}
TINY_CONFIG = {
    "preset": "mistral_small_4_tiny", "num_hidden_layers": 2, "q_lora_rank": 32, "kv_lora_rank": 32,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 16, "moe_intermediate_size": 32,
    "n_routed_experts": 32, "num_attention_heads": 4, "hidden_size": 64,
    "rope_parameters": {"beta_fast": 32, "beta_slow": 1, "factor": 16, "llama_4_scaling_beta": 0.1,
                        "mscale": 1, "mscale_all_dim": 1, "original_max_position_embeddings": 32,
                        "rope_theta": 10000},
    "held": {"experts_first": 0, "experts_held": 32, "vocab_first": 0, "vocab_rows": 256,
             "router_outputs": 32, "num_experts_per_tok": 4},
    "published": {"n_routed_experts": 32, "vocab_size": 256},
}
TINY_CELL = {
    "engine": {"max_batch_size": 4, "block_size": 8, "pool_tokens": 2048, "max_queue": 256,
               "max_model_len": 512, "prefill_chunk": 64},
    "traffic": {"prompt_len": {"median": 120, "sigma": 0.6, "lo": 32, "hi": 400},
                "max_tokens": {"median": 12, "sigma": 0.5, "lo": 4, "hi": 32},
                "max_total_tokens": 512, "trace_seconds": 0.5, "clients": 8, "pool_requests": 32,
                "lead_in": {"after_full_s": 0.5, "at_most_s": 30.0}, "send_gap_s": 0.01},
    "checks": {"prompt_lens": [12, 150], "max_tokens": 6, "logit_margin": 1e-3, "logit_distance": 3e-4,
               "expert_agreement_min": 0.99, "positions_agreeing_min": 0.9, "cached_row_columns": 128},
}
# what a traced run prints without a chip: the counters' metrics and the
# host clock's (the five that read the device trace find nothing on the
# CPU and are left out)
ON_THE_CPU = {"engine_step_ms.backlog", "lanes_busy_pct.backlog", "host_ms_per_step", "prefill_share_pct",
              "prefill_pad_ratio", "prefill_chunk_ms", "decode_overlap_pct",
              "kv_gather_useful_pct", "deploy_ready_s.serve", "moe_experts_hit_pct",
              "moe_imbalance", "moe_held_share_pct"}
FROM_THE_DEVICE = {"device_idle_pct.backlog", "moe_gmm_busy_pct", "moe_gmm_roofline_pct",
                   "mla_paged_decode_attention_busy_pct.mla", "mla_paged_decode_attention_roofline.mla"}


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
        assert out["metrics"]["moe_held_share_pct"]["value"] == 100  # the tiny preset holds all 32
        assert 0 < out["metrics"]["kv_gather_useful_pct"]["value"] <= 100
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
    # the traffic and the engine the issue names
    tr, eng = cell["traffic"], cell["engine"]
    assert (tr["clients"], tr["pool_requests"], eng["max_batch_size"], eng["block_size"]) == (96, 192, 48, 64)
    assert tr["prompt_len"] == {"median": 8192, "sigma": 0.7, "lo": 2048, "hi": 32768}
    assert tr["max_tokens"] == {"median": 1024, "sigma": 0.7, "lo": 256, "hi": 4096}
    assert tr["max_total_tokens"] == eng["max_model_len"] == 36864 and eng["prefill_chunk"] == 4096
    assert 589824 <= eng["pool_tokens"] <= 786432 and eng["pool_tokens"] % 65536 == 0
    assert cell["checks"]["prompt_lens"] == [64, 9216] and cell["checks"]["logit_margin"] == 0.2


def test_the_configuration_is_the_catalog_s_but_for_what_it_says_is_reduced():
    config = spec.load_config("mistral-small-4")
    bench = spec.load_benchmark()
    assert spec.entry(bench, "configs", "mistral-small-4")["reduced"] == config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Mistral-Small-4-119B-2603")
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in config["reduced"]:
                assert config["published"][key] == value and config[key] < value
            else:
                assert config[key] == value, key
    # every width as published; the share, and the floors it keeps
    assert (config["hidden_size"], config["q_lora_rank"], config["kv_lora_rank"], config["qk_nope_head_dim"],
            config["qk_rope_head_dim"], config["v_head_dim"], config["moe_intermediate_size"],
            config["num_experts_per_tok"], config["num_attention_heads"]) == (4096, 1024, 256, 64, 64, 128, 2048, 4, 32)
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"]) == (6, 32, 32768)
    assert config["num_hidden_layers"] >= 4 and config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= config["published"]["vocab_size"]
    assert config["held"]["router_outputs"] == config["published"]["n_routed_experts"] == 128
    assert "4 chips share each layer" in config["deployment"]
    for item in ("router_scoring", "query_scale", "yarn_ramp", "param_dtype", "weights", "max_model_len",
                 "cached_row", "engine_sizes_why", "vocab_rows"):
        assert item in config["assumed"], item


def test_runner_fails_at_once_where_the_program_has_no_such_family(monkeypatch):
    """The parent of PR 33 has no ``ray_tpu.models.mistral4``: the runner
    must raise before it deploys, not wait for a replica."""
    from benchmark.runners import serve_mistral_small_4

    monkeypatch.setattr(serve_mistral_small_4, "FAMILY", "ray_tpu.models.no_such_family")
    monkeypatch.setattr(serve_mistral_small_4, "deploy", lambda job: pytest.fail("deployed"))
    with pytest.raises(RuntimeError, match="no ray_tpu.models.no_such_family"):
        serve_mistral_small_4.run({"config": {"name": "mistral-small-4"}})


def test_decode_kernel_and_held_experts_work_and_roofline_shares_by_hand():
    config = spec.load_config("mistral-small-4")
    peak = spec.load_peaks()["TPU v5 lite"]
    # one decode program: 48 lanes of 10,000 positions in each of 6 layers
    positions, lane_calls = 48 * 10_000 * 6, 48 * 6
    work = flops_mla.mla_decode_work(config, positions, lane_calls)
    assert work["flops"] == 2 * positions * 32 * (320 + 256)
    assert work["bytes"] == positions * 640 + lane_calls * (32 * 320 + 320 + 32 * 256) * 4
    assert work["flops"] / (positions * 640) == pytest.approx(57.6)  # under the ridge of 240: memory
    least = flops.least_seconds(work, peak)
    assert least["bound"] == "memory" and least["seconds"] == pytest.approx(2.27693e-3, rel=1e-4)
    from benchmark.runners.serve_mistral_small_4 import gmm_roofline_pct, kernel_roofline_pct  # the OLMoE runner's

    # 500 such programs in the window; 100 of them in the trace, 600 calls taking 0.60 s
    before = {"kv_positions_attended": 0, "steps": 0}
    after = {"kv_positions_attended": 500 * positions, "steps": 500, "max_batch_size": 48}
    trace = {"devices": 1, "window_s": 5.0,
             "op_seconds": {"mla_paged_decode_attention tpu_custom_call": 0.60, "moe_gmm tpu_custom_call": 1.2,
                            "fusion": 2.0},
             "op_counts": {"mla_paged_decode_attention tpu_custom_call": 600, "fusion": 9000}}
    # a call's least time is a sixth of the program's 2.27693 ms; a call took 1 ms
    assert kernel_roofline_pct(config, trace, before, after, peak) == pytest.approx(100 * 0.379488 / 1.0, rel=1e-4)
    assert kernel_roofline_pct(config, {"devices": 0}, before, after, peak) is None
    assert kernel_roofline_pct(config, dict(trace, op_seconds={"fusion": 1.0}), before, after, peak) is None
    assert kernel_roofline_pct(config, trace, {"steps": 0}, {"steps": 500, "max_batch_size": 48}, peak) is None
    # the held experts: a pair is three 4096 x 2048 matmuls, an expert hit 50.3 MB of them
    moe = flops_mla.held_experts_work(config, 48, 25)
    assert moe["flops"] == 2 * 48 * 3 * 4096 * 2048
    assert moe["bytes"] == 25 * 3 * 4096 * 2048 * 2 + 48 * (2 * 4096 + 3 * 2048) * 2
    # 2 s of trace in which 100 programs x 6 layers computed 48 pairs over 25 experts each
    start = {"t": 10.0, "moe_pairs": 0, "moe_experts_hit": 0}
    end = {"t": 12.0, "moe_pairs": 100 * 6 * 48, "moe_experts_hit": 100 * 6 * 25}
    least_s = flops.least_seconds(flops_mla.held_experts_work(config, 100 * 6 * 48, 100 * 6 * 25), peak)["seconds"]
    sizes = flops_mla.expert_sizes(config)
    assert (sizes["intermediate_size"], config["intermediate_size"]) == (2048, 12288)
    assert gmm_roofline_pct(sizes, trace, start, end, peak) == pytest.approx(100 * (least_s / 2.0) / (1.2 / 5.0))
    assert gmm_roofline_pct(sizes, trace, start, {"t": 12.0}, peak) is None

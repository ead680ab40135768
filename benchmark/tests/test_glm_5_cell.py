"""The GLM-5 cell on the CPU, beside ``test_benchmark.py``:

    python -m pytest benchmark/tests -q

The cell end to end at the ``glm5_tiny`` preset behind ``run_cell``'s
rehearsal argument (traced and untraced), its metric names against the
entries of ``BENCHMARK.json``, the configuration file against the
catalog's published keys and its own arithmetic, the runner's refusal of
a program without the family, and the arithmetic of the two decode
kernels' least work.
"""

import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import flops, flops_dsa, spec  # noqa: E402
from benchmark.tests import rehearsal  # noqa: E402

CELL = "glm-5.serve.longrepo-backlog"
NAME = "glm-5"
TINY = {"n_layer": 3, "n_embd": 64, "n_head": 8, "n_positions": 512, "vocab_size": 256,
        "vocab_rows": 256, "dtype": "float32"}
TINY_CONFIG = {
    "preset": "glm5_tiny", "num_hidden_layers": 3, "first_k_dense_replace": 1, "hidden_size": 64,
    "num_attention_heads": 8, "q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "index_n_heads": 8, "index_head_dim": 16, "index_topk": 16,
    "intermediate_size": 96, "moe_intermediate_size": 32, "n_routed_experts": 16,
    "num_experts_per_tok": 4,
    "held": {"experts_first": 0, "experts_held": 16, "vocab_first": 0, "vocab_rows": 256,
             "router_outputs": 16, "num_experts_per_tok": 4, "dense_layers": 1, "expert_layers": 2},
    "published": {"n_routed_experts": 16, "vocab_size": 256},
}
TINY_CELL = {
    "engine": {"max_batch_size": 4, "block_size": 8, "pool_tokens": 2048, "max_queue": 256,
               "max_model_len": 512, "prefill_chunk": 64},
    "traffic": {"prompt_len": {"median": 120, "sigma": 0.6, "lo": 32, "hi": 400},
                "max_tokens": {"median": 12, "sigma": 0.5, "lo": 4, "hi": 32},
                "max_total_tokens": 512, "trace_seconds": 0.5, "clients": 8, "pool_requests": 32,
                "lead_in": {"after_full_s": 0.5, "at_most_s": 30.0}, "send_gap_s": 0.01},
    # under the tiny index_topk (every position kept); three chunks, every decode step selects
    "checks": {"prompt_lens": [8, 150], "max_tokens": 6, "logit_margin": 1e-3, "logit_distance": 3e-4,
               "expert_agreement_min": 0.99, "selection_agreement_min": 0.99, "selection_position_min": 1.0,
               "positions_agreeing_min": 0.9, "cached_row_columns": 128, "held_bytes_min": 1},
}
# what a traced run prints without a chip: the counters' metrics and the
# host clock's (the seven that read the device trace find nothing on the
# CPU and are left out)
ON_THE_CPU = {"engine_step_ms.backlog", "lanes_busy_pct.backlog", "host_ms_per_step", "prefill_share_pct",
              "prefill_pad_ratio", "prefill_chunk_ms", "decode_overlap_pct",
              "kv_gather_useful_pct", "deploy_ready_s.serve", "moe_experts_hit_pct",
              "moe_imbalance", "moe_held_share_pct", "dsa_kept_pct.glm5"}
FROM_THE_DEVICE = {"device_idle_pct.backlog", "moe_gmm_busy_pct", "moe_gmm_roofline_pct",
                   "dsa_index_paged_scores_busy_pct.glm5", "dsa_index_paged_scores_roofline.glm5",
                   "mla_sparse_paged_decode_attention_busy_pct.glm5",
                   "mla_sparse_paged_decode_attention_roofline.glm5"}


def _run(trace, checks=None):
    from benchmark import run

    cell = dict(TINY_CELL, checks=dict(TINY_CELL["checks"], **(checks or {})))
    return run.run_cell(CELL, seed=3_000_000_019, seconds=3, trace=trace,
                        rehearsal={"sizes": TINY, "config": TINY_CONFIG, "cell": cell})


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_end_to_end_at_tiny_size(monkeypatch, trace):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    out = _run(trace)
    assert out is not None
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    if trace:
        assert set(out["metrics"]) >= ON_THE_CPU and "breakdown" in out
        assert out["metrics"]["moe_held_share_pct"]["value"] == 100  # the tiny preset holds all 16
        # 16 chosen of the whole pages a lane holds (40-430 positions): the walk copies them all
        assert 3 < out["metrics"]["kv_gather_useful_pct"]["value"] < 45
        # prompts of 32-400 tokens decode at 16 positions kept of 40-430: a few per cent
        assert 3 < out["metrics"]["dsa_kept_pct.glm5"]["value"] < 45
        assert out["metrics"]["prefill_chunk_ms"]["value"] > 0
    else:
        assert set(out["metrics"]) == {"serve_out_tokens_per_s", "setup_s"}
        assert out["metrics"]["serve_out_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("wrong", ["recent", "all"])
def test_a_wrong_choice_is_not_correct(monkeypatch, wrong):
    """The reference choosing by recency, or attending every position,
    against the program's index: the run is not correct, by the share of
    chosen positions that agree."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    out = _run(0, {"wrong_on_purpose": wrong})
    assert out is not None and not out["correct"] and out["failed"] == 0


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
    assert spec.entry(bench, "configs", NAME)["file"] == "benchmark/configs/glm-5.json"
    # the traffic and the engine the issue names
    tr, eng = cell["traffic"], cell["engine"]
    assert (tr["clients"], tr["pool_requests"], eng["max_batch_size"], eng["block_size"]) == (40, 192, 20, 64)
    assert tr["prompt_len"] == {"median": 16384, "sigma": 0.6, "lo": 4096, "hi": 32768}
    assert tr["max_tokens"] == {"median": 1024, "sigma": 0.7, "lo": 256, "hi": 4096}
    assert tr["max_total_tokens"] == eng["max_model_len"] == 36864
    assert 294912 <= eng["pool_tokens"] <= 458752 and (458752 - eng["pool_tokens"]) % 32768 == 0
    chk = cell["checks"]
    assert chk["prompt_lens"] == [64, 9216] and chk["max_tokens"] == 16 and chk["cached_row_columns"] == 640
    assert chk["held_bytes_min"] == 12_000_000_000
    # every prompt is over index_topk: every decode step and every chunk but a prompt's first selects
    assert tr["prompt_len"]["lo"] > spec.load_config(NAME)["index_topk"]
    for limit in ("logit_distance", "logit_margin", "expert_agreement_min", "selection_agreement_min",
                  "selection_position_min", "positions_agreeing_min"):
        assert limit in chk and limit in chk["logit_why"], limit
    for reading in ("float8_e4m3", "most recent 2,048", "every position attended"):
        assert reading in chk["logit_why"], reading


def test_the_configuration_is_the_catalog_s_but_for_what_it_says_is_reduced():
    config = spec.load_config(NAME)
    bench = spec.load_benchmark()
    assert spec.entry(bench, "configs", NAME)["reduced"] == config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5")
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in config["reduced"]:
                assert config["published"][key] == value and config[key] < value
            else:
                assert config[key] == value, key
    # every width as published; the share, and the floors it keeps
    assert (config["hidden_size"], config["num_attention_heads"], config["q_lora_rank"], config["kv_lora_rank"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"],
            config["index_n_heads"], config["index_head_dim"], config["index_topk"],
            config["moe_intermediate_size"], config["intermediate_size"], config["num_experts_per_tok"]) == (
        6144, 64, 2048, 512, 192, 64, 256, 32, 128, 2048, 2048, 12288, 8)
    assert (config["num_hidden_layers"], config["first_k_dense_replace"], config["n_routed_experts"],
            config["vocab_size"]) == (6, 1, 16, 19360)
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4 and config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= config["published"]["vocab_size"]
    assert config["held"]["router_outputs"] == config["published"]["n_routed_experts"] == 256
    assert "16 chips share each layer" in config["deployment"] and "0.6 tokens an expert" in config["deployment"]
    for item in ("router_bias", "indexer_rotation", "indexer_norm", "indexer_weight_scale",
                 "indexer_hadamard_and_fp8", "selection_ties", "param_dtype", "weights", "max_model_len",
                 "cached_row", "engine_sizes_why", "vocab_rows"):
        assert item in config["assumed"], item
    assert "multi_token_prediction" in config["left_out"]


def test_the_cut_s_arithmetic_reckoned_again():
    """The parameters of the cut from the configuration file's sizes, and
    the bytes a cached position takes: what ``reduced_why`` says."""
    c = spec.load_config(NAME)
    d, H = c["hidden_size"], c["num_attention_heads"]
    attention = (d * c["q_lora_rank"] + c["q_lora_rank"] * H * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"])
                 + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
                 + c["kv_lora_rank"] * H * (c["qk_nope_head_dim"] + c["v_head_dim"]) + H * c["v_head_dim"] * d)
    indexer = c["q_lora_rank"] * c["index_n_heads"] * c["index_head_dim"] + d * c["index_head_dim"] + d * c["index_n_heads"]
    expert = 3 * d * c["moe_intermediate_size"]
    router = d * c["published"]["n_routed_experts"]
    assert round(attention / 1e6, 2) == 165.02 and round(indexer / 1e6, 2) == 9.37
    assert round((attention + indexer + expert + router) / 1e6, 2) == 213.71
    dense = attention + indexer + 3 * d * c["intermediate_size"]
    assert round(dense / 1e6, 2) == 400.88
    layer = attention + indexer + expert + router + c["n_routed_experts"] * expert
    assert round(layer / 1e6, 2) == 817.69
    held = dense + 5 * layer + 2 * c["vocab_size"] * d
    assert round(held / 1e6) == 4727 and round(2 * held / 1e9, 2) == 9.45
    whole = 3 * dense + 75 * (attention + indexer + expert + router + 256 * expert) + 2 * 154880 * d
    assert round(whole / 1e9, 1) == 743.9
    cell = spec.load_cell(CELL)
    position = c["num_hidden_layers"] * (cell["checks"]["cached_row_columns"] + c["index_head_dim"]) * 2
    assert position == 9216
    assert 2 * held + cell["engine"]["pool_tokens"] * position >= cell["checks"]["held_bytes_min"]


def test_runner_fails_at_once_where_the_program_has_no_such_family(monkeypatch):
    """The parent of PR 57 has no ``ray_tpu.models.glm_moe_dsa``: the
    runner must raise before it deploys, not wait for a replica."""
    from benchmark.runners import serve_glm_5

    monkeypatch.setattr(serve_glm_5, "FAMILY", "ray_tpu.models.no_such_family")
    monkeypatch.setattr(serve_glm_5, "deploy", lambda job: pytest.fail("deployed"))
    with pytest.raises(RuntimeError, match="no ray_tpu.models.no_such_family"):
        serve_glm_5.run({"config": {"name": NAME}})


def test_the_two_decode_kernels_work_and_roofline_shares_by_hand():
    config = spec.load_config(NAME)
    peak = spec.load_peaks()["TPU v5 lite"]
    # one decode program: 20 lanes of 16,000 cached positions in each of 6 layers
    scored, lane_calls = 20 * 16_000 * 6, 20 * 6
    work = flops_dsa.index_scores_work(config, scored, lane_calls)
    assert work["flops"] == 2 * scored * 32 * 128
    assert work["bytes"] == scored * (256 + 4) + lane_calls * (32 * 128 + 32) * 4
    least = flops.least_seconds(work, peak)
    assert least["bound"] == "memory" and least["seconds"] == pytest.approx(work["bytes"] / 819e9, rel=1e-3)
    # of which each lane attends 2,047 cached rows (and its own)
    attended = 20 * 2047 * 6
    work = flops_dsa.sparse_decode_work(config, attended, lane_calls)
    assert work["flops"] == 2 * attended * 64 * (576 + 512)
    assert work["bytes"] == attended * 1152 + lane_calls * (64 * 576 + 576 + 64 * 512) * 4
    assert work["flops"] / (attended * 1152) == pytest.approx(120.9, rel=1e-3)  # under the ridge of 240: memory
    from benchmark.runners.serve_glm_5 import ATTEND_KERNEL, INDEX_KERNEL, kernel_roofline_pct

    # 500 such programs in the window; 100 of them in the trace: 600 calls of each kernel
    before = {"dsa_index_positions_scored": 0, "kv_positions_attended": 0, "steps": 0}
    after = {"dsa_index_positions_scored": 500 * scored, "kv_positions_attended": 500 * attended, "steps": 500,
             "max_batch_size": 20}
    trace = {"devices": 1, "window_s": 5.0,
             "op_seconds": {"dsa_index_paged_scores tpu_custom_call": 0.12,
                            "mla_sparse_paged_decode_attention tpu_custom_call": 0.06, "fusion": 2.0},
             "op_counts": {"dsa_index_paged_scores tpu_custom_call": 600,
                           "mla_sparse_paged_decode_attention tpu_custom_call": 600, "fusion": 9000}}
    # a call's least time is a sixth of the program's; a call took 0.2 ms and 0.1 ms
    index_call = (scored * 260 + lane_calls * 4128 * 4) / 6 / 819e9
    got = kernel_roofline_pct(INDEX_KERNEL, flops_dsa.index_scores_work, "dsa_index_positions_scored", config,
                              trace, before, after, peak)
    assert got == pytest.approx(100 * index_call / 0.2e-3, rel=2e-3) and 40 < got < 100
    attend_call = (attended * 1152 + lane_calls * (64 * 576 + 576 + 64 * 512) * 4) / 6 / 819e9
    got = kernel_roofline_pct(ATTEND_KERNEL, flops_dsa.sparse_decode_work, "kv_positions_attended", config,
                              trace, before, after, peak)
    assert got == pytest.approx(100 * attend_call / 0.1e-3, rel=2e-3) and 40 < got < 100
    args = (INDEX_KERNEL, flops_dsa.index_scores_work, "dsa_index_positions_scored", config)
    assert kernel_roofline_pct(*args, {"devices": 0}, before, after, peak) is None
    assert kernel_roofline_pct(*args, dict(trace, op_seconds={"fusion": 1.0}), before, after, peak) is None
    assert kernel_roofline_pct(*args, trace, {"steps": 0}, {"steps": 500, "max_batch_size": 20}, peak) is None
    # the two kernels' names do not match one another's pattern, nor the dense latent kernel's
    assert not re.search("^mla_paged_decode_attention", "mla_sparse_paged_decode_attention tpu_custom_call")
    assert not INDEX_KERNEL.search("mla_sparse_paged_decode_attention") and not ATTEND_KERNEL.search(
        "mla_paged_decode_attention tpu_custom_call")

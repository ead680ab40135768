"""The Kimi-Linear cell on the CPU, beside ``test_benchmark.py``:

    python -m pytest benchmark/tests -q

The cell end to end at the ``kimi_linear_tiny`` preset behind
``run_cell``'s rehearsal argument (traced and untraced), its
wrong-on-purpose readings, its metric names against the entries of
``BENCHMARK.json``, the configuration file against the catalog's
published keys and its own arithmetic, the runner's refusal of a program
without the family, and the arithmetic of the two decode kernels' least
work.
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

from benchmark import flops, flops_kda, flops_mla, spec  # noqa: E402
from benchmark.tests import rehearsal  # noqa: E402

CELL = "kimi-linear-48b-a3b.serve.rollout-backlog"
NAME = "kimi-linear-48b-a3b"
TINY = {"n_layer": 5, "n_embd": 64, "n_head": 8, "n_positions": 512, "vocab_size": 256,
        "vocab_rows": 256, "dtype": "float32"}
TINY_CONFIG = {
    "preset": "kimi_linear_tiny", "num_hidden_layers": 5, "first_k_dense_replace": 1, "hidden_size": 64,
    "num_attention_heads": 8, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "intermediate_size": 96, "moe_intermediate_size": 32, "num_experts": 16,
    "num_experts_per_token": 4,
    "linear_attn_config": {"full_attn_layers": [4], "head_dim": 16, "kda_layers": [1, 2, 3, 5], "num_heads": 4,
                           "short_conv_kernel_size": 4},
    "held": {"experts_first": 0, "experts_held": 16, "vocab_first": 0, "vocab_rows": 256,
             "router_outputs": 16, "num_experts_per_token": 4, "dense_layers": 1, "expert_layers": 4},
    "published": {"num_experts": 16, "vocab_size": 256},
    "assumed": {"vocab_rows": 256, "low_rank": 8},
}
TINY_CELL = {
    "engine": {"max_batch_size": 4, "block_size": 8, "pool_tokens": 2048, "max_queue": 256,
               "max_model_len": 512, "prefill_chunk": 64},
    "traffic": {"prompt_len": {"median": 120, "sigma": 0.6, "lo": 32, "hi": 400},
                "max_tokens": {"median": 12, "sigma": 0.5, "lo": 4, "hi": 32},
                "max_total_tokens": 512, "trace_seconds": 0.5, "clients": 8, "pool_requests": 32,
                "lead_in": {"after_full_s": 0.5, "at_most_s": 30.0}, "send_gap_s": 0.01},
    # one program under a chunk; three chunks, the last with pads inside a block of the delta rule
    "checks": {"prompt_lens": [8, 150], "max_tokens": 6, "logit_margin": 1e-3, "logit_distance": 3e-4,
               "state_distance": 1e-4,
               "expert_agreement_min": 0.99, "positions_agreeing_min": 0.9, "cached_row_columns": 128,
               "held_bytes_min": 1},
}
# what a traced run prints without a chip: the counters' metrics and the
# host clock's (the seven that read the device trace find nothing on the
# CPU and are left out)
ON_THE_CPU = {"engine_step_ms.backlog", "lanes_busy_pct.backlog", "host_ms_per_step", "prefill_share_pct",
              "prefill_pad_ratio", "prefill_chunk_ms", "decode_overlap_pct", "kv_gather_useful_pct",
              "deploy_ready_s.serve", "moe_experts_hit_pct", "moe_imbalance", "moe_held_share_pct",
              "ssm_state_mb_per_step", "kda_state_share_of_step_bytes_pct"}
FROM_THE_DEVICE = {"device_idle_pct.backlog", "moe_gmm_busy_pct", "moe_gmm_roofline_pct",
                   "kda_decode_step_busy_pct", "kda_decode_step_roofline",
                   "mla_paged_decode_attention_busy_pct.mla", "mla_paged_decode_attention_roofline.mla"}


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
        # a lane's length of the whole pages of 8 it holds: most of them
        assert 60 < out["metrics"]["kv_gather_useful_pct"]["value"] <= 100
        # 4 lanes x 4 KDA layers x (4 x 16 x 16 x 4 B of state + 3 x 3 x 64 x 4 B of tails), read and written
        # by a decode step; a chunk program's one lane is counted with the steps'
        a_step = 2 * 4 * 4 * (4 * 16 * 16 * 4 + 3 * 3 * 64 * 4) / 1e6
        assert a_step <= out["metrics"]["ssm_state_mb_per_step"]["value"] < 1.5 * a_step
        assert 0 < out["metrics"]["kda_state_share_of_step_bytes_pct"]["value"] < 100
        assert out["metrics"]["prefill_chunk_ms"]["value"] > 0
    else:
        assert set(out["metrics"]) == {"serve_out_tokens_per_s", "setup_s"}
        assert out["metrics"]["serve_out_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("wrong", ["no_correction", "head_decay", "bf16_state", "rotated"])
def test_a_wrong_model_is_not_correct(monkeypatch, wrong):
    """The reference as another model (the delta rule's correction left
    out, one decay a head, a bfloat16 state, the shared key rotated)
    against the program: the run is not correct, by one of its limits."""
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
    assert "over" in cell["why"] and "under" in cell["why"]  # what sees more and less than its share
    assert spec.entry(bench, "configs", NAME)["file"] == "benchmark/configs/kimi-linear-48b-a3b.json"
    # the traffic and the engine the issue names
    tr, eng = cell["traffic"], cell["engine"]
    assert (tr["clients"], eng["max_batch_size"], eng["block_size"], tr["mode"]) == (512, 256, 64, "closed")
    assert tr["prompt_len"] == {"median": 2048, "sigma": 0.9, "lo": 256, "hi": 32768}
    assert tr["max_tokens"] == {"median": 2048, "sigma": 0.6, "lo": 512, "hi": 8192}
    assert tr["max_total_tokens"] == eng["max_model_len"] == 40960 and tr["trace_seconds"] == 5
    assert tr["prompt_len"]["hi"] + tr["max_tokens"]["hi"] <= tr["max_total_tokens"]
    assert 1_400_000 <= eng["pool_tokens"] <= 2_000_000 and eng["pool_tokens"] % eng["block_size"] == 0
    assert len(cell["trace_annotations"]) >= 9 and all(a.startswith("engine.") for a in cell["trace_annotations"])
    chk = cell["checks"]
    assert chk["max_tokens"] == 16 and chk["cached_row_columns"] == 640 and chk["held_bytes_min"] >= 10_000_000_000
    short, long_ = chk["prompt_lens"]
    assert short < eng["prefill_chunk"] and long_ > 2 * eng["prefill_chunk"]  # under a chunk; three chunks or more
    for limit in ("logit_distance", "state_distance", "logit_margin", "expert_agreement_min",
                  "positions_agreeing_min"):
        assert limit in chk and limit in chk["logit_why"], limit
    for reading in ("no_correction", "head_decay", "bf16_state"):
        assert reading in chk["logit_why"], reading
    for why in ("send_gap_why", "pool_why"):
        assert len(tr[why]) > 100
    assert "bursts.json" in tr["lead_in"]["why"]


def test_the_configuration_is_the_catalog_s_but_for_what_it_says_is_reduced():
    config = spec.load_config(NAME)
    bench = spec.load_benchmark()
    assert spec.entry(bench, "configs", NAME)["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "linear_attn_config"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key == "linear_attn_config":
                assert config["published"][key] == value
                # the two layer lists alone are cut; no width inside the group moves
                assert {k for k in value if config[key][k] != value[k]} == {"kda_layers", "full_attn_layers"}
            elif key in config["reduced"]:
                assert config["published"][key] == value and config[key] < value
            else:
                assert config[key] == value, key
    lin = config["linear_attn_config"]
    # every width as published; the share, and the floors it keeps
    assert (config["hidden_size"], lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"],
            config["num_attention_heads"], config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"], config["kv_lora_rank"], config["moe_intermediate_size"],
            config["num_experts_per_token"], config["intermediate_size"]) == (
        2304, 32, 128, 4, 32, 128, 64, 128, 512, 1024, 8, 9216)
    assert config["q_lora_rank"] is None and config["mla_use_nope"] is True
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (8, 32, 20480)
    assert lin["kda_layers"] == [1, 2, 3, 5, 6, 7] and lin["full_attn_layers"] == [4, 8]  # two whole periods
    assert sorted(lin["kda_layers"] + lin["full_attn_layers"]) == list(range(1, config["num_hidden_layers"] + 1))
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4 and config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= config["published"]["vocab_size"]
    assert config["held"]["router_outputs"] == config["published"]["num_experts"] == 256
    assert config["held"]["expert_layers"] == 7 and config["held"]["dense_layers"] == 1
    assert "8 chips share each layer" in config["deployment"] and "8 tokens an expert" in config["deployment"]
    assert "nothing stands in for the absent chips" in config["deployment"]
    for item in ("low_rank", "kda_equations", "state_dtype", "decay_init", "mla_equations", "cached_row",
                 "router_bias", "param_dtype", "weights", "max_model_len", "engine_sizes_why", "vocab_rows"):
        assert item in config["assumed"], item
    assert config["left_out"] == {} and config["num_nextn_predict_layers"] == 0


def test_the_cut_s_arithmetic_reckoned_again():
    """The parameters of the cut from the configuration file's sizes,
    the bytes a lane holds and a cached position takes: what
    ``reduced_why`` says."""
    c = spec.load_config(NAME)
    lin, d, r = c["linear_attn_config"], c["hidden_size"], c["assumed"]["low_rank"]
    inner = lin["num_heads"] * lin["head_dim"]
    kda = (3 * d * inner + 3 * inner * lin["short_conv_kernel_size"] + 2 * (d * r + r * inner) + lin["num_heads"]
           + inner + d * lin["num_heads"] + lin["head_dim"] + inner * d)
    H = c["num_attention_heads"]
    mla = (d * H * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
           + c["kv_lora_rank"] + c["kv_lora_rank"] * H * (c["qk_nope_head_dim"] + c["v_head_dim"])
           + H * c["v_head_dim"] * d)
    assert kda == 39_514_272 and mla == 29_114_880
    expert = 3 * d * c["moe_intermediate_size"]
    outside = expert + d * 256 + 256  # the shared expert, the router and its bias
    assert expert == 7_077_888 and outside == 7_667_968
    dense = 3 * d * c["intermediate_size"]
    norms = 2 * d
    n_k, n_a = len(lin["kda_layers"]), len(lin["full_attn_layers"])
    held = ((kda + dense + norms) + (n_k - 1) * (kda + outside + c["num_experts"] * expert + norms)
            + n_a * (mla + outside + c["num_experts"] * expert + norms) + 2 * c["vocab_size"] * d + d)
    assert held == 2_092_550_080 and round(2 * held / 1e9, 2) == 4.19
    whole = (26 * (outside + 256 * expert) + 20 * kda + 7 * mla + dense + 27 * norms + 2 * 163840 * d + d)
    assert round(whole / 1e9, 1) == 49.1
    from benchmark.runners.serve_kimi_linear import lane_state_bytes

    assert lane_state_bytes(c) == n_k * (32 * 128 * 128 * 4 + 3 * 3 * 4096 * 2) == 13_025_280
    cell = spec.load_cell(CELL)
    position = n_a * cell["checks"]["cached_row_columns"] * 2
    assert position == 2560
    eng = cell["engine"]
    held_bytes = 2 * held + eng["max_batch_size"] * lane_state_bytes(c) + eng["pool_tokens"] * position
    assert held_bytes >= cell["checks"]["held_bytes_min"] and held_bytes < 13e9
    # the mean reservation (prompt + answer of the two clipped log-normals, about 5.5k) of every lane fits the pool
    assert eng["pool_tokens"] >= eng["max_batch_size"] * 5500


def test_runner_fails_at_once_where_the_program_has_no_such_family(monkeypatch):
    """The parent of PR 61 has no ``ray_tpu.models.kimi_linear``: the
    runner must raise before it deploys, not wait for a replica."""
    from benchmark.runners import serve_kimi_linear

    monkeypatch.setattr(serve_kimi_linear, "FAMILY", "ray_tpu.models.no_such_family")
    monkeypatch.setattr(serve_kimi_linear, "deploy", lambda job: pytest.fail("deployed"))
    with pytest.raises(RuntimeError, match="no ray_tpu.models.no_such_family"):
        serve_kimi_linear.run({"config": {"name": NAME}})


def test_the_two_decode_kernels_and_the_chunk_form_s_work_by_hand():
    config = spec.load_config(NAME)
    peak = spec.load_peaks()["TPU v5 lite"]
    # one decode program: 256 running lanes, 6 KDA layers
    lane_steps = 256 * 6
    work = flops_kda.kda_step_work(config, lane_steps)
    values = 32 * 128 * 128
    assert work["flops"] == 7 * lane_steps * values
    assert work["bytes"] == lane_steps * (2 * values * 4 + (5 * 32 * 128 + 32) * 4)
    least = flops.least_seconds(work, peak)
    assert least["bound"] == "memory" and least["seconds"] == pytest.approx(work["bytes"] / 819e9, rel=1e-3)
    assert 7.5e-3 < least["seconds"] < 8.5e-3  # 6.5 GB a step at the chip's 819 GB/s
    # and 256 lanes of 4,000 cached positions in each of the 2 latent layers
    attended, lane_calls = 256 * 4000 * 2, 256 * 2
    mla = flops_mla.mla_decode_work(config, attended, lane_calls)
    assert mla["flops"] == 2 * attended * 32 * (576 + 512)
    assert mla["bytes"] == attended * 1152 + lane_calls * (32 * 576 + 576 + 32 * 512) * 4
    # a chunk of 2,048 tokens through 6 KDA layers
    chunk = flops_kda.kda_chunk_work(config, 2048 * 6)
    assert chunk["flops"] == 2048 * 6 * 32 * (8 * 64 * 128 + 6 * 128 * 128) == 2048 * 6 * 32 * 163_840
    from benchmark.runners.serve_kimi_linear import KDA_KERNEL, MLA_KERNEL, kernel_roofline_pct

    # 500 such programs in the window; 100 of them in the trace: 600 calls of the KDA kernel, and of the
    # latent kernel 8 a layer a step (32 lanes a call): 1,600
    trace = {"devices": 1, "window_s": 5.0,
             "op_seconds": {"kda_decode_step tpu_custom_call": 0.9, "mla_paged_decode_attention tpu_custom_call": 0.32,
                            "fusion": 2.0},
             "op_counts": {"kda_decode_step tpu_custom_call": 600, "mla_paged_decode_attention tpu_custom_call": 1600,
                           "fusion": 9000}}
    got = kernel_roofline_pct(KDA_KERNEL, flops_kda.kda_step_work(config, 500 * lane_steps), 500 * 6, trace, peak)
    assert got == pytest.approx(100 * (work["bytes"] / 6 / 819e9) / 1.5e-3, rel=2e-3) and 60 < got < 100
    got = kernel_roofline_pct(MLA_KERNEL, flops_mla.mla_decode_work(config, 500 * attended, 500 * lane_calls),
                              500 * 16, trace, peak)
    assert got == pytest.approx(100 * (mla["bytes"] / 16 / 819e9) / 0.2e-3, rel=2e-3) and 40 < got < 100
    assert kernel_roofline_pct(KDA_KERNEL, work, 6, {"devices": 0}, peak) is None
    assert kernel_roofline_pct(KDA_KERNEL, work, 6, dict(trace, op_seconds={"fusion": 1.0}), peak) is None
    assert kernel_roofline_pct(KDA_KERNEL, work, 0, trace, peak) is None
    # the kernels' names do not match one another's pattern, nor the latent kernel under a choice
    assert not KDA_KERNEL.search("mla_paged_decode_attention") and not MLA_KERNEL.search("kda_decode_step")
    assert not MLA_KERNEL.search("mla_sparse_paged_decode_attention tpu_custom_call")
    assert not re.search("^mamba2_decode_step", "kda_decode_step tpu_custom_call")

"""The Nemotron-3-Nano cell on the CPU, beside ``test_benchmark.py``:

    python -m pytest benchmark/tests -q

The cell end to end at the ``nemotron_3_nano_tiny`` preset behind
``run_cell``'s rehearsal argument (traced and untraced), its metric names
against the entries of ``BENCHMARK.json``, the configuration file against
the catalog's published keys, the runner's refusal of a program without
the family, and the arithmetic of the two decode kernels' and the held
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

from benchmark import flops, flops_ssm, spec  # noqa: E402
from benchmark.tests import rehearsal  # noqa: E402

CELL = "nemotron-3-nano.serve.reason-backlog"
TINY = {"n_layer": 8, "n_embd": 64, "n_head": 4, "n_positions": 512, "vocab_size": 256,
        "vocab_rows": 256, "dtype": "float32"}
TINY_CONFIG = {
    "preset": "nemotron_3_nano_tiny", "num_hidden_layers": 8, "hybrid_override_pattern": "MEM*EM*E",
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "mamba_num_heads": 8, "mamba_head_dim": 8, "ssm_state_size": 16, "n_groups": 2, "chunk_size": 8,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 48, "n_routed_experts": 32,
    "held": {"experts_first": 0, "experts_held": 32, "vocab_first": 0, "vocab_rows": 256,
             "router_outputs": 32, "num_experts_per_tok": 6},
    "published": {"n_routed_experts": 32, "vocab_size": 256},
}
TINY_CELL = {
    "engine": {"max_batch_size": 4, "block_size": 8, "pool_tokens": 2048, "max_queue": 256,
               "max_model_len": 512, "prefill_chunk": 32},
    "traffic": {"prompt_len": {"median": 60, "sigma": 0.6, "lo": 16, "hi": 200},
                "max_tokens": {"median": 12, "sigma": 0.5, "lo": 4, "hi": 32},
                "max_total_tokens": 512, "trace_seconds": 0.5, "clients": 8, "pool_requests": 32,
                "lead_in": {"after_full_s": 0.5, "at_most_s": 30.0}, "send_gap_s": 0.01},
    "checks": {"prompt_lens": [12, 40, 75], "max_tokens": 6, "logit_margin": 1e-3, "logit_distance": 3e-4,
               "expert_agreement_min": 0.99, "positions_agreeing_min": 0.9},
}
# what a traced run prints without a chip: the counters' metrics and the
# host clock's (the seven that read the device trace find nothing on the
# CPU and are left out)
ON_THE_CPU = {"engine_step_ms.backlog", "lanes_busy_pct.backlog", "host_ms_per_step", "prefill_share_pct",
              "prefill_pad_ratio", "prefill_chunk_ms", "decode_overlap_pct",
              "kv_gather_useful_pct", "deploy_ready_s.serve", "moe_experts_hit_pct",
              "moe_imbalance", "moe_held_share_pct", "ssm_state_mb_per_step", "kv_blocks_whole_pct"}
FROM_THE_DEVICE = {"device_idle_pct.backlog", "moe_gmm_busy_pct", "moe_gmm_roofline_pct",
                   "mamba2_decode_step_busy_pct", "mamba2_decode_step_roofline",
                   "gqa_paged_decode_attention_busy_pct", "gqa_paged_decode_attention_roofline"}


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
        # 4 lanes x 3 Mamba layers x (8 x 8 x 16 float32 + 3 x 96 float32), read and written a decode
        # step, and a lane's share of it for every chunk program between two steps
        a_step = 2 * 4 * 3 * (8 * 8 * 16 + 3 * 96) * 4 / 1e6
        assert a_step <= out["metrics"]["ssm_state_mb_per_step"]["value"] < 2 * a_step
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
    # the cell and its configuration are there among eight or more; of the cells at most a
    # quarter, rounded down, are on four chips, and one always (the driver's rule)
    names = [w["name"] for w in bench["workloads"]]
    assert 8 <= len(names) <= 24 and sum(w["chips"] == 4 for w in bench["workloads"]) <= max(1, len(names) // 4)
    assert CELL in names and "nemotron-3-nano" in [c["name"] for c in bench["configs"]]
    cell, wl = spec.load_cell(CELL), spec.entry(bench, "workloads", CELL)
    assert (cell["why"], cell["config"], cell["chips"]) == (wl["why"], wl["config"], 1)
    # the traffic and the engine the issue names
    tr, eng = cell["traffic"], cell["engine"]
    assert (tr["clients"], tr["pool_requests"], eng["max_batch_size"], eng["block_size"]) == (256, 512, 128, 64)
    assert tr["prompt_len"] == {"median": 512, "sigma": 0.8, "lo": 128, "hi": 4096}
    assert tr["max_tokens"] == {"median": 512, "sigma": 0.7, "lo": 128, "hi": 2048}
    assert tr["max_total_tokens"] == eng["max_model_len"] == 6144 and eng["prefill_chunk"] == 2048
    assert (tr["send_gap_s"], tr["trace_seconds"], tr["mode"]) == (0.05, 5, "closed")
    assert 262144 <= eng["pool_tokens"] <= 393216 and eng["pool_tokens"] % 65536 == 0
    # one of the checked prompts is longer than a chunk: a state and a tail cross a chunk boundary
    assert max(cell["checks"]["prompt_lens"]) > eng["prefill_chunk"]
    assert len(cell["checks"]["prompt_lens"]) * cell["checks"]["max_tokens"] > 32


def test_the_configuration_is_the_catalog_s_but_for_what_it_says_is_reduced():
    config = spec.load_config("nemotron-3-nano")
    bench = spec.load_benchmark()
    assert spec.entry(bench, "configs", "nemotron-3-nano")["reduced"] == config["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts", "vocab_size"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in config["reduced"]:
                assert config["published"][key] == value and len(str(config[key])) <= len(str(value))
            else:
                assert config[key] == value, key
    # every width as published; the share, and the floors it keeps
    assert (config["hidden_size"], config["mamba_num_heads"], config["mamba_head_dim"], config["ssm_state_size"],
            config["n_groups"], config["conv_kernel"], config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["moe_intermediate_size"], config["moe_shared_expert_intermediate_size"],
            config["num_experts_per_tok"], config["routed_scaling_factor"]) == (
        2688, 64, 64, 128, 8, 4, 32, 2, 128, 1856, 3712, 6, 2.5)
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"]) == (26, 32, 32768)
    pattern = config["hybrid_override_pattern"]
    assert pattern == config["published"]["hybrid_override_pattern"][:26] and len(pattern) == 26
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) == (12, 11, 3)
    assert pattern.startswith("MEMEM*EMEMEM*")  # the opening and a whole period
    assert config["n_routed_experts"] >= 8 and config["vocab_size"] * 8 >= config["published"]["vocab_size"]
    assert config["held"]["router_outputs"] == config["published"]["n_routed_experts"] == 128
    assert "FOUR chips share each layer" in config["deployment"] and "TWO such groups" in config["deployment"]
    for item in ("rotation", "router_scoring", "d_inner", "gated_norm", "state_dtype", "expert_layout", "weights",
                 "param_dtype", "max_model_len", "engine_sizes_why", "vocab_rows"):
        assert item in config["assumed"], item


def test_runner_fails_at_once_where_the_program_has_no_such_family(monkeypatch):
    """The parent of PR 38 has no ``ray_tpu.models.nemotron_h``: the
    runner must raise before it deploys, not wait for a replica."""
    from benchmark.runners import serve_nemotron_3_nano

    monkeypatch.setattr(serve_nemotron_3_nano, "FAMILY", "ray_tpu.models.no_such_family")
    monkeypatch.setattr(serve_nemotron_3_nano, "deploy", lambda job: pytest.fail("deployed"))
    with pytest.raises(RuntimeError, match="no ray_tpu.models.no_such_family"):
        serve_nemotron_3_nano.run({"config": {"name": "nemotron-3-nano"}})


def test_the_stated_cache_is_three_paged_layers_and_two_arrays_a_mamba_layer():
    from benchmark.runners.serve_nemotron_3_nano import stated_cache

    cache = stated_cache(spec.load_config("nemotron-3-nano"), spec.load_cell(CELL), "bfloat16")
    assert len(cache) == 2 + 24
    assert cache["k_pages"] == cache["v_pages"] == [[3, 393216 + 64, 256], "bfloat16"]
    assert cache["conv_tail_11"] == [[128, 3 * 6144], "bfloat16"]
    assert cache["ssm_state_11"] == [[128, 64, 64, 128], "float32"]
    # a lane: 25.6 MB; the lanes: 3.28 GB; K and V: 1.21 GB
    lane = 12 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)
    assert lane == 25_608_192 and 128 * lane == 3_277_848_576
    assert 2 * 3 * (393216 + 64) * 256 * 2 == 1_208_156_160


def test_decode_kernels_and_held_experts_work_and_roofline_shares_by_hand():
    config = spec.load_config("nemotron-3-nano")
    peak = spec.load_peaks()["TPU v5 lite"]
    from benchmark.runners.serve_nemotron_3_nano import (
        GQA_KERNEL, SSM_KERNEL, gmm_roofline_pct, kernel_roofline_pct,
    )

    # one decode program: 128 running lanes in each of 12 Mamba layers
    work = flops_ssm.ssm_step_work(config, 128 * 12)
    token = (2 * 4096 + 2 * 8 * 128 + 64) * 4
    assert work["bytes"] == 128 * 12 * (2 * 2_097_152 + token) and work["flops"] == 5 * 128 * 12 * 524_288
    least = flops.least_seconds(work, peak)
    assert least["bound"] == "memory"  # 1.2 operations a byte
    assert least["seconds"] == pytest.approx(128 * 12 * (4_194_304 + token) / peak["hbm_bytes_per_s"])
    # 400 such programs in the window; 100 of them in the trace, 1,200 calls taking 0.96 s
    before = {"ssm_lane_steps": 0, "kv_positions_attended": 0, "steps": 0}
    after = {"ssm_lane_steps": 400 * 128 * 12, "kv_positions_attended": 400 * 128 * 1300 * 3, "steps": 400,
             "max_batch_size": 128}
    trace = {"devices": 1, "window_s": 5.0,
             "op_seconds": {"mamba2_decode_step tpu_custom_call": 0.96, "gqa_paged_decode_attention tpu_custom_call": 0.09,
                            "moe_gmm tpu_custom_call": 1.5, "fusion": 2.0},
             "op_counts": {"mamba2_decode_step tpu_custom_call": 1200, "gqa_paged_decode_attention tpu_custom_call": 300}}

    def ssm(done, _):
        return flops_ssm.ssm_step_work(config, done)

    def gqa(done, lane_calls):
        return flops_ssm.gqa_decode_work(config, done, lane_calls)

    # a call's least time is a twelfth of the program's; a call took 0.8 ms
    assert kernel_roofline_pct(SSM_KERNEL, "ssm_lane_steps", 12, ssm, trace, before, after, peak) == pytest.approx(
        100 * (least["seconds"] / 12) / 0.8e-3)
    assert kernel_roofline_pct(SSM_KERNEL, "ssm_lane_steps", 12, ssm, {"devices": 0}, before, after, peak) is None
    assert kernel_roofline_pct(SSM_KERNEL, "ssm_lane_steps", 12, ssm, dict(trace, op_seconds={"fusion": 1.0}),
                               before, after, peak) is None
    assert kernel_roofline_pct(SSM_KERNEL, "ssm_lane_steps", 12, ssm, trace, {"steps": 0},
                               {"steps": 400, "max_batch_size": 128}, peak) is None
    # the grouped-query kernel: 1,024 B and 32 x 2 x 2 x 128 operations an attended position
    att = flops_ssm.gqa_decode_work(config, 128 * 1300 * 3, 128 * 3)
    assert att["flops"] == 128 * 1300 * 3 * 32 * 2 * 2 * 128
    assert att["bytes"] == 128 * 1300 * 3 * 1024 + 128 * 3 * (2 * 32 + 2 * 2) * 128 * 4
    assert att["flops"] / (128 * 1300 * 3 * 1024) == 16  # under the ridge of 240: memory
    least_att = flops.least_seconds(att, peak)["seconds"]
    assert kernel_roofline_pct(GQA_KERNEL, "kv_positions_attended", 3, gqa, trace, before, after, peak) == (
        pytest.approx(100 * (least_att / 3) / 0.3e-3))
    # the held experts: a pair is TWO 2688 x 1856 matmuls, an expert hit 20.0 MB of them
    moe = flops_ssm.held_experts_work(config, 768, 32)
    assert moe["flops"] == 2 * 768 * 2 * 2688 * 1856
    assert moe["bytes"] == 32 * 2 * 2688 * 1856 * 2 + 768 * 2 * (2688 + 1856) * 2
    assert 32 * 2 * 2688 * 1856 * 2 == 32 * 19_955_712
    # 2 s of trace in which 80 programs x 11 layers computed 190 pairs over 31 experts each
    start = {"t": 10.0, "moe_pairs": 0, "moe_experts_hit": 0}
    end = {"t": 12.0, "moe_pairs": 80 * 11 * 190, "moe_experts_hit": 80 * 11 * 31}
    least_s = flops.least_seconds(flops_ssm.held_experts_work(config, 80 * 11 * 190, 80 * 11 * 31), peak)["seconds"]
    assert gmm_roofline_pct(config, trace, start, end, peak) == pytest.approx(100 * (least_s / 2.0) / (1.5 / 5.0))
    assert gmm_roofline_pct(config, trace, start, {"t": 12.0}, peak) is None

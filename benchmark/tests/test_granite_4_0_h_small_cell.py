"""The Granite 4.0-H cell on the CPU, beside ``test_benchmark.py``:

    python -m pytest benchmark/tests -q

The cell end to end at the ``granite_4_0_h_small_tiny`` preset behind
``run_cell``'s rehearsal argument (traced and untraced), its metric names
against the entries of ``BENCHMARK.json``, the configuration file against
the catalog's published keys and its own arithmetic, the runner's refusal
of a program without the family, and the arithmetic of the two decode
kernels', the held SwiGLU experts' and a chunk's least work.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import flops, flops_granite, spec  # noqa: E402
from benchmark.tests import rehearsal  # noqa: E402

CELL = "granite-4.0-h-small.serve.rag-backlog"
NAME = "granite-4.0-h-small"
TINY = {"n_layer": 4, "n_embd": 64, "n_head": 8, "n_positions": 512, "vocab_size": 256,
        "vocab_rows": 256, "dtype": "float32"}
TINY_CONFIG = {
    "preset": "granite_4_0_h_small_tiny", "num_hidden_layers": 4,
    "layer_types": ["mamba", "attention", "mamba", "attention"],
    "hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 2,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16, "mamba_n_groups": 1, "mamba_chunk_size": 8,
    "intermediate_size": 32, "shared_intermediate_size": 48, "num_local_experts": 16, "num_experts_per_tok": 4,
    "attention_multiplier": 0.25,
    "held": {"experts_first": 0, "experts_held": 16, "vocab_first": 0, "vocab_rows": 256,
             "router_outputs": 16, "num_experts_per_tok": 4},
    "published": {"num_local_experts": 16, "vocab_size": 256},
}
TINY_CELL = {
    "engine": {"max_batch_size": 4, "block_size": 8, "pool_tokens": 2048, "max_queue": 256,
               "max_model_len": 512, "prefill_chunk": 32},
    "traffic": {"prompt_len": {"median": 60, "sigma": 0.6, "lo": 16, "hi": 200},
                "max_tokens": {"median": 12, "sigma": 0.5, "lo": 4, "hi": 32},
                "max_total_tokens": 512, "trace_seconds": 0.5, "clients": 8, "pool_requests": 32,
                "lead_in": {"after_full_s": 0.5, "at_most_s": 30.0}, "send_gap_s": 0.01},
    "checks": {"prompt_lens": [12, 40, 75], "max_tokens": 6, "logit_margin": 1e-5, "logit_distance": 3e-6,
               "expert_agreement_min": 0.99, "positions_agreeing_min": 0.9},
}
# what a traced run prints without a chip: the counters' metrics and the
# host clock's (those that read the device trace or the chip's peak find
# nothing on the CPU and are left out)
ON_THE_CPU = {"engine_step_ms.backlog", "lanes_busy_pct.backlog", "host_ms_per_step",
              "prefill_share_pct", "prefill_pad_ratio", "prefill_chunk_ms",
              "decode_overlap_pct", "kv_gather_useful_pct", "deploy_ready_s.serve",
              "moe_experts_hit_pct", "moe_imbalance", "moe_held_share_pct",
              "ssm_state_mb_per_step", "kv_blocks_whole_pct"}
FROM_THE_DEVICE = {"device_idle_pct.backlog", "moe_gmm_busy_pct", "moe_gmm_roofline_pct",
                   "mamba2_decode_step_busy_pct", "mamba2_decode_step_roofline",
                   "gqa_paged_decode_attention_busy_pct", "gqa_paged_decode_attention_roofline",
                   "prefill_mfu_pct"}


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
        assert out["metrics"]["moe_held_share_pct"]["value"] == 100  # the tiny preset holds all 16
        assert 0 < out["metrics"]["kv_gather_useful_pct"]["value"] <= 100
        # 4 lanes x 2 Mamba layers x (8 x 16 x 16 float32 + 3 x 160 float32), read and written a decode
        # step, and a lane's share of it for every chunk program between two steps
        a_step = 2 * 4 * 2 * (8 * 16 * 16 + 3 * 160) * 4 / 1e6
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
    # the cell and its configuration are there, on one chip
    names = [w["name"] for w in bench["workloads"]]
    assert CELL in names and NAME in [c["name"] for c in bench["configs"]]
    # the driver's rule: at most a quarter of the cells, rounded down, on four chips, and one always
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(1, len(names) // 4) and len(names) <= 24
    cell, wl = spec.load_cell(CELL), spec.entry(bench, "workloads", CELL)
    assert (cell["why"], cell["config"], cell["chips"]) == (wl["why"], wl["config"], 1) and len(wl["why"]) <= 200
    # the traffic and the engine the issue names
    tr, eng = cell["traffic"], cell["engine"]
    assert (tr["clients"], tr["pool_requests"], eng["max_batch_size"], eng["block_size"]) == (64, 192, 32, 64)
    assert tr["prompt_len"] == {"median": 6144, "sigma": 0.6, "lo": 2048, "hi": 16384}
    assert tr["max_tokens"] == {"median": 256, "sigma": 0.6, "lo": 64, "hi": 1024}
    assert tr["max_total_tokens"] == eng["max_model_len"] == 17408 and eng["prefill_chunk"] == 2048
    assert (tr["send_gap_s"], tr["trace_seconds"], tr["mode"]) == (0.05, 5, "closed")
    assert 393216 <= eng["pool_tokens"] <= 557056 and (557056 - eng["pool_tokens"]) % 65536 == 0
    # two of the checked prompts are longer than a chunk: a state and a tail cross one and two boundaries
    lens = cell["checks"]["prompt_lens"]
    assert lens == [64, 320, 2304, 5000] and cell["checks"]["max_tokens"] == 64
    assert sorted(-(-n // eng["prefill_chunk"]) for n in lens) == [1, 1, 2, 3]


def test_the_configuration_is_the_catalog_s_but_for_what_it_says_is_reduced():
    config = spec.load_config(NAME)
    bench = spec.load_benchmark()
    assert spec.entry(bench, "configs", NAME)["reduced"] == config["reduced"] == [
        "num_hidden_layers", "layer_types", "num_local_experts", "vocab_size"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == NAME)
        assert config["source"] == row["source_url"] == spec.entry(bench, "configs", NAME)["source"]
        for key, value in row["config"].items():
            if key in config["reduced"]:
                assert config["published"][key] == value and len(str(config[key])) <= len(str(value))
            else:
                assert config[key] == value, key
    # every width and multiplier as published; the share, and the floors it keeps
    assert (config["hidden_size"], config["mamba_n_heads"], config["mamba_d_head"], config["mamba_d_state"],
            config["mamba_n_groups"], config["mamba_d_conv"], config["mamba_chunk_size"], config["mamba_expand"],
            config["num_attention_heads"], config["num_key_value_heads"], config["intermediate_size"],
            config["shared_intermediate_size"], config["num_experts_per_tok"]) == (
        4096, 128, 64, 128, 1, 4, 256, 2, 32, 8, 768, 1536, 10)
    assert (config["embedding_multiplier"], config["residual_multiplier"], config["attention_multiplier"],
            config["logits_scaling"], config["tie_word_embeddings"], config["position_embedding_type"]) == (
        12, 0.22, 0.0078125, 16, True, "nope")
    assert (config["num_hidden_layers"], config["num_local_experts"], config["vocab_size"]) == (10, 36, 50176)
    kinds = config["layer_types"]
    assert kinds == config["published"]["layer_types"][:10] and kinds.index("attention") == 5
    assert (kinds.count("mamba"), kinds.count("attention")) == (9, 1)  # a whole period, 9 : 1 as published
    assert config["num_local_experts"] >= 8 and config["vocab_size"] * 8 >= config["published"]["vocab_size"]
    assert config["held"]["router_outputs"] == config["published"]["num_local_experts"] == 72
    assert "TWO chips share each layer" in config["deployment"] and "FOUR such pairs" in config["deployment"]
    for item in ("rotation", "router_scoring", "d_inner", "gated_norm", "state_dtype", "expert_width", "head_dim",
                 "multipliers", "shared_expert", "weights", "param_dtype", "max_model_len", "engine_sizes_why",
                 "vocab_rows"):
        assert item in config["assumed"], item


def test_the_cut_s_arithmetic_reckoned_again():
    """The parameters held, from the file's own sizes: what
    ``reduced_why`` and the issue's table say."""
    c = spec.load_config(NAME)
    d, f, fs = c["hidden_size"], c["intermediate_size"], c["shared_intermediate_size"]
    inner = c["mamba_n_heads"] * c["mamba_d_head"]
    conv = inner + 2 * c["mamba_n_groups"] * c["mamba_d_state"]
    mamba = d * (inner + conv + c["mamba_n_heads"]) + conv * c["mamba_d_conv"] + conv + 3 * c["mamba_n_heads"] + (
        inner + inner * d)
    kv = c["num_key_value_heads"] * d // c["num_attention_heads"]
    attention = 2 * d * d + 2 * d * kv
    experts = d * c["held"]["router_outputs"] + 3 * d * fs + c["held"]["experts_held"] * 3 * d * f
    assert (mamba, attention, experts) == (102_286_976, 41_943_040, 358_907_904)
    layers = 9 * (mamba + experts + 2 * d) + (attention + experts + 2 * d)
    ends = c["vocab_size"] * d + d
    assert (layers, ends, layers + ends) == (4_551_686_784, 205_524_992, 4_757_211_776)
    for number in ("102,286,976", "41,943,040", "358,907,904", "4,757,211,776", "1,222,557,696", "2,281,963,520"):
        assert number in c["reduced_why"]["num_hidden_layers"], number
    # more than a quarter of a 16 GB chip by the weights alone
    assert 2 * (layers + ends) > 0.25 * spec.load_peaks()["TPU v5 lite"]["hbm_bytes"]


def test_runner_fails_at_once_where_the_program_has_no_such_family(monkeypatch):
    """The parent of PR 41 has no ``ray_tpu.models.granite_hybrid``: the
    runner must raise before it deploys, not wait for a replica."""
    from benchmark.runners import serve_granite_4_0_h_small as runner

    monkeypatch.setattr(runner, "FAMILY", "ray_tpu.models.no_such_family")
    monkeypatch.setattr(runner, "deploy", lambda job: pytest.fail("deployed"))
    with pytest.raises(RuntimeError, match="no ray_tpu.models.no_such_family"):
        runner.run({"config": {"name": NAME}})


def test_the_stated_cache_is_one_paged_layer_and_two_arrays_a_mamba_layer():
    from benchmark.runners.serve_granite_4_0_h_small import stated_cache

    cell = spec.load_cell(CELL)
    slots = cell["engine"]["pool_tokens"] + 64
    cache = stated_cache(spec.load_config(NAME), cell, "bfloat16")
    assert len(cache) == 2 + 18
    assert cache["k_pages"] == cache["v_pages"] == [[1, slots, 1024], "bfloat16"]
    assert cache["conv_tail_8"] == [[32, 3 * 8448], "bfloat16"]
    assert cache["ssm_state_8"] == [[32, 128, 64, 128], "float32"]
    # a lane: 38.2 MB; the lanes: 1.22 GB; K and V: 2.28 GB at the pool the issue names
    lane = 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)
    assert lane == 38_204_928 and 32 * lane == 1_222_557_696
    assert 2 * (557056 + 64) * 1024 * 2 == 2_281_963_520


def test_decode_kernels_held_experts_and_chunk_work_and_their_shares_by_hand():
    config = spec.load_config(NAME)
    peak = spec.load_peaks()["TPU v5 lite"]
    from benchmark.runners.serve_granite_4_0_h_small import (
        GQA_KERNEL, SSM_KERNEL, gmm_roofline_pct, kernel_roofline_pct,
    )

    # one decode program: 32 running lanes in each of 9 Mamba layers, a state of 128 heads
    work = flops_granite.ssm_step_work(config, 32 * 9)
    token = (2 * 8192 + 2 * 1 * 128 + 128) * 4
    assert work["bytes"] == 32 * 9 * (2 * 4_194_304 + token) and work["flops"] == 5 * 32 * 9 * 1_048_576
    least = flops.least_seconds(work, peak)
    assert least["bound"] == "memory"  # 0.6 operations a byte
    # 400 such programs in the window; 100 of them in the trace, 900 calls taking 0.9 s
    before = {"ssm_lane_steps": 0, "kv_positions_attended": 0, "steps": 0}
    after = {"ssm_lane_steps": 400 * 32 * 9, "kv_positions_attended": 400 * 32 * 7000, "steps": 400,
             "max_batch_size": 32}
    trace = {"devices": 1, "window_s": 5.0,
             "op_seconds": {"mamba2_decode_step tpu_custom_call": 0.9, "gqa_paged_decode_attention tpu_custom_call": 0.2,
                            "moe_gmm tpu_custom_call": 1.5, "fusion": 2.0},
             "op_counts": {"mamba2_decode_step tpu_custom_call": 900, "gqa_paged_decode_attention tpu_custom_call": 100}}

    def ssm(done, _):
        return flops_granite.ssm_step_work(config, done)

    def gqa(done, lane_calls):
        return flops_granite.gqa_decode_work(config, done, lane_calls)

    # a call's least time is a ninth of the program's; a call took 1 ms
    assert kernel_roofline_pct(SSM_KERNEL, "ssm_lane_steps", 9, ssm, trace, before, after, peak) == pytest.approx(
        100 * (least["seconds"] / 9) / 1e-3)
    assert kernel_roofline_pct(SSM_KERNEL, "ssm_lane_steps", 9, ssm, {"devices": 0}, before, after, peak) is None
    # the grouped-query kernel at 8 K/V heads: 4,096 B and 32 x 2 x 2 x 128 operations an attended position
    att = flops_granite.gqa_decode_work(config, 32 * 7000, 32)
    assert att["flops"] == 32 * 7000 * 32 * 2 * 2 * 128
    assert att["bytes"] == 32 * 7000 * 4096 + 32 * (2 * 32 + 2 * 8) * 128 * 4
    assert att["flops"] / (32 * 7000 * 4096) == 4  # four queries a row read: far under the ridge of 240
    least_att = flops.least_seconds(att, peak)["seconds"]
    assert kernel_roofline_pct(GQA_KERNEL, "kv_positions_attended", 1, gqa, trace, before, after, peak) == (
        pytest.approx(100 * least_att / 2e-3))
    # the held experts: a pair is THREE 4096 x 768 matmuls, an expert hit 18.9 MB of them
    moe = flops_granite.held_experts_work(config, 160, 36)
    assert moe["flops"] == 2 * 160 * 3 * 4096 * 768
    assert moe["bytes"] == 36 * 3 * 4096 * 768 * 2 + 160 * (2 * 4096 + 3 * 768) * 2
    assert 3 * 4096 * 768 * 2 == 18_874_368
    # 2 s of trace in which 60 programs x 10 layers computed 160 pairs over 35 experts each
    start = {"t": 10.0, "moe_pairs": 0, "moe_experts_hit": 0}
    end = {"t": 12.0, "moe_pairs": 60 * 10 * 160, "moe_experts_hit": 60 * 10 * 35}
    least_s = flops.least_seconds(
        flops_granite.held_experts_work(config, 60 * 10 * 160, 60 * 10 * 35), peak)["seconds"]
    assert gmm_roofline_pct(config, trace, start, end, peak) == pytest.approx(100 * (least_s / 2.0) / (1.5 / 5.0))
    assert gmm_roofline_pct(config, trace, start, {"t": 12.0}, peak) is None
    # a chunk's token: the issue's 3.24 GFLOP of matmuls (9 x 102M, 42M, 10 x 18.9M, 10 x 5 x 9.44M, all x 2)
    # and the recurrence's 5 operations a state value in 9 layers
    matmuls = 2 * (9 * (4096 * 16768 + 8192 * 4096) + 41_943_040 + 10 * (4096 * 72 + 18_874_368 + 5 * 9_437_184))
    assert flops_granite.chunk_token_flops(config) == matmuls + 9 * 5 * 1_048_576
    assert 3.2e9 < matmuls < 3.3e9
    # 40 prompts of 7,000 tokens in 22 s of chunk programs: 21% of the peak
    share = flops_granite.prefill_mfu_pct(config, 280_000, 22.0, peak)
    assert share == pytest.approx(100 * 280_000 * flops_granite.chunk_token_flops(config) / (22.0 * 197e12))
    assert 20 < share < 22 and flops_granite.prefill_mfu_pct(config, 0, 22.0, peak) is None
    assert flops_granite.prefill_mfu_pct(config, 280_000, 22.0, None) is None

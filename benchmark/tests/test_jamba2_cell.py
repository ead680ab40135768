"""The Jamba2 cell on the CPU, beside ``test_benchmark.py``:

    python -m pytest benchmark/tests -q

The cell end to end at the ``jamba2_tiny`` preset behind ``run_cell``'s
rehearsal argument (traced and untraced), its metric names against the
entries of ``BENCHMARK.json``, the configuration file against the
catalog's published keys (NOTHING reduced) and its own arithmetic, the
runner's refusal of a program without the family, the stated cache, and
the arithmetic of the two Mamba-1 kernels', the grouped-query kernel's
and a chunk's least work.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import flops, flops_jamba, flops_ssm, spec  # noqa: E402
from benchmark.tests import rehearsal  # noqa: E402

CELL = "jamba2-3b.serve.think-backlog"
NAME = "jamba2-3b"


def parameters(c: dict) -> int:
    """The parameter count from a configuration's sizes, as the file's
    ``parameters_why`` reckons it."""
    d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    inner, n, r, k = c["mamba_expand"] * d, c["mamba_d_state"], c["mamba_dt_rank"], c["mamba_d_conv"]
    mamba = d * 2 * inner + inner * k + inner + inner * (r + 2 * n) + (r + 2 * n) + r * inner + inner \
        + inner * n + inner + inner * d
    kv = c["num_key_value_heads"] * (d // c["num_attention_heads"])
    attention = 2 * d * d + 2 * d * kv
    mlp = 3 * d * f + 2 * d
    layers = c["num_hidden_layers"]
    attn = sum(1 for i in range(layers) if i % c["attn_layer_period"] == c["attn_layer_offset"])
    return (layers - attn) * (mamba + mlp) + attn * (attention + mlp) + v * d + d


TINY = {"n_layer": 8, "n_embd": 40, "n_head": 5, "n_positions": 256, "vocab_size": 256,
        "vocab_rows": 256, "dtype": "float32"}
TINY_CONFIG = {
    "preset": "jamba2_tiny", "num_hidden_layers": 8, "attn_layer_period": 4, "attn_layer_offset": 2,
    "hidden_size": 40, "num_attention_heads": 5, "num_key_value_heads": 1, "intermediate_size": 64,
    "mamba_expand": 2, "mamba_d_state": 4, "mamba_d_conv": 4, "mamba_dt_rank": 4, "vocab_size": 256,
    "assumed": {"vocab_rows": 256, "head_dim": 8,
                "layers_block_type": ["mamba", "mamba", "attention", "mamba"] * 2},
}
TINY_CONFIG["parameters"] = parameters(TINY_CONFIG)
TINY_CELL = {
    "engine": {"max_batch_size": 4, "block_size": 4, "pool_tokens": 2048, "max_queue": 256,
               "max_model_len": 256, "prefill_chunk": 8},
    "traffic": {"prompt_len": {"median": 12, "sigma": 1.0, "lo": 2, "hi": 60},
                "max_tokens": {"median": 12, "sigma": 0.7, "lo": 4, "hi": 40},
                "max_total_tokens": 256, "trace_seconds": 0.5, "clients": 8, "pool_requests": 32,
                "lead_in": {"after_full_s": 0.5, "at_most_s": 30.0}, "send_gap_s": 0.01},
    # shorter than the convolution; one chunk; three chunks with a ragged last one
    "checks": {"prompt_lens": [3, 8, 21], "max_tokens": 20, "logit_margin": 1e-5, "logit_distance": 3e-6},
}
# what a traced run prints without a chip: the counters' metrics and the
# host clock's (those that read the device trace or the chip's peak find
# nothing on the CPU and are left out)
ON_THE_CPU = {"engine_step_ms.backlog", "lanes_busy_pct.backlog", "host_ms_per_step", "prefill_share_pct",
              "prefill_chunk_ms", "deploy_ready_s.serve", "prefill_pad_ratio", "decode_overlap_pct",
              "kv_gather_useful_pct", "ssm_state_mb_per_step", "ssm_share_of_step_bytes_pct",
              "kv_blocks_whole_pct"}
FROM_THE_DEVICE = {"device_idle_pct.backlog", "mamba1_decode_step_busy_pct", "mamba1_decode_step_roofline",
                   "mamba1_chunk_scan_busy_pct", "mamba1_chunk_scan_roofline",
                   "gqa_paged_decode_attention_busy_pct", "gqa_paged_decode_attention_roofline", "prefill_mfu_pct"}


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
        assert 0 < out["metrics"]["ssm_share_of_step_bytes_pct"]["value"] < 100
    else:
        assert set(out["metrics"]) == {"serve_out_tokens_per_s", "setup_s"}
        assert out["metrics"]["serve_out_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("wrong", ["inner_norms_off", "no_dt_bias", "attention_one_layer_early", "state_bf16"])
def test_a_reference_told_another_model_is_not_correct(monkeypatch, wrong):
    """At the tiny preset in float32 the limits are tight enough that
    each wrong reading fails by itself."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    out = _run(0, {"wrong_on_purpose": wrong})
    assert out is not None and not out["correct"]


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
    # the five entries of the cell's own, each with a file, kernels but the one the counters give
    own = {n: per_layer[n] for n in (
        "mamba1_decode_step_busy_pct", "mamba1_decode_step_roofline", "mamba1_chunk_scan_busy_pct",
        "mamba1_chunk_scan_roofline", "ssm_share_of_step_bytes_pct")}
    assert {m["layer"] for n, m in own.items() if n.startswith("mamba1")} == {"kernels"}
    assert (own["ssm_share_of_step_bytes_pct"]["layer"], own["ssm_share_of_step_bytes_pct"]["source"]) == (
        "models", "program_counter")
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
    assert (tr["clients"], tr["pool_requests"], eng["max_batch_size"], eng["block_size"]) == (512, 1024, 256, 64)
    assert tr["prompt_len"] == {"median": 256, "sigma": 1.0, "lo": 32, "hi": 4096}
    assert tr["max_tokens"] == {"median": 1024, "sigma": 0.7, "lo": 256, "hi": 4096}
    assert tr["max_total_tokens"] == eng["max_model_len"] == 8192 and eng["prefill_chunk"] == 2048
    assert tr["send_gap_s"] in (0.05, 0.025) and (tr["trace_seconds"], tr["mode"]) == (5, "closed")
    assert eng["max_queue"] == 2048 and eng["max_batch_size"] * tr["send_gap_s"] <= 15
    # the issue's fallback order: the pool down in steps of 131,072 to 524,288
    assert 524288 <= eng["pool_tokens"] <= 786432 and (786432 - eng["pool_tokens"]) % 131072 == 0
    # inside one small bucket; one mid bucket; two grid steps of the chunk kernel and more; across a chunk boundary
    lens = cell["checks"]["prompt_lens"]
    assert lens == [40, 320, 1100, 2304] and cell["checks"]["max_tokens"] == 64
    assert sorted(-(-n // eng["prefill_chunk"]) for n in lens) == [1, 1, 1, 2]
    # the warm-up sends one prompt of each bucket the mix can use, and one of two chunks
    from benchmark.runners.serve_jamba2 import warm_up_lens

    assert warm_up_lens(cell) == [32, 64, 128, 256, 512, 1024, 2048, 4096]
    assert len(cell["trace_annotations"]) == 9 and all(a.startswith("engine.") for a in cell["trace_annotations"])


def test_the_configuration_is_the_catalog_s_with_nothing_reduced():
    config = spec.load_config(NAME)
    bench = spec.load_benchmark()
    assert spec.entry(bench, "configs", NAME)["reduced"] == config["reduced"] == []
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "AI21-Jamba2-3B")
        assert config["source"] == row["source_url"] == spec.entry(bench, "configs", NAME)["source"]
        for key, value in row["config"].items():
            assert config[key] == value, key
    assert (config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"],
            config["intermediate_size"], config["vocab_size"], config["num_hidden_layers"]) == (
        2560, 20, 1, 8192, 65536, 28)
    assert (config["mamba_expand"], config["mamba_d_state"], config["mamba_d_conv"], config["mamba_dt_rank"]) == (
        2, 16, 4, 160)
    assert (config["num_experts"], config["num_experts_per_tok"], config["tie_word_embeddings"]) == (1, 1, True)
    kinds = config["assumed"]["layers_block_type"]
    assert len(kinds) == 28 and [i for i, k in enumerate(kinds) if k == "attention"] == [7, 21]
    assert kinds == ["attention" if i % config["attn_layer_period"] == config["attn_layer_offset"] else "mamba"
                     for i in range(28)]
    assert "ONE chip" in config["deployment"] and "nothing is left out" in config["deployment"]
    for item in ("layers_block_type_why", "head_dim", "experts", "rotation", "d_inner", "inner_norms",
                 "param_dtype", "state_dtype", "max_model_len", "weights", "engine_sizes_why", "vocab_rows"):
        assert item in config["assumed"], item
    assert spec.sizes(config) == {"n_layer": 28, "n_embd": 2560, "n_head": 20, "n_positions": 262144,
                                  "vocab_size": 65536, "vocab_rows": 65536, "dtype": "bfloat16"}


def test_the_whole_model_s_arithmetic_reckoned_again():
    """The parameters from the file's own sizes: what ``parameters_why``
    and the issue's arithmetic say; the lanes' state and the pages."""
    c = spec.load_config(NAME)
    assert parameters(c) == c["parameters"] == 3_029_337_472
    for number in ("41,241,792", "62,914,560", "104,161,472", "76,682,240", "167,772,160", "3,029,337,472"):
        assert number in c["parameters_why"], number
    # whole on a 16 GB chip: 38% by the weights alone, over the floor of a quarter
    assert 0.25 * 16e9 < 2 * c["parameters"] < 0.4 * 16e9
    from benchmark.runners.serve_jamba2 import lane_state_bytes

    assert lane_state_bytes(c, "bfloat16") == 26 * (5120 * 16 * 4 + 3 * 5120 * 2) == 9_318_400
    cell = spec.load_cell(CELL)
    lanes, pool = cell["engine"]["max_batch_size"], cell["engine"]["pool_tokens"]
    assert lanes * 9_318_400 == 2_385_510_400
    # 1,024 B a position over the two attention layers
    assert 2 * 2 * 128 * 2 == 1024 and 0.5e9 < pool * 1024 < 0.82e9
    assert 2 * c["parameters"] + lanes * 9_318_400 + (pool + 64) * 1024 < 0.6 * 16e9


def test_runner_fails_at_once_where_the_program_has_no_such_family(monkeypatch):
    """The parent of PR 50 has no ``ray_tpu.models.jamba``: the runner
    must raise before it deploys, not wait for a replica."""
    from benchmark.runners import serve_jamba2 as runner

    monkeypatch.setattr(runner, "FAMILY", "ray_tpu.models.no_such_family")
    monkeypatch.setattr(runner, "deploy", lambda job: pytest.fail("deployed"))
    with pytest.raises(RuntimeError, match="no ray_tpu.models.no_such_family"):
        runner.run({"config": {"name": NAME}})


def test_the_stated_cache_is_two_paged_layers_of_one_head_and_two_arrays_a_mamba_layer():
    from benchmark.runners.serve_jamba2 import stated_cache

    cell = spec.load_cell(CELL)
    config = spec.load_config(NAME)
    slots = cell["engine"]["pool_tokens"] + 64
    cache = stated_cache(config, cell, "bfloat16")
    assert list(cache)[:4] == ["k_pages", "v_pages", "conv_tail_0", "ssm_state_0"] and len(cache) == 2 + 2 * 26
    assert cache["k_pages"] == cache["v_pages"] == [[2, slots, 128], "bfloat16"]
    assert cache["conv_tail_25"] == [[256, 15360], "bfloat16"]
    assert cache["ssm_state_25"] == [[256, 16, 5120], "float32"]  # N on the sublanes, the channels along the lanes
    # a lane's state has neither the pool nor a sequence's length in it
    smaller = dict(cell, engine=dict(cell["engine"], pool_tokens=524288, max_model_len=4096))
    assert stated_cache(config, smaller, "bfloat16")["ssm_state_0"] == cache["ssm_state_0"]


def test_wrong_reference_swaps_the_mixers_of_the_layers_it_moves():
    from benchmark.runners.serve_jamba2 import ATTENTION_MIXER, MAMBA_MIXER, wrong_reference

    mamba = lambda i: {**{k: ("m", i) for k in MAMBA_MIXER}, "norm1": i, "w_down": i}  # noqa: E731
    attn = lambda i: {**{k: ("a", i) for k in ATTENTION_MIXER}, "norm1": i, "w_down": i}  # noqa: E731
    params = {"embed": 0, "layers": [mamba(0), mamba(1), attn(2), mamba(3)]}
    told, tree, flags = wrong_reference({"attn_layer_offset": 2}, params, "attention_one_layer_early")
    assert told == {"attn_layer_offset": 1} and flags == () and tree["embed"] == 0
    assert tree["layers"][1] == {"wqkv": ("a", 2), "wo": ("a", 2), "norm1": 1, "w_down": 1}
    assert tree["layers"][2]["in_proj"] == ("m", 1) and tree["layers"][2]["norm1"] == 2 and "wqkv" not in tree["layers"][2]
    assert tree["layers"][0] == params["layers"][0] and tree["layers"][3] == params["layers"][3]
    assert wrong_reference({"x": 1}, params, "inner_norms_off") == ({"x": 1}, params, ("no_dt_norm", "no_b_norm", "no_c_norm"))
    assert wrong_reference({"x": 1}, params, "no_d") == ({"x": 1}, params, ("no_d",))


def test_the_two_scan_kernels_the_grouped_query_kernel_and_a_chunk_s_work_by_hand():
    config = spec.load_config(NAME)
    peak = spec.load_peaks()["TPU v5 lite"]
    from benchmark.runners.serve_jamba2 import (
        CHUNK_KERNEL, GQA_KERNEL, STEP_KERNEL, chunk_roofline_pct, kernel_roofline_pct,
    )

    # a decode step: a state of 5120 x 16 float32 in and out, the token's rows once, 5 operations a value
    step = flops_jamba.ssm1_step_work(config, 256 * 26)
    assert step["bytes"] == 256 * 26 * (2 * 327_680 + (3 * 5120 + 2 * 16) * 4)
    assert step["flops"] == 5 * 256 * 26 * 81_920
    least = flops.least_seconds(step, peak)
    assert least["bound"] == "memory" and 0.22e-3 < least["seconds"] / 26 < 0.23e-3  # 4.77 GB a step at 819 GB/s
    # the states a step moves: the issue's 4.36 GB
    assert 256 * 26 * 2 * 327_680 == 4_362_076_160
    # a 2,048-token chunk: 61 KB of rows a position, the state once; 168 M state values a layer
    chunk = flops_jamba.ssm1_chunk_work(config, 2048, 1)
    assert chunk["bytes"] == 2048 * (3 * 5120 + 32) * 4 + 2 * 327_680 and chunk["flops"] == 5 * 2048 * 81_920
    assert 2048 * 81_920 == 167_772_160
    assert flops.least_seconds(chunk, peak)["bound"] == "memory"  # by these peaks; the vector unit's is not among them
    # 300 decode programs of 26 + 2 calls; 100 in the trace
    before = {"ssm_lane_steps": 0, "kv_positions_attended": 0, "steps": 0, "prefill_chunks": 0, "ssm_chunk_tokens": 0}
    after = {"ssm_lane_steps": 300 * 256 * 26, "kv_positions_attended": 300 * 256 * 2 * 1500, "steps": 300,
             "max_batch_size": 256, "prefill_chunks": 60, "ssm_chunk_tokens": 60 * 26 * 400}
    trace = {"devices": 1, "window_s": 5.0,
             "op_seconds": {"mamba1_decode_step tpu_custom_call": 0.78, "mamba1_chunk_scan tpu_custom_call": 0.13,
                            "gqa_paged_decode_attention tpu_custom_call": 0.1, "fusion": 2.0},
             "op_counts": {"mamba1_decode_step tpu_custom_call": 2600, "mamba1_chunk_scan tpu_custom_call": 520,
                           "gqa_paged_decode_attention tpu_custom_call": 200}}
    share = kernel_roofline_pct(STEP_KERNEL, "ssm_lane_steps", 26,
                                lambda done, _: flops_jamba.ssm1_step_work(config, done), trace, before, after, peak)
    assert share == pytest.approx(100 * (least["seconds"] / 26) / 0.3e-3)
    # a chunk call: 400 real tokens on average, 0.25 ms in the trace
    a_chunk = flops.least_seconds(flops_jamba.ssm1_chunk_work(config, 400, 1), peak)["seconds"]
    assert chunk_roofline_pct(config, 26, trace, before, after, peak) == pytest.approx(100 * a_chunk / 0.25e-3)
    assert chunk_roofline_pct(config, 26, {"devices": 0}, before, after, peak) is None
    assert chunk_roofline_pct(config, 26, trace, before, dict(after, prefill_chunks=0), peak) is None
    # the grouped-query kernel at ONE K/V head: 512 B a position a layer, 20 x 2 x 2 x 128 operations
    one_head = {"num_attention_heads": 20, "num_key_value_heads": 1, "head_dim": 128}
    att = flops_ssm.gqa_decode_work(one_head, 256 * 1500, 256)
    assert att["bytes"] == 256 * 1500 * 512 + 256 * (2 * 20 + 2) * 128 * 4
    assert att["flops"] == 256 * 1500 * 20 * 2 * 2 * 128 and att["flops"] / (256 * 1500 * 512) == 20
    gqa = kernel_roofline_pct(GQA_KERNEL, "kv_positions_attended", 2,
                              lambda done, calls: flops_ssm.gqa_decode_work(one_head, done, calls),
                              trace, before, after, peak)
    a_step = flops.least_seconds(flops_ssm.gqa_decode_work(one_head, 256 * 2 * 1500, 256 * 2), peak)["seconds"]
    assert gqa == pytest.approx(100 * (a_step / 2) / 0.5e-3)
    # a chunk's token: 26 Mamba layers, 2 attention layers, 28 SwiGLUs, all x 2: 5.7 GFLOP
    mamba = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    assert flops_jamba.chunk_token_flops(config) == 2 * (26 * mamba + 2 * (2 * 2560 * 2560 + 2 * 2560 * 128)
                                                         + 28 * 3 * 2560 * 8192)
    assert 5.6e9 < flops_jamba.chunk_token_flops(config) < 5.8e9  # twice the 2.86 B parameters a token meets
    share = flops_jamba.prefill_mfu_pct(config, 100_000, 6.0, peak)
    assert share == pytest.approx(100 * 100_000 * flops_jamba.chunk_token_flops(config) / (6.0 * 197e12))
    assert flops_jamba.prefill_mfu_pct(config, 0, 6.0, peak) is None
    assert flops_jamba.prefill_mfu_pct(config, 100_000, 6.0, None) is None
    # the share of a step's bytes that is state: the expression of ssm_share_of_step_bytes_pct by hand
    from benchmark import readers

    stats = {"before": {"state_bytes": 0, "steps": 0, "kv_positions_gathered": 0},
             "after": {"state_bytes": 100 * 4_771_020_800, "steps": 100, "kv_positions_gathered": 100 * 256 * 2 * 1536},
             "window_s": 2.0}
    got = readers.stats_delta(spec.load_layer_metric("ssm_share_of_step_bytes_pct")["args"],
                              {"stats": stats, "values": {"weight_bytes": 6_063_466_240}})
    assert got == pytest.approx(100 * 4_771_020_800 / (4_771_020_800 + 6_063_466_240 + 256 * 2 * 1536 * 512))
    assert 40 < got < 45

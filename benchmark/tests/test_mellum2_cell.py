"""The Mellum 2 cell on the CPU, beside ``test_benchmark.py``:

    python -m pytest benchmark/tests -q

The cell end to end at the ``mellum2_tiny`` preset behind ``run_cell``'s
rehearsal argument (traced and untraced), its metric names against the
entries of ``BENCHMARK.json``, the configuration file against the
catalog's published keys and its own arithmetic, the runner's refusal of
a program without the family, the stated cache, and the arithmetic of
the decode kernel's, the experts' and a chunk's least work.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import flops, flops_mellum, spec  # noqa: E402
from benchmark.tests import rehearsal  # noqa: E402

CELL = "mellum2-12b-a2.5b.serve.mixed-backlog"
NAME = "mellum2-12b-a2.5b"
KINDS = ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"]
TINY = {"n_layer": 8, "n_embd": 64, "n_head": 8, "n_positions": 512, "vocab_size": 256,
        "vocab_rows": 256, "dtype": "float32"}
TINY_CONFIG = {
    "preset": "mellum2_tiny", "num_hidden_layers": 8, "layer_types": KINDS * 2,
    "hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 16,
    "moe_intermediate_size": 32, "num_experts": 8, "num_experts_per_tok": 2,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                           "original_max_position_embeddings": 32, "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "published": {"num_hidden_layers": 8, "layer_types": KINDS * 2},
}
TINY_CELL = {
    "engine": {"max_batch_size": 4, "block_size": 8, "pool_tokens": 2048, "max_queue": 256,
               "max_model_len": 512, "prefill_chunk": 8},
    "traffic": {"prompt_len": {"median": 40, "sigma": 1.1, "lo": 4, "hi": 200},
                "max_tokens": {"median": 12, "sigma": 0.7, "lo": 4, "hi": 40},
                "max_total_tokens": 512, "trace_seconds": 0.5, "clients": 8, "pool_requests": 32,
                "lead_in": {"after_full_s": 0.5, "at_most_s": 30.0}, "send_gap_s": 0.01},
    # inside the window; past it; the ring wrapped five times and decode continuing over it
    "checks": {"prompt_lens": [6, 20, 75], "max_tokens": 20, "logit_margin": 1e-5, "logit_distance": 3e-6,
               "expert_agreement_min": 0.99, "positions_agreeing_min": 0.9, "ring_key_distance": 1e-5},
}
# what a traced run prints without a chip: the counters' metrics and the
# host clock's (those that read the device trace or the chip's peak find
# nothing on the CPU and are left out)
ON_THE_CPU = {"engine_step_ms.backlog", "lanes_busy_pct.backlog", "host_ms_per_step",
              "prefill_share_pct", "prefill_chunk_ms", "deploy_ready_s.serve",
              "attn_positions_kept_pct.mellum",
              # the five the full list of PR 45 had no room for (PR 49)
              "prefill_pad_ratio", "decode_overlap_pct", "kv_gather_useful_pct",
              "moe_experts_hit_pct", "moe_imbalance", "kv_blocks_whole_pct"}
FROM_THE_DEVICE = {"device_idle_pct.backlog", "moe_gmm_busy_pct", "moe_gmm_roofline_pct",
                   "gqa_paged_decode_attention_busy_pct", "gqa_paged_decode_attention_roofline",
                   "prefill_mfu_pct"}


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
        # 6 window layers read at most 15 of a lane's positions, 2 full layers all of them
        assert 25 <= out["metrics"]["attn_positions_kept_pct.mellum"]["value"] < 100
    else:
        assert set(out["metrics"]) == {"serve_out_tokens_per_s", "setup_s"}
        assert out["metrics"]["serve_out_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("wrong", ["window_1023", "no_window", "rotations_swapped"])
def test_a_reference_told_another_model_is_not_correct(monkeypatch, wrong):
    """At the tiny preset in float32 the limits are tight enough that
    each wrong reading fails by itself (``window_1023``: one key short)."""
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
    assert tr["prompt_len"] == {"median": 4096, "sigma": 1.1, "lo": 256, "hi": 32768}
    assert tr["max_tokens"] == {"median": 512, "sigma": 0.7, "lo": 128, "hi": 2048}
    assert tr["max_total_tokens"] == eng["max_model_len"] == 34816 and eng["prefill_chunk"] == 2048
    assert (tr["send_gap_s"], tr["trace_seconds"], tr["mode"]) == (0.05, 5, "closed")
    assert 262144 <= eng["pool_tokens"] <= 393216 and (393216 - eng["pool_tokens"]) % 65536 == 0
    # inside the window; past it inside one chunk; across a chunk boundary; the ring wrapped four times
    lens = cell["checks"]["prompt_lens"]
    assert lens == [64, 1500, 2304, 5000] and cell["checks"]["max_tokens"] == 64
    assert sorted(-(-n // eng["prefill_chunk"]) for n in lens) == [1, 1, 2, 3]
    assert [n // 1023 for n in lens] == [0, 1, 2, 4]


def test_the_configuration_is_the_catalog_s_but_for_what_it_says_is_reduced():
    config = spec.load_config(NAME)
    bench = spec.load_benchmark()
    assert spec.entry(bench, "configs", NAME)["reduced"] == config["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Mellum2-12B-A2.5B-Instruct")
        assert config["source"] == row["source_url"] == spec.entry(bench, "configs", NAME)["source"]
        for key, value in row["config"].items():
            if key in config["reduced"]:
                assert config["published"][key] == value and len(str(config[key])) <= len(str(value))
            else:
                assert config[key] == value, key
    # every width as published; nothing within a layer is cut
    assert (config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"],
            config["moe_intermediate_size"], config["num_experts"], config["num_experts_per_tok"],
            config["vocab_size"], config["sliding_window"], config["intermediate_size"]) == (
        2304, 32, 4, 128, 896, 64, 8, 98304, 1024, 7168)
    assert config["norm_topk_prob"] is True and config["tie_word_embeddings"] is False
    assert config["rope_parameters"]["full_attention"]["attention_factor"] == 1.2772588722239782
    kinds = config["layer_types"]
    assert config["num_hidden_layers"] == len(kinds) == len(config["mlp_layer_types"]) == 12
    assert kinds == config["published"]["layer_types"][:12] == KINDS * 3  # three whole periods
    assert set(config["mlp_layer_types"]) == {"sparse"}
    assert "12, 8 and 8" in config["deployment"] and "FIRST stage" in config["deployment"]
    for item in ("qk_norm", "param_dtype", "router_scoring", "window_edge", "mtp_head", "rotation", "dense_width",
                 "max_model_len", "weights", "engine_sizes_why", "vocab_rows"):
        assert item in config["assumed"], item
    assert spec.sizes(config) == {"n_layer": 12, "n_embd": 2304, "n_head": 32, "n_positions": 131072,
                                  "vocab_size": 98304, "vocab_rows": 98304, "dtype": "bfloat16"}


def test_the_cut_s_arithmetic_reckoned_again():
    """The parameters held, from the file's own sizes: what
    ``reduced_why`` and the issue's arithmetic say."""
    c = spec.load_config(NAME)
    d, f, hd = c["hidden_size"], c["moe_intermediate_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    attention = 2 * d * q + 2 * d * kv + 2 * hd
    experts = d * c["num_experts"] + c["num_experts"] * 3 * d * f
    assert (attention, experts, attention + experts + 2 * d) == (21_233_920, 396_509_184, 417_747_712)
    ends = 2 * c["vocab_size"] * d + d
    held = 12 * 417_747_712 + ends
    assert (ends, held, 28 * 417_747_712 + ends) == (452_987_136, 5_465_959_680, 12_149_923_072)
    for number in ("417,747,712", "452,984,832", "12,149,923,072", "5,465,959,680", "603,979,776", "2,416,312,320"):
        assert number in c["reduced_why"]["num_hidden_layers"], number
    # a token meets 8 of 64 experts: the name's "A2.5B"
    active = 28 * (attention + d * c["num_experts"] + c["num_experts_per_tok"] * 3 * d * f + 2 * d) + ends
    assert 2.4e9 < active < 2.5e9
    # more than a quarter of a 16 GB chip by the weights alone
    assert 2 * held > 0.25 * spec.load_peaks()["TPU v5 lite"]["hbm_bytes"]


def test_runner_fails_at_once_where_the_program_has_no_such_family(monkeypatch):
    """The parent of PR 45 has no ``ray_tpu.models.mellum``: the runner
    must raise before it deploys, not wait for a replica."""
    from benchmark.runners import serve_mellum2 as runner

    monkeypatch.setattr(runner, "FAMILY", "ray_tpu.models.no_such_family")
    monkeypatch.setattr(runner, "deploy", lambda job: pytest.fail("deployed"))
    with pytest.raises(RuntimeError, match="no ray_tpu.models.no_such_family"):
        runner.run({"config": {"name": NAME}})


def test_the_stated_cache_is_three_paged_layers_and_two_rings_a_lane():
    from benchmark.runners.serve_mellum2 import stated_cache

    cell = spec.load_cell(CELL)
    config = spec.load_config(NAME)
    slots = cell["engine"]["pool_tokens"] + 64
    cache = stated_cache(config, cell, "bfloat16")
    assert list(cache) == ["k_pages", "v_pages", "win_k", "win_v"]
    assert cache["k_pages"] == cache["v_pages"] == [[3, slots, 512], "bfloat16"]
    assert cache["win_k"] == cache["win_v"] == [[32, 9, 1024, 512], "bfloat16"]
    # a ring's shape has neither the pool nor a sequence's length in it
    smaller = dict(cell, engine=dict(cell["engine"], pool_tokens=262144, max_model_len=8192))
    assert stated_cache(config, smaller, "bfloat16")["win_k"] == cache["win_k"]
    # a lane: 18.9 MB; the lanes: 0.60 GB; K and V pages: 2.42 GB at the pool the issue names
    assert 2 * 9 * 1024 * 512 * 2 == 18_874_368 and 32 * 18_874_368 == 603_979_776
    assert 2 * 3 * (393216 + 64) * 512 * 2 == 2_416_312_320
    # served as twelve paged layers a position would be 24,576 B; with the window honoured 6,144 B
    assert (12 * 2048, 3 * 2048) == (24_576, 6_144)


def test_decode_kernel_experts_and_chunk_work_and_their_shares_by_hand():
    config = spec.load_config(NAME)
    peak = spec.load_peaks()["TPU v5 lite"]
    from benchmark.runners.serve_mellum2 import GQA_KERNEL, gmm_roofline_pct, kernel_roofline_pct

    # a window layer reads min(length, 1023) cached positions, a full layer all of them
    assert [flops_mellum.window_positions(config, n) for n in (0, 500, 1023, 1024, 30000)] == [0, 500, 1023, 1023, 1023]
    step = flops_mellum.step_positions(config, [300, 8192])
    assert step == {"window": 9 * (300 + 1023), "full": 3 * 8492, "unwindowed": 12 * 8492}
    # at a mean context of 8k the issue's 34% (9 x 1k + 3 x 8k of 12 x 8k)
    mean = flops_mellum.step_positions(config, [8192])
    assert 100 * (mean["window"] + mean["full"]) / mean["unwindowed"] == pytest.approx(34.4, abs=0.1)
    # the grouped-query kernel at 4 K/V heads: 2,048 B and 32 x 2 x 2 x 128 operations an attended position
    att = flops_mellum.gqa_decode_work(config, 32 * 7000, 32)
    assert att["flops"] == 32 * 7000 * 32 * 2 * 2 * 128
    assert att["bytes"] == 32 * 7000 * 2048 + 32 * (2 * 32 + 2 * 4) * 128 * 4
    assert att["flops"] / (32 * 7000 * 2048) == 8  # eight queries a row read: far under the ridge of 240
    # 400 decode programs of 12 calls each; 100 of them in the trace, 1,200 calls taking 0.6 s
    before = {"kv_positions_attended": 0, "steps": 0}
    after = {"kv_positions_attended": 400 * 32 * (9 * 1023 + 3 * 7000), "steps": 400, "max_batch_size": 32}
    trace = {"devices": 1, "window_s": 5.0,
             "op_seconds": {"gqa_paged_decode_attention tpu_custom_call": 0.6, "moe_gmm tpu_custom_call": 1.5,
                            "fusion": 2.0},
             "op_counts": {"gqa_paged_decode_attention tpu_custom_call": 1200}}

    def gqa(done, lane_calls):
        return flops_mellum.gqa_decode_work(config, done, lane_calls)

    a_step = flops_mellum.gqa_decode_work(config, 32 * (9 * 1023 + 3 * 7000), 32 * 12)
    least = flops.least_seconds(a_step, peak)
    assert least["bound"] == "memory"
    assert kernel_roofline_pct(GQA_KERNEL, "kv_positions_attended", 12, gqa, trace, before, after, peak) == (
        pytest.approx(100 * (least["seconds"] / 12) / 0.5e-3))
    no_trace = {"devices": 0}
    assert kernel_roofline_pct(GQA_KERNEL, "kv_positions_attended", 12, gqa, no_trace, before, after, peak) is None
    # the experts: a pair is THREE 2304 x 896 matmuls, an expert hit 12.4 MB of them
    moe = flops_mellum.experts_work(config, 256, 60)
    assert moe["flops"] == 2 * 256 * 3 * 2304 * 896
    assert moe["bytes"] == 60 * 3 * 2304 * 896 * 2 + 256 * (2 * 2304 + 3 * 896) * 2
    assert 3 * 2304 * 896 * 2 == 12_386_304
    # 2 s of trace in which 60 programs x 12 layers computed 256 pairs over 60 experts each
    start = {"t": 10.0, "moe_pairs": 0, "moe_experts_hit": 0}
    end = {"t": 12.0, "moe_pairs": 60 * 12 * 256, "moe_experts_hit": 60 * 12 * 60}
    least_s = flops.least_seconds(flops_mellum.experts_work(config, 60 * 12 * 256, 60 * 12 * 60), peak)["seconds"]
    assert gmm_roofline_pct(config, trace, start, end, peak) == pytest.approx(100 * (least_s / 2.0) / (1.5 / 5.0))
    assert gmm_roofline_pct(config, trace, start, {"t": 12.0}, peak) is None
    # a chunk's token: 12 layers x (q, o 9.44M each; k, v 1.18M each; the router; 8 experts of 6.19M), all x 2
    assert flops_mellum.chunk_token_flops(config) == 2 * 12 * (2 * 9_437_184 + 2 * 1_179_648 + 147_456 + 8 * 6_193_152)
    assert 1.7e9 < flops_mellum.chunk_token_flops(config) < 1.8e9
    # 40 prompts of 7,000 tokens in 12 s of chunk programs: 21% of the peak
    share = flops_mellum.prefill_mfu_pct(config, 280_000, 12.0, peak)
    assert share == pytest.approx(100 * 280_000 * flops_mellum.chunk_token_flops(config) / (12.0 * 197e12))
    assert 20 < share < 22 and flops_mellum.prefill_mfu_pct(config, 0, 12.0, peak) is None
    assert flops_mellum.prefill_mfu_pct(config, 280_000, 12.0, None) is None

"""The ZAYA1 cell on the CPU, beside ``test_benchmark.py``:

    python -m pytest benchmark/tests -q

The cell end to end at the ``zaya1_tiny`` preset behind ``run_cell``'s
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

from benchmark import flops, flops_zaya, spec  # noqa: E402
from benchmark.tests import rehearsal  # noqa: E402

CELL = "zaya1-8b.serve.longthink-backlog"
NAME = "zaya1-8b"
TINY = {"n_layer": 4, "n_embd": 128, "n_head": 8, "n_positions": 512, "vocab_size": 256,
        "vocab_rows": 256, "dtype": "float32"}
TINY_CONFIG = {
    "preset": "zaya1_tiny", "num_hidden_layers": 4, "layer_types": ["hybrid"] * 4, "hidden_size": 128,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16, "num_experts": 4,
    "moe_intermediate_size": 64, "router_hidden_size": 16,
}
TINY_CELL = {
    "engine": {"max_batch_size": 4, "block_size": 8, "pool_tokens": 2048, "max_queue": 256,
               "max_model_len": 512, "prefill_chunk": 8},
    "traffic": {"prompt_len": {"median": 40, "sigma": 0.8, "lo": 4, "hi": 200},
                "max_tokens": {"median": 24, "sigma": 0.6, "lo": 8, "hi": 80},
                "max_total_tokens": 512, "trace_seconds": 0.5, "clients": 8, "pool_requests": 32,
                "lead_in": {"after_full_s": 0.5, "at_most_s": 30.0}, "send_gap_s": 0.01},
    # inside a bucket; across one chunk boundary; across many
    "checks": {"prompt_lens": [6, 13, 75], "max_tokens": 20, "logit_margin": 1e-5, "logit_distance": 5e-6,
               "expert_agreement_min": 0.99, "positions_agreeing_min": 0.9},
}
# what a traced run prints without a chip: the counters' metrics and the
# host clock's (those that read the device trace or the chip's peak find
# nothing on the CPU and are left out)
ON_THE_CPU = {"engine_step_ms.backlog", "lanes_busy_pct.backlog", "host_ms_per_step", "prefill_share_pct",
              "prefill_chunk_ms", "deploy_ready_s.serve", "prefill_pad_ratio", "decode_overlap_pct",
              "kv_gather_useful_pct", "moe_experts_hit_pct", "moe_imbalance", "moe_held_share_pct",
              "ssm_state_mb_per_step", "kv_blocks_whole_pct", "moe_skip_share_pct"}
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
        # every expert is held: what is not held is skipped, about one pair in five at four experts
        assert 50 < out["metrics"]["moe_held_share_pct"]["value"] < 100
        # a decode step reads and writes every lane's tails: 4 lanes x 4 layers x 336 float32 values, twice
        assert 0 < out["metrics"]["ssm_state_mb_per_step"]["value"] <= 2 * 4 * 4 * 336 * 4 / 1e6 * 1.5
    else:
        assert set(out["metrics"]) == {"serve_out_tokens_per_s", "setup_s"}
        assert out["metrics"]["serve_out_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("wrong", ["no_value_shift", "gamma_0", "skip_is_an_expert"])
def test_a_reference_told_another_model_is_not_correct(monkeypatch, wrong):
    """At the tiny preset in float32 the limits are tight enough that
    each wrong model fails by itself."""
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
    assert cell["runner"] == "serve_zaya1"
    # the traffic and the engine the issue names, letter for letter
    tr, eng = cell["traffic"], cell["engine"]
    assert (tr["clients"], tr["pool_requests"], eng["max_batch_size"], eng["block_size"]) == (96, 512, 48, 64)
    assert tr["prompt_len"] == {"median": 1024, "sigma": 0.8, "lo": 128, "hi": 8192}
    assert tr["max_tokens"] == {"median": 2048, "sigma": 0.6, "lo": 512, "hi": 8192}
    assert tr["max_total_tokens"] == eng["max_model_len"] == 16384 and eng["prefill_chunk"] == 2048
    assert (tr["send_gap_s"], tr["trace_seconds"], tr["mode"], eng["max_queue"]) == (0.05, 5, "closed", 2048)
    assert tr["lead_in"]["at_most_s"] == 120.0
    # the issue's fallback order: the pool down in steps of 32,768 to 163,840, then the lanes to 40
    assert 163840 <= eng["pool_tokens"] <= 229376 and (229376 - eng["pool_tokens"]) % 32768 == 0
    # inside a bucket, a mid bucket, across one and across two chunk boundaries
    lens = cell["checks"]["prompt_lens"]
    assert lens == [64, 700, 2304, 5000] and cell["checks"]["max_tokens"] == 64
    assert [-(-n // eng["prefill_chunk"]) for n in lens] == [1, 1, 2, 3]


def test_the_configuration_is_the_catalog_s_but_for_what_it_says_is_reduced():
    config = spec.load_config(NAME)
    bench = spec.load_benchmark()
    assert spec.entry(bench, "configs", NAME)["reduced"] == config["reduced"] == ["num_hidden_layers", "layer_types"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "ZAYA1-8B")
        assert config["source"] == row["source_url"] == spec.entry(bench, "configs", NAME)["source"]
        for key, value in row["config"].items():
            if key in config["reduced"]:
                assert config["published"][key] == value and len(str(config[key])) <= len(str(value))
            else:
                assert config[key] == value, key
    # every width as published; nothing within a layer is cut
    assert (config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"],
            config["moe_intermediate_size"], config["num_experts"], config["num_experts_per_tok"],
            config["router_hidden_size"], config["vocab_size"], config["cca_time0"], config["cca_time1"]) == (
        2048, 8, 2, 128, 2048, 16, 1, 256, 262272, 2, 2)
    assert config["tie_word_embeddings"] is True and config["partial_rotary_factor"] == 0.5
    assert config["rope_parameters"]["hybrid"]["rope_theta"] == 5000000 and config["sliding_window"] is None
    assert config["num_hidden_layers"] == len(config["layer_types"]) == 20 and set(config["layer_types"]) == {"hybrid"}
    assert config["published"] == {"num_hidden_layers": 40, "layer_types": ["hybrid"] * 40}
    assert "v5e-2" in config["deployment"] and "FIRST stage" in config["deployment"]
    assert "NOTHING shared" in config["deployment"]
    # every item of the issue's "assumed", each with its reason
    for item in ("cca_steps", "value_shift", "qk_norm", "rotation", "router", "skip_output", "expert_weight",
                 "residual_scales", "param_dtype"):
        assert item in config["assumed"] and item + "_why" in config["assumed"], item
    for item in ("max_model_len", "weights", "engine_sizes_why", "vocab_rows"):
        assert item in config["assumed"], item
    assert spec.sizes(config) == {"n_layer": 20, "n_embd": 2048, "n_head": 8, "n_positions": 131072,
                                  "vocab_size": 262272, "vocab_rows": 262272, "dtype": "bfloat16"}


def test_the_cut_s_arithmetic_reckoned_again():
    """The parameters held, from the file's own sizes: what
    ``parameters_why`` and the issue's arithmetic say."""
    c = spec.load_config(NAME)
    d, f, hd, R = c["hidden_size"], c["moe_intermediate_size"], c["head_dim"], c["router_hidden_size"]
    heads, kv, E = c["num_attention_heads"], c["num_key_value_heads"], c["num_experts"]
    S = (heads + kv) * hd
    attention = d * S + d * kv * hd + heads * hd * d + (S * c["cca_time0"] + S) + (
        (heads + kv) * c["cca_time1"] * hd * hd + S) + kv
    router = d * R + 2 * (R * R + R) + R * (E + 1) + R + 1 + (E + 1)
    experts = E * 3 * d * f
    layer = attention + router + experts + 6 * d
    assert (attention, router, experts, layer) == (5_575_682, 660_498, 201_326_592, 207_575_060)
    ends = c["vocab_size"] * d + d
    assert (ends, 20 * layer + ends, 40 * layer + ends) == (537_135_104, 4_688_636_304, 8_840_137_504)
    assert c["parameters"] == 4_688_636_304
    for number in ("5,575,682", "660,498", "201,326,592", "207,575,060", "537,133,056", "4,688,636,304",
                   "8,840,137,504", "4,698,931,200", "107,520"):
        assert number in c["parameters_why"], number
    # a token meets one of 16 experts or none: the card's "A0.76B", the embedding's rows not counted
    active = 40 * (attention + router + 3 * d * f + 6 * d)
    assert 0.75e9 < active < 0.76e9
    # a position in pages, a lane in tails, the pool, and more than a quarter of a 16 GB chip by the weights alone
    assert 20 * 2 * kv * hd * 2 == 20_480 and 20 * (2 * S + kv // 2 * hd) * 2 == 107_520
    assert 20_480 * (229_376 + 64) == 4_698_931_200
    assert 2 * c["parameters"] > 0.25 * spec.load_peaks()["TPU v5 lite"]["hbm_bytes"]


def test_runner_fails_at_once_where_the_program_has_no_such_family(monkeypatch):
    """The parent of PR 54 has no ``ray_tpu.models.zaya``: the runner
    must raise before it deploys, not wait for a replica."""
    from benchmark.runners import serve_zaya1 as runner

    monkeypatch.setattr(runner, "FAMILY", "ray_tpu.models.no_such_family")
    monkeypatch.setattr(runner, "deploy", lambda job: pytest.fail("deployed"))
    with pytest.raises(RuntimeError, match="no ray_tpu.models.no_such_family"):
        runner.run({"config": {"name": NAME}})


def test_the_stated_cache_is_twenty_paged_layers_and_a_tail_a_lane_a_layer():
    from benchmark.runners.serve_zaya1 import stated_cache

    cell = spec.load_cell(CELL)
    config = spec.load_config(NAME)
    eng = cell["engine"]
    cache = stated_cache(config, cell, "bfloat16")
    assert list(cache) == ["k_pages", "v_pages", *(f"cca_tail_{i}" for i in range(20))]
    assert cache["k_pages"] == cache["v_pages"] == [[20, eng["pool_tokens"] + 64, 256], "bfloat16"]
    assert all(cache[f"cca_tail_{i}"] == [[eng["max_batch_size"], 2688], "bfloat16"] for i in range(20))
    # a tail's shape has neither the pool nor a sequence's length in it
    smaller = dict(cell, engine=dict(eng, pool_tokens=163840, max_model_len=8192))
    assert stated_cache(config, smaller, "bfloat16")["cca_tail_7"] == cache["cca_tail_7"]
    # with 8 full K/V heads a position would be 81,920 B and the same pool would hold 15 lanes' mean reservation
    assert 20 * 2 * 8 * 128 * 2 == 81_920 and 4_698_931_200 // 81_920 // 3_750 == 15


def test_decode_kernel_experts_and_chunk_work_and_their_shares_by_hand():
    config = spec.load_config(NAME)
    peak = spec.load_peaks()["TPU v5 lite"]
    from benchmark.runners.serve_zaya1 import GQA_KERNEL, gmm_roofline_pct, kernel_roofline_pct

    # the grouped-query kernel at 2 K/V heads: 1,024 B and 8 x 2 x 2 x 128 operations an attended position
    att = flops_zaya.gqa_decode_work(config, 48 * 2500, 48)
    assert att["flops"] == 48 * 2500 * 8 * 2 * 2 * 128
    assert att["bytes"] == 48 * 2500 * 1024 + 48 * (2 * 8 + 2 * 2) * 128 * 4
    assert att["flops"] / (48 * 2500 * 1024) == 4  # four queries a row read: far under the ridge of 240
    # 400 decode programs of 20 calls each; 100 of them in the trace, 2,000 calls taking 0.4 s
    before = {"kv_positions_attended": 0, "steps": 0}
    after = {"kv_positions_attended": 400 * 20 * 48 * 2500, "steps": 400, "max_batch_size": 48}
    trace = {"devices": 1, "window_s": 5.0,
             "op_seconds": {"gqa_paged_decode_attention tpu_custom_call": 0.4, "moe_gmm tpu_custom_call": 2.5,
                            "fusion": 1.0},
             "op_counts": {"gqa_paged_decode_attention tpu_custom_call": 2000}}

    def gqa(done, lane_calls):
        return flops_zaya.gqa_decode_work(config, done, lane_calls)

    a_step = flops_zaya.gqa_decode_work(config, 20 * 48 * 2500, 48 * 20)
    least = flops.least_seconds(a_step, peak)
    assert least["bound"] == "memory"
    assert kernel_roofline_pct(GQA_KERNEL, "kv_positions_attended", 20, gqa, trace, before, after, peak) == (
        pytest.approx(100 * (least["seconds"] / 20) / 0.2e-3))
    no_trace = {"devices": 0}
    assert kernel_roofline_pct(GQA_KERNEL, "kv_positions_attended", 20, gqa, no_trace, before, after, peak) is None
    # the experts: a pair is THREE 2048 x 2048 matmuls, an expert hit 25.2 MB of them
    moe = flops_zaya.experts_work(config, 45, 15)
    assert moe["flops"] == 2 * 45 * 3 * 2048 * 2048
    assert moe["bytes"] == 15 * 3 * 2048 * 2048 * 2 + 45 * (2 * 2048 + 3 * 2048) * 2
    assert 3 * 2048 * 2048 * 2 == 25_165_824
    # a decode step at 48 lanes: 45 pairs over 15 experts a layer is a weight stream, not arithmetic
    assert flops.least_seconds(flops_zaya.experts_work(config, 20 * 45, 20 * 15), peak)["bound"] == "memory"
    # 2 s of trace in which 100 programs x 20 layers computed 45 pairs over 15 experts each
    start = {"t": 10.0, "moe_pairs": 0, "moe_experts_hit": 0}
    end = {"t": 12.0, "moe_pairs": 100 * 20 * 45, "moe_experts_hit": 100 * 20 * 15}
    least_s = flops.least_seconds(flops_zaya.experts_work(config, 100 * 20 * 45, 100 * 20 * 15), peak)["seconds"]
    assert gmm_roofline_pct(config, trace, start, end, peak) == pytest.approx(100 * (least_s / 2.0) / (2.5 / 5.0))
    assert gmm_roofline_pct(config, trace, start, {"t": 12.0}, peak) is None
    # a chunk's token: 20 layers x (latents 3.15M; the grouped convolution 0.33M; W_o 2.10M; the router
    # 0.66M; 16 of 17 tokens through an expert of 12.58M), all x 2
    per_layer = 2048 * 1536 + 10 * 2 * 128 * 128 + 1024 * 2048 + (2048 * 256 + 2 * 256 * 256 + 256 * 17) + (
        16 / 17 * 3 * 2048 * 2048)
    assert flops_zaya.chunk_token_flops(config) == pytest.approx(2 * 20 * per_layer)
    assert 0.71e9 < flops_zaya.chunk_token_flops(config) < 0.73e9
    share = flops_zaya.prefill_mfu_pct(config, 60_000, 3.0, peak)
    assert share == pytest.approx(100 * 60_000 * flops_zaya.chunk_token_flops(config) / (3.0 * 197e12))
    assert flops_zaya.prefill_mfu_pct(config, 0, 3.0, peak) is None
    assert flops_zaya.prefill_mfu_pct(config, 60_000, 3.0, None) is None

"""Operations and bytes of what Mellum 2's programs do, under the
configuration file's OWN key names (``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``sliding_window``,
``num_experts``, ``moe_intermediate_size``): the grouped-query paged
decode kernel at 4 K/V heads of 8 queries
(``ray_tpu/ops/pallas_gqa_paged_attention.py``:
``gqa_paged_decode_attention``) over the full layers' pages AND over the
window layers' rings, the expert layer's grouped matmuls
(``ray_tpu/ops/moe.py``: ``moe_gmm``, THREE matrices an expert, all 64
held), and a prompt chunk's program by its real tokens.  From the
configuration file's sizes and the engine's own counters.  Like
``flops.py``: what the algorithm needs, nothing imported from the
program or JAX."""

from __future__ import annotations


def window_positions(config: dict, cached: int) -> int:
    """The cached positions a window layer's decode step must read for a
    lane that has `cached`: the fed token's window is itself and the
    ``sliding_window - 1`` before it, so never more than those."""
    return min(cached, config["sliding_window"] - 1)


def step_positions(config: dict, cached: list) -> dict:
    """What one decode step attends for lanes with `cached` positions
    each: {"window", "full", "unwindowed"}, summed over lanes and the
    layers of each kind (``attn_positions_window``,
    ``attn_positions_full``, ``attn_positions_unwindowed`` of
    ``LLMEngine.stats()``)."""
    kinds = config["layer_types"]
    n_w, n_f = kinds.count("sliding_attention"), kinds.count("full_attention")
    return {"window": n_w * sum(window_positions(config, n) for n in cached), "full": n_f * sum(cached),
            "unwindowed": (n_w + n_f) * sum(cached)}


def gqa_decode_work(config: dict, positions_attended: int, lane_calls: int, itemsize: int = 2) -> dict:
    """The least work of the grouped-query decode kernel's calls that
    attended `positions_attended` cached positions
    (``kv_positions_attended``: ``min(length, sliding_window - 1)`` a
    window layer and ``length`` a full layer, summed over lanes and
    layers) for `lane_calls` (lane, layer) pairs.

    An attended position is one row of K and one of V of
    ``num_key_value_heads x head_dim`` values (4 x 128: 2,048 B in bf16
    for both), read ONCE for the eight query heads of each group; each
    of the ``num_attention_heads`` heads multiplies its K/V head's
    ``head_dim`` values once for the score and once for the weighted sum
    (``32 x 2 x 2 x 128`` operations; the heads of zeros the kernel pads
    a group of 8 to a tile of 16 with are the program's cost, not the
    algorithm's, and so are the whole pages copied past a length).  A
    lane's queries, own key and value come in and its output goes out in
    float32 once."""
    heads, kv, dh = config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]
    return {
        "flops": 2.0 * 2 * positions_attended * heads * dh,
        "bytes": positions_attended * 2 * kv * dh * itemsize + lane_calls * (2 * heads + 2 * kv) * dh * 4,
    }


def experts_work(config: dict, pairs: int, experts_hit: int, itemsize: int = 2) -> dict:
    """The least work of the grouped matmuls that computed `pairs`
    token-expert pairs in programs whose layers hit `experts_hit`
    experts in all (``moe_pairs`` and ``moe_experts_hit``).

    A SwiGLU expert: a pair is one row through gate, up and down, THREE
    ``hidden_size x moe_intermediate_size`` matmuls (2304 x 896).  An
    expert's three matrices (12,386,304 B in bf16) are read once for
    each program and layer in which it received a row; a pair's rows are
    read and written once on each side of the two grouped matmuls (d in,
    2f out; f in, d out)."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    return {
        "flops": 2.0 * pairs * 3 * d * f,
        "bytes": experts_hit * 3 * d * f * itemsize + pairs * (2 * d + 3 * f) * itemsize,
    }


def chunk_token_flops(config: dict) -> float:
    """The operations ONE real token of a prompt chunk needs in the
    layers held: every weight matrix it meets, twice its size (a
    multiply and an add a weight).  A layer: q and o (d x heads x
    head_dim each), k and v (d x kv heads x head_dim each), the router
    (d x num_experts) and the token's ``num_experts_per_tok`` experts, 3
    d x moe_intermediate_size each.  The scores are left OUT (at most
    1,024 keys a window layer, the context's length a full layer, which
    no counter of a chunk gives: 16,384 operations a key a token, 3% of
    a token's work at 4k), so the share of the peak this gives is a
    floor.  The head is one position a chunk and is left out."""
    d, dh = config["hidden_size"], config["head_dim"]
    q, kv = config["num_attention_heads"] * dh, config["num_key_value_heads"] * dh
    layer = 2.0 * (2 * d * q + 2 * d * kv + d * config["num_experts"]
                   + config["num_experts_per_tok"] * 3 * d * config["moe_intermediate_size"])
    return config["num_hidden_layers"] * layer


def prefill_mfu_pct(config: dict, prompt_tokens: int, program_seconds: float, peak: dict):
    """The operations of chunk programs that took in `prompt_tokens` real
    tokens (``chunk_token_flops``) over the seconds those programs took
    (the engine's own clock around each: built, awaited, fetched) times
    the chip's bf16 peak.  A floor: the clock's seconds hold the device's
    and more.  None where no chunk ran."""
    if not peak or program_seconds <= 0 or prompt_tokens <= 0:
        return None
    return 100.0 * prompt_tokens * chunk_token_flops(config) / (program_seconds * peak["bf16_flops_per_s"])

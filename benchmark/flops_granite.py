"""Operations and bytes of what Granite 4.0-H's programs do, under the
configuration file's OWN key names (``mamba_n_heads``, ``mamba_d_head``,
``mamba_d_state``, ``mamba_n_groups``, ``num_local_experts``,
``intermediate_size``): the Mamba-2 decode kernel over the lanes' states
(``ray_tpu/ops/pallas_mamba2.py``: ``mamba2_decode_step``), the dense
grouped-query paged decode kernel at 8 K/V heads
(``ray_tpu/ops/pallas_gqa_paged_attention.py``:
``gqa_paged_decode_attention``), the expert part of a chip that holds a
SHARE of routed SwiGLU experts (``ray_tpu/ops/moe.py``: ``moe_gmm``,
THREE matrices an expert), and a prompt chunk's program by its real
tokens.  From the configuration file's sizes and the engine's own
counters.  Like ``flops.py``: what the algorithm needs, nothing imported
from the program or JAX."""

from __future__ import annotations

from benchmark import flops_ssm


def _state_values(config: dict) -> int:
    return config["mamba_n_heads"] * config["mamba_d_head"] * config["mamba_d_state"]


def _as_flops_ssm_names_them(config: dict) -> dict:
    """The mixers' sizes under the keys ``flops_ssm.py`` reads."""
    return {"mamba_num_heads": config["mamba_n_heads"], "mamba_head_dim": config["mamba_d_head"],
            "ssm_state_size": config["mamba_d_state"], "n_groups": config["mamba_n_groups"],
            "num_attention_heads": config["num_attention_heads"],
            "num_key_value_heads": config["num_key_value_heads"],
            "head_dim": config["hidden_size"] // config["num_attention_heads"]}


def ssm_step_work(config: dict, lane_steps: int) -> dict:
    """The least work of decode-kernel calls that updated `lane_steps`
    (lane, Mamba layer) states (``ssm_lane_steps`` of
    ``LLMEngine.stats()``: idle lanes are not counted and cost nothing).

    A state is ``mamba_n_heads x mamba_d_head x mamba_d_state`` float32
    values (128 x 64 x 128: 4,194,304 B), read once and written once; a
    value is scaled by its head's decay, takes the rank-one update (a
    product and a sum) and is contracted with C (a product and a sum): 5
    operations.  The token's x, B and C come in and y goes out in float32
    once."""
    return flops_ssm.ssm_step_work(_as_flops_ssm_names_them(config), lane_steps)


def gqa_decode_work(config: dict, positions_attended: int, lane_calls: int, itemsize: int = 2) -> dict:
    """The least work of the grouped-query decode kernel's calls that
    attended `positions_attended` cached positions
    (``kv_positions_attended``: a lane's length, summed over lanes and
    attention layers) for `lane_calls` (lane, layer) pairs.

    An attended position is one row of K and one of V of
    ``num_key_value_heads x head_dim`` values (8 x 128: 4,096 B in bf16
    for both), read ONCE for the four query heads of each group; each of
    the ``num_attention_heads`` heads multiplies its K/V head's
    ``head_dim`` values once for the score and once for the weighted sum
    (``32 x 2 x 2 x 128`` operations; the heads of zeros the kernel pads
    a group with are the program's cost, not the algorithm's).  A lane's
    queries, own key and value come in and its output goes out in float32
    once."""
    return flops_ssm.gqa_decode_work(_as_flops_ssm_names_them(config), positions_attended, lane_calls, itemsize)


def held_experts_work(config: dict, pairs: int, experts_hit: int, itemsize: int = 2) -> dict:
    """The least work of the grouped matmuls that computed `pairs`
    token-expert pairs of HELD experts in programs whose layers hit
    `experts_hit` held experts in all (``moe_pairs`` and
    ``moe_experts_hit``).  The pairs of absent experts are no work.

    A SwiGLU expert: a pair is one row through a, b and down, THREE
    ``hidden_size x intermediate_size`` matmuls (an expert without a gate
    has two: ``flops_ssm.held_experts_work``).  An expert's three
    matrices are read once for each program and layer in which it
    received a row; a pair's rows are read and written once on each side
    of the two grouped matmuls (d in, 2f out; f in, d out)."""
    d, f = config["hidden_size"], config["intermediate_size"]
    return {
        "flops": 2.0 * pairs * 3 * d * f,
        "bytes": experts_hit * 3 * d * f * itemsize + pairs * (2 * d + 3 * f) * itemsize,
    }


def chunk_token_flops(config: dict) -> float:
    """The operations ONE real token of a prompt chunk needs in the
    layers held: every weight matrix it meets, twice its size (a
    multiply and an add a weight), and the scan's recurrence.

    A Mamba layer: ``in_proj`` (d x (2 inner + 2 groups x state +
    heads)) and ``out_proj`` (inner x d), and 5 operations a state value
    (``ssm_step_work``: the recurrence as written; the chunked form the
    program runs does more, which is the program's cost).  An attention
    layer: q and o (d x d each), k and v (d x kv heads x head size each);
    the scores over the cached context are left OUT (they go with the
    context's length, which no counter of a chunk gives: 16,384
    operations a cached position a token, 2% of a token's work at 4k),
    so the share of the peak this gives is a floor.  Every layer's
    experts part: the router (d x num_local_experts), the shared expert
    (3 d x shared_intermediate_size) and the token's pairs with HELD
    experts, ``num_experts_per_tok x held / num_local_experts`` of them
    under a router with no favourite (5 of 10), 3 d x intermediate_size
    each.  The head is one position a chunk and is left out."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    inner = config["mamba_n_heads"] * config["mamba_d_head"]
    kv = config["num_key_value_heads"] * (d // heads)
    mamba = 2.0 * (d * (2 * inner + 2 * config["mamba_n_groups"] * config["mamba_d_state"] + config["mamba_n_heads"])
                   + inner * d) + 5.0 * _state_values(config)
    attention = 2.0 * (2 * d * d + 2 * d * kv)
    held_pairs = config["num_experts_per_tok"] * config["held"]["experts_held"] / config["held"]["router_outputs"]
    experts = 2.0 * (d * config["held"]["router_outputs"] + 3 * d * config["shared_intermediate_size"]
                     + held_pairs * 3 * d * config["intermediate_size"])
    kinds = config["layer_types"]
    return kinds.count("mamba") * mamba + kinds.count("attention") * attention + len(kinds) * experts


def prefill_mfu_pct(config: dict, prompt_tokens: int, program_seconds: float, peak: dict):
    """The operations of chunk programs that took in `prompt_tokens` real
    tokens (``chunk_token_flops``) over the seconds those programs took
    (the engine's own clock around each: built, awaited, fetched) times
    the chip's bf16 peak.  A floor: the clock's seconds hold the device's
    and more.  None where no chunk ran."""
    if not peak or program_seconds <= 0 or prompt_tokens <= 0:
        return None
    return 100.0 * prompt_tokens * chunk_token_flops(config) / (program_seconds * peak["bf16_flops_per_s"])

"""Operations and bytes of what Nemotron-H's layers do beside the dense
projections every model has: the Mamba-2 decode kernel over the lanes'
states (``ray_tpu/ops/pallas_mamba2.py``: ``mamba2_decode_step``), the
dense grouped-query paged decode kernel
(``ray_tpu/ops/pallas_gqa_paged_attention.py``:
``gqa_paged_decode_attention``) and the expert layer of a chip that holds
a SHARE of routed experts that have NO gate (``ray_tpu/ops/moe.py``:
``moe_gmm``, two matrices an expert and not three).  From the
configuration file's sizes and the engine's own counters.  Like
``flops.py``: what the algorithm needs, nothing imported from the program
or JAX."""

from __future__ import annotations


def ssm_step_work(config: dict, lane_steps: int) -> dict:
    """The least work of decode-kernel calls that updated `lane_steps`
    (lane, Mamba layer) states (``ssm_lane_steps`` of
    ``LLMEngine.stats()``: idle lanes are not counted and cost nothing).

    A state is ``mamba_num_heads x mamba_head_dim x ssm_state_size``
    float32 values (2,097,152 B), read once and written once; a value is
    scaled by its head's decay, takes the rank-one update (a product and
    a sum) and is contracted with C (a product and a sum): 5 operations.
    The token's x, B and C come in and y goes out in float32 once."""
    heads, p, n = config["mamba_num_heads"], config["mamba_head_dim"], config["ssm_state_size"]
    values = heads * p * n
    token = (2 * heads * p + 2 * config["n_groups"] * n + heads) * 4
    return {"flops": 5.0 * lane_steps * values, "bytes": lane_steps * (2 * values * 4 + token)}


def gqa_decode_work(config: dict, positions_attended: int, lane_calls: int, itemsize: int = 2) -> dict:
    """The least work of the grouped-query decode kernel's calls that
    attended `positions_attended` cached positions
    (``kv_positions_attended``: a lane's length, summed over lanes and
    attention layers) for `lane_calls` (lane, layer) pairs.

    An attended position is one row of K and one of V of
    ``num_key_value_heads x head_dim`` values (1,024 B in bf16 for both),
    read ONCE for all the query heads of a group; each of the
    ``num_attention_heads`` heads multiplies its K/V head's ``head_dim``
    values once for the score and once for the weighted sum (``32 x 2 x 2
    x 128`` operations).  The positions of the whole pages the kernel
    copies past a lane's length are the program's cost and show as a
    lower share.  A lane's queries, own key and value come in and its
    output goes out in float32 once."""
    heads, kv, dh = config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]
    return {
        "flops": 2.0 * 2 * positions_attended * heads * dh,
        "bytes": positions_attended * 2 * kv * dh * itemsize + lane_calls * (2 * heads + 2 * kv) * dh * 4,
    }


def held_experts_work(config: dict, pairs: int, experts_hit: int, itemsize: int = 2) -> dict:
    """The least work of the grouped matmuls that computed `pairs`
    token-expert pairs of HELD experts in programs whose layers hit
    `experts_hit` held experts in all (``moe_pairs`` and
    ``moe_experts_hit``).  The pairs of absent experts are no work.

    These experts have no gate: a pair is one row through up and down,
    TWO ``hidden_size x moe_intermediate_size`` matmuls (a SwiGLU
    expert's are three: ``flops_moe.grouped_matmul_work``).  An expert's
    two matrices are read once for each program and layer in which it
    received a row; a pair's rows are read and written once on each side
    of the two matmuls (d in, f out; f in, d out)."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    return {
        "flops": 2.0 * pairs * 2 * d * f,
        "bytes": experts_hit * 2 * d * f * itemsize + pairs * 2 * (d + f) * itemsize,
    }

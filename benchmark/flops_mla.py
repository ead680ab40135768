"""Operations and bytes of what Mistral-Small-4's layers do beside the
dense projections every model has: the absorbed decode kernel over the
latent pages (``ray_tpu/ops/pallas_mla_paged_attention.py``:
``mla_paged_decode_attention``) and the expert layer of a chip that
holds a SHARE of the routed experts beside a shared one
(``ray_tpu/ops/moe.py``: ``moe_gmm``).  From the configuration file's
sizes and the engine's own counters.  Like ``flops.py``: what the
algorithm needs, nothing imported from the program or JAX."""

from __future__ import annotations

from benchmark import flops_moe


def mla_decode_work(config: dict, positions_attended: int, lane_calls: int, itemsize: int = 2) -> dict:
    """The least work of the decode kernel's calls that attended
    `positions_attended` cached positions (``kv_positions_attended`` of
    ``LLMEngine.stats()``: a lane's length, summed over lanes and
    layers) for `lane_calls` (lane, layer) pairs.

    An attended position is ONE row of ``kv_lora_rank +
    qk_rope_head_dim`` values (320: 640 B in bf16), read once for all
    heads, keys and values both; each of the ``num_attention_heads``
    heads multiplies all of it once for the score and its first
    ``kv_lora_rank`` values once for the weighted sum.  The positions of
    the whole pages the kernel copies past a lane's length, and the
    columns a stored row is padded by, are the program's cost and show
    as a lower share.  A lane's queries and own row come in and its
    output goes out in float32 once."""
    row, lat = config["kv_lora_rank"] + config["qk_rope_head_dim"], config["kv_lora_rank"]
    heads = config["num_attention_heads"]
    return {
        "flops": 2.0 * positions_attended * heads * (row + lat),
        "bytes": positions_attended * row * itemsize + lane_calls * (heads * row + row + heads * lat) * 4,
    }


def expert_sizes(config: dict) -> dict:
    """The configuration under the names ``flops_moe.grouped_matmul_work``
    and ``serve_olmoe.gmm_roofline_pct`` read: an expert's width is
    ``moe_intermediate_size`` here (``intermediate_size`` is the model's
    unused dense width)."""
    return dict(config, intermediate_size=config["moe_intermediate_size"])


def held_experts_work(config: dict, pairs: int, experts_hit: int, itemsize: int = 2) -> dict:
    """The least work of the grouped matmuls that computed `pairs`
    token-expert pairs of HELD experts in programs whose layers hit
    `experts_hit` held experts in all (``moe_pairs`` and
    ``moe_experts_hit`` of ``LLMEngine.stats()``):
    ``flops_moe.grouped_matmul_work`` at this model's expert width.  The
    pairs of absent experts are no work."""
    return flops_moe.grouped_matmul_work(expert_sizes(config), pairs, experts_hit, itemsize)

"""Operations and bytes of what GLM-5's decode step does beside the
projections and the experts every such model has: the index scores over
the paged index keys (``ray_tpu/ops/pallas_dsa.py``:
``dsa_index_paged_scores``) and the absorbed attention over the CHOSEN
latent rows (``mla_sparse_paged_decode_attention``).  From the
configuration file's sizes and the engine's own counters.  Like
``flops.py``: what the algorithm needs, nothing imported from the
program or JAX.  The experts' work is ``flops_mla.held_experts_work``
(an expert's width is ``moe_intermediate_size`` here too)."""

from __future__ import annotations


def index_scores_work(config: dict, positions_scored: int, lane_calls: int, itemsize: int = 2) -> dict:
    """The least work of the index kernel's calls that scored
    `positions_scored` cached positions (``dsa_index_positions_scored`` of
    ``LLMEngine.stats()``: a lane's length, summed over lanes and layers)
    for `lane_calls` (lane, layer) pairs.

    A scored position is ONE index key of ``index_head_dim`` values (128:
    256 B in bf16), read once for all ``index_n_heads`` heads; each head
    multiplies all of it once (``2 x 32 x 128`` operations a position;
    the ReLU, the weights and the sum over heads are not counted); the
    score goes out once, float32.  A lane's queries and weights come in
    once, float32.  The keys of the whole pages copied past a lane's
    length, and the positions of the result past it, are the program's
    cost and show as a lower share."""
    heads, dim = config["index_n_heads"], config["index_head_dim"]
    return {
        "flops": 2.0 * positions_scored * heads * dim,
        "bytes": positions_scored * (dim * itemsize + 4) + lane_calls * (heads * dim + heads) * 4,
    }


def sparse_decode_work(config: dict, positions_attended: int, lane_calls: int, itemsize: int = 2) -> dict:
    """The least work of the attention kernel's calls that attended
    `positions_attended` chosen cached positions
    (``kv_positions_attended``) for `lane_calls` (lane, layer) pairs.

    A chosen position is ONE row of ``kv_lora_rank + qk_rope_head_dim``
    values (576: 1,152 B in bf16), read once for all heads, keys and
    values both; each of the ``num_attention_heads`` heads multiplies all
    of it once for the score and its first ``kv_lora_rank`` values once
    for the weighted sum (``2 x 64 x (576 + 512)`` operations a
    position).  The 64 columns a stored row is padded by and the rows
    NOT chosen that the kernel's walk copies with every page a lane holds
    (a pool's single row is not a copy the compiler takes) are the
    program's cost and show as a lower share: at most the share of a
    lane's positions that were chosen.  A lane's queries and own row come
    in and its output goes out in float32 once."""
    row, lat = config["kv_lora_rank"] + config["qk_rope_head_dim"], config["kv_lora_rank"]
    heads = config["num_attention_heads"]
    return {
        "flops": 2.0 * positions_attended * heads * (row + lat),
        "bytes": positions_attended * row * itemsize + lane_calls * (heads * row + row + heads * lat) * 4,
    }

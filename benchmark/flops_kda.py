"""Operations and bytes of what Kimi-Linear's KDA layers do beside the
dense projections every model has: the gated-delta-rule decode kernel
over the lanes' states (``ray_tpu/ops/pallas_kda.py``:
``kda_decode_step``) and the chunked form a prompt chunk takes
(``ray_tpu/ops/kda.py``: ``kda_chunk``, plain XLA).  The latent layers'
decode kernel and the held experts' grouped matmuls are
``flops_mla.py``'s (``mla_decode_work``, ``held_experts_work``), at this
configuration's sizes.  From the configuration file's sizes and the
engine's own counters.  Like ``flops.py``: what the algorithm needs,
nothing imported from the program or JAX."""

from __future__ import annotations


def _head(config: dict) -> tuple:
    lin = config["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"]


def kda_step_work(config: dict, lane_steps: int) -> dict:
    """The least work of decode-kernel calls that updated `lane_steps`
    (lane, KDA layer) states (``kda_lane_steps`` of
    ``LLMEngine.stats()``: idle lanes are not counted and cost nothing).

    A state is ``num_heads x head_dim x head_dim`` float32 values
    (2,097,152 B), read once and written once; a value is scaled by its
    channel's decay (1 operation), multiplied by k and summed for ``S'^T
    k`` (2), takes the rank-one correction (a product and a sum: 2) and is
    multiplied by q and summed for the output (2): 7 operations.  The
    token's q, k, alpha (``3 x heads x head_dim``), v (``heads x
    head_dim``) and beta come in and o goes out in float32 once."""
    heads, d = _head(config)
    values = heads * d * d
    token = (5 * heads * d + heads) * 4
    return {"flops": 7.0 * lane_steps * values, "bytes": lane_steps * (2 * values * 4 + token)}


def kda_chunk_work(config: dict, tokens: int, block: int = 64, itemsize: int = 2) -> dict:
    """The least work of the chunked form over `tokens` (token, KDA
    layer) pairs (``kda_chunk_tokens``), in blocks of `block` positions.

    A block of C positions of one head: the two decayed products ``A``
    (k with k) and ``P`` (q with k), ``2 x 2 C^2 d``; the triangular
    solve against ``[V | K exp(G)]``, ``C^2 (d + d)``; against the
    carried state ``T K S``, ``Q S`` and the state's update, ``3 x 2 C
    d^2``; and ``P U``, ``2 C^2 d``.  A token: ``8 C d + 6 d^2``
    operations a head (163,840 at C 64, d 128; 5.24 M a layer of 32
    heads).  Its q, k, v come in and o goes out in the serving dtype,
    its log-decays in float32; the state stays on the chip within a
    chunk."""
    heads, d = _head(config)
    per_token_head = 8 * block * d + 6 * d * d
    return {"flops": float(tokens) * heads * per_token_head,
            "bytes": tokens * heads * d * (4 * itemsize + 4)}

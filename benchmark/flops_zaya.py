"""Operations and bytes of what ZAYA1's programs do, under the
configuration file's OWN key names: the grouped-query paged decode
kernel at 2 K/V heads of 4 queries in EVERY layer
(``ray_tpu/ops/pallas_gqa_paged_attention.py``:
``gqa_paged_decode_attention``) and the expert part's grouped matmuls
(``ray_tpu/ops/moe.py``: ``moe_gmm``, THREE matrices an expert, all 16
held, a token's ONE pair or none) are what ``flops_mellum.py`` already
says under the same key names (``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``hidden_size``,
``moe_intermediate_size``) and are imported from there; a prompt chunk's
program by its real tokens is this family's own.  Like ``flops.py``:
what the algorithm needs, nothing imported from the program or JAX."""

from __future__ import annotations

from benchmark.flops_mellum import experts_work, gqa_decode_work  # noqa: F401 - this family's, under the same keys


def chunk_token_flops(config: dict) -> float:
    """The operations ONE real token of a prompt chunk needs in the
    layers held: every weight matrix it meets, twice its size (a
    multiply and an add a weight).  A layer: the latents (d x (heads +
    kv heads + 2 half-groups of values) x head_dim: 2048 x 1536), the
    grouped convolution (10 heads x 2 taps x 128 x 128), ``W_o`` (heads
    x head_dim x d), the router (d x R, two R x R, R x 17) and, for the
    16 of 17 tokens that a router with no favourite sends to an expert,
    3 d x moe_intermediate_size.  The depthwise convolution, the q-k
    mean, the norms and the rotation are elementwise and left out, and
    so are the scores (the context's length, which no counter of a chunk
    gives: 4,096 operations a cached position a token, 2% of a token's
    work at 4k), so the share of the peak this gives is a floor.  The
    head is one position a chunk and is left out."""
    d, dh, R = config["hidden_size"], config["head_dim"], config["router_hidden_size"]
    heads, kv, E = config["num_attention_heads"], config["num_key_value_heads"], config["num_experts"]
    latents = d * (heads + kv + 2 * (kv // 2)) * dh
    conv1 = (heads + kv) * config["cca_time1"] * dh * dh
    router = d * R + 2 * R * R + R * (E + 1)
    experts = config["num_experts_per_tok"] * E / (E + 1) * 3 * d * config["moe_intermediate_size"]
    return config["num_hidden_layers"] * 2.0 * (latents + conv1 + heads * dh * d + router + experts)


def prefill_mfu_pct(config: dict, prompt_tokens: int, program_seconds: float, peak: dict):
    """The operations of chunk programs that took in `prompt_tokens` real
    tokens (``chunk_token_flops``) over the seconds those programs took
    (the engine's own clock around each: built, awaited, fetched) times
    the chip's bf16 peak.  A floor: the clock's seconds hold the device's
    and more.  None where no chunk ran."""
    if not peak or program_seconds <= 0 or prompt_tokens <= 0:
        return None
    return 100.0 * prompt_tokens * chunk_token_flops(config) / (program_seconds * peak["bf16_flops_per_s"])

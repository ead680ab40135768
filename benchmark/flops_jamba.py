"""Operations and bytes of what Jamba's layers do beside the dense
projections every model has: the two Mamba-1 kernels
(``ray_tpu/ops/pallas_mamba1.py``: ``mamba1_decode_step`` over the
lanes' states, ``mamba1_chunk_scan`` over a prompt chunk's positions),
and a prompt token's matmuls for ``prefill_mfu_pct``.  From the
configuration file's sizes (the source's own key names:
``mamba_expand``, ``mamba_d_state``, ``mamba_dt_rank``) and the engine's
own counters.  Like ``flops.py``: what the algorithm needs, nothing
imported from the program or JAX.

``peaks.json`` has no vector-unit and no transcendental peak, and the
scan is neither bytes nor matmuls: a value of the state takes one
``exp`` and five operations a position and moves nothing.  So the
least time here is the BYTES' (the operations over the matrix unit's
197 TFLOP/s are far under it), and a kernel that the vector unit bounds
reads LOW against it: the chunk kernel by its nature (a position's 16 x
5,120 values of state are kept in registers: 61 KB of rows against 82 K
``exp``), the decode kernel only where its arithmetic cannot keep up
with its copies.  Read ``mamba1_chunk_scan_roofline`` beside the
kernel's milliseconds a 2,048-token chunk (``PERF.md`` section 5).
The grouped-query kernel's work is ``flops_ssm.gqa_decode_work`` given
``num_key_value_heads`` 1 and ``head_dim`` 128."""

from __future__ import annotations


def _inner(config: dict) -> int:
    return config["mamba_expand"] * config["hidden_size"]


def ssm1_step_work(config: dict, lane_steps: int) -> dict:
    """The least work of decode-kernel calls that updated `lane_steps`
    (lane, Mamba layer) states (``ssm_lane_steps`` of
    ``LLMEngine.stats()``: idle lanes are not counted).

    A state is ``d_inner x mamba_d_state`` float32 values (5,120 x 16:
    327,680 B), read once and written once; a value takes its own decay
    (a product, an ``exp``, a product), the update (a product and a sum)
    and the contraction with C (a product and a sum): one ``exp`` and
    five operations beside it.  The token's rows come in and go out in
    float32 once: ``dt``, ``dt x`` and ``y`` (d_inner each), ``B`` and
    ``C`` (mamba_d_state each)."""
    values = _inner(config) * config["mamba_d_state"]
    token = (3 * _inner(config) + 2 * config["mamba_d_state"]) * 4
    return {"flops": 5.0 * lane_steps * values, "bytes": lane_steps * (2 * values * 4 + token)}


def ssm1_chunk_work(config: dict, chunk_tokens: int, chunks: int) -> dict:
    """The least work of chunk-kernel calls that took `chunk_tokens`
    (real token, Mamba layer) pairs in `chunks` (chunk program, Mamba
    layer) calls (``ssm_chunk_tokens``; ``prefill_chunks`` x the Mamba
    layers).

    A position's ``dt``, ``dt x`` and ``y`` rows (d_inner float32 values
    each) and its ``B`` and ``C`` (mamba_d_state each) move once; the
    lane's state (d_inner x mamba_d_state float32) comes in and goes out
    once a call; a value of the state takes one ``exp`` and five
    operations a position.  The pads of a bucket are the program's cost
    and show as a lower share."""
    d, n = _inner(config), config["mamba_d_state"]
    return {"flops": 5.0 * chunk_tokens * d * n,
            "bytes": chunk_tokens * (3 * d + 2 * n) * 4 + chunks * 2 * d * n * 4}


def chunk_token_flops(config: dict) -> float:
    """The operations ONE real token of a prompt chunk needs in the 28
    layers: every weight matrix it meets, twice its size.  A Mamba
    layer: in_proj (d x 2 d_inner), x_proj (d_inner x (dt_rank + 2 N)),
    dt_proj (dt_rank x d_inner), out_proj (d_inner x d); an attention
    layer: q and o (d x d each), k and v (d x kv heads x head_dim each);
    every layer the SwiGLU's three d x intermediate_size.  The scan (5
    operations and an ``exp`` a value of the state, on the vector unit)
    and the scores are left OUT, so the share of the matrix unit's peak
    this gives is a floor.  The head is one position a chunk and is left
    out."""
    d, di, n, r = config["hidden_size"], _inner(config), config["mamba_d_state"], config["mamba_dt_rank"]
    layers, period, offset = (config[k] for k in ("num_hidden_layers", "attn_layer_period", "attn_layer_offset"))
    attn = sum(1 for i in range(layers) if i % period == offset)
    kv = config["num_key_value_heads"] * (d // config["num_attention_heads"])
    mamba = 2.0 * (d * 2 * di + di * (r + 2 * n) + r * di + di * d)
    attention = 2.0 * (2 * d * d + 2 * d * kv)
    mlp = 2.0 * 3 * d * config["intermediate_size"]
    return (layers - attn) * mamba + attn * attention + layers * mlp


def prefill_mfu_pct(config: dict, prompt_tokens: int, program_seconds: float, peak: dict):
    """``flops_mellum.prefill_mfu_pct`` with this configuration's
    ``chunk_token_flops``.  None where no chunk ran."""
    if not peak or program_seconds <= 0 or prompt_tokens <= 0:
        return None
    return 100.0 * prompt_tokens * chunk_token_flops(config) / (program_seconds * peak["bf16_flops_per_s"])

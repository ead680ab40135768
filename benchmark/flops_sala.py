"""Operations and bytes of what MiniCPM-SALA's mixers do beside the
matmuls every model has: the sparse layers' decode kernel over chosen
pages (``ray_tpu/ops/pallas_sparse_paged_attention.py``:
``sparse_paged_decode_attention``), the lightning layers' state in
decode, and a prompt chunk's scan.  From the configuration file's sizes
and the engine's own counters.  Like ``flops.py``: what the algorithm
needs, nothing imported from the program or JAX."""

from __future__ import annotations


def sparse_decode_work(config: dict, positions_gathered: int, pairs: int, itemsize: int = 2) -> dict:
    """The least work of the decode kernel's calls that copied
    `positions_gathered` positions (``kv_positions_gathered`` of
    ``LLMEngine.stats()``: the chosen blocks, whole, summed over lanes,
    K/V heads and sparse layers) for `pairs` (lane, K/V head, layer)
    triples.

    A copied position is one key and one value of ``head_dim`` values,
    read once; each of the ``num_attention_heads / num_key_value_heads``
    query heads of the pair multiplies it twice (the score, the
    weighted sum).  A pair's queries come in and its output goes out in
    float32, its own key and value once."""
    d = config["head_dim"]
    rep = config["num_attention_heads"] // config["num_key_value_heads"]
    return {
        "flops": 2.0 * 2 * positions_gathered * rep * d,
        "bytes": positions_gathered * 2 * d * itemsize + pairs * (2 * rep * d + 2 * d) * 4,
    }


def lightning_step_work(config: dict, lanes: int, layers: int) -> dict:
    """One decode step's rank-one updates: every lane's state of
    ``lightning_nh x d x d`` float32 read and written once in every
    lightning layer (``state_bytes`` counts the same); a multiply-add an
    element for the update, another for q S."""
    n = lanes * layers * config["lightning_nh"] * config["lightning_head_dim"] ** 2
    return {"flops": 4.0 * n, "bytes": 2 * 4 * n}


def lightning_chunk_work(config: dict, tokens: int, layers: int, block: int = 256, itemsize: int = 2) -> dict:
    """A prompt chunk's scan in blocks of `block` positions: inside a
    block q k^T and its product with v (2 * block * d a token and head
    each), across blocks q S and k^T v (2 * d * d each); q, k, v read
    and o written once; the state read and written once a block."""
    H, d = config["lightning_nh"], config["lightning_head_dim"]
    per_token = 2.0 * H * (2 * block * d + 2 * d * d)
    blocks = -(-tokens // block)
    return {
        "flops": layers * tokens * per_token,
        "bytes": layers * (4 * tokens * H * d * itemsize + blocks * 2 * H * d * d * 4),
    }

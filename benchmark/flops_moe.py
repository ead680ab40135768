"""Operations and bytes of the expert layers' grouped matmuls
(``ray_tpu/ops/moe.py``: ``moe_gmm``), from the configuration file's
sizes and the engine's own counters.  Like ``flops.py``: what the
algorithm needs, nothing imported from the program or JAX."""

from __future__ import annotations


def grouped_matmul_work(config: dict, pairs: int, experts_hit: int, itemsize: int = 2) -> dict:
    """The least work of the grouped matmuls that computed `pairs`
    token-expert pairs in programs whose layers hit `experts_hit`
    experts in all (``moe_pairs`` and ``moe_experts_hit`` of
    ``LLMEngine.stats()``).

    A pair is one row through gate, up and down: three d x f matmuls.
    An expert's three matrices are read once for each program and layer
    in which it received a row (a floor: a kernel whose row tiles split
    a group reads them again); a pair's rows are read and written once
    on each side of the two matmuls (d in, 2f out; f in, d out)."""
    d, f = config["hidden_size"], config["intermediate_size"]
    return {
        "flops": 2.0 * pairs * 3 * d * f,
        "bytes": experts_hit * 3 * d * f * itemsize + pairs * (2 * d + 3 * f) * itemsize,
    }

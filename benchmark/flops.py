"""Operations and bytes from shapes.

The benchmark's own arithmetic: what the algorithm needs, never what a
compiler reports (``cost_analysis()`` counts recomputation and sees a
Pallas call as zero).  Sizes come from a configuration file
(``benchmark/configs/<config>.json``), so nothing here imports the
program or JAX.
"""

from __future__ import annotations


def matmul_params(sizes: dict) -> int:
    """Parameters that take part in a matrix multiplication for every
    token: the four dense layers of each block (12 d^2) and the output
    head over the rows of the vocabulary that are held.  Embedding
    look-ups, biases and layer norms do no matmul work."""
    d = sizes["n_embd"]
    return sizes["n_layer"] * 12 * d * d + sizes["vocab_rows"] * d


def train_flops_per_token(sizes: dict, seq: int) -> float:
    """Forward and backward operations one trained token requires:
    6 per matmul parameter, plus causal attention.  Attention forward is
    QK^T and PV, 2 * 2 * T * d a token and layer over the full square
    and half of that under the causal mask; backward is twice forward.
    Recomputation (remat, the flash backward's second QK^T) is not
    counted: this is the model's work, not the program's."""
    attn_fwd = 2 * seq * sizes["n_embd"]  # causal half of 4*T*d
    return 6.0 * matmul_params(sizes) + 3.0 * sizes["n_layer"] * attn_fwd


def _bytes(n_elements: int, itemsize: int) -> int:
    return n_elements * itemsize


def flash_forward(batch: int, heads: int, seq: int, d_head: int, itemsize: int = 2) -> dict:
    """One causal flash forward over [batch, heads, seq, d_head]: QK^T
    and PV on the lower triangle; q, k, v read and o written once, the
    float32 log-sum-exp written once."""
    tile = batch * heads * seq * d_head
    return {
        "flops": 0.5 * 4.0 * batch * heads * seq * seq * d_head,
        "bytes": _bytes(4 * tile, itemsize) + _bytes(batch * heads * seq, 4),
    }


def flash_backward(batch: int, heads: int, seq: int, d_head: int, itemsize: int = 2) -> dict:
    """One causal flash backward: S = QK^T again, dP = dO V^T, dV = P^T
    dO, dQ = dS K, dK = dS^T Q, five matmuls of the forward's two, on
    the lower triangle.  A kernel split into a dq and a dkv pass
    recomputes S and dP in each (seven matmuls): that is the program's
    cost and shows as a lower roofline share.  Reads q, k, v, o, dO and
    the two float32 row vectors; writes dq, dk, dv."""
    tile = batch * heads * seq * d_head
    return {
        "flops": 0.5 * 10.0 * batch * heads * seq * seq * d_head,
        "bytes": _bytes(8 * tile, itemsize) + _bytes(2 * batch * heads * seq, 4),
    }


def least_seconds(work: dict, peak: dict) -> dict:
    """The least time one chip could take for `work`, and which roof
    sets it."""
    by_compute = work["flops"] / peak["bf16_flops_per_s"]
    by_memory = work["bytes"] / peak["hbm_bytes_per_s"]
    return {
        "seconds": max(by_compute, by_memory),
        "bound": "compute" if by_compute >= by_memory else "memory",
    }


def flash_step_work(sizes: dict, batch: int, seq: int) -> dict:
    """Forward plus backward flash work of one train step on the whole
    batch, every layer."""
    d_head = sizes["n_embd"] // sizes["n_head"]
    fwd = flash_forward(batch, sizes["n_head"], seq, d_head)
    bwd = flash_backward(batch, sizes["n_head"], seq, d_head)
    return {
        "flops": sizes["n_layer"] * (fwd["flops"] + bwd["flops"]),
        "bytes": sizes["n_layer"] * (fwd["bytes"] + bwd["bytes"]),
    }

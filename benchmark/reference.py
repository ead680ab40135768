"""The plain reference of the GPT-2 family: forward pass in float32
``jax.numpy`` with einsum attention, no kernel, no cache, no batching
tricks, and nothing imported from the program.  It reads the program's
parameter tree (wte, wpe, h_<i>/{ln_1, attn/{qkv, attn_out}, ln_2,
mlp/{mlp_up, mlp_down}}, ln_f, lm_head): that tree is the interface.

Departures of the program from the published model, which the reference
follows because it checks the program and not the checkpoint: layer-norm
epsilon 1e-6 (Flax's default; the source says 1e-5), an output head of
its own (the source ties it to the embedding), 50,304 rows of vocabulary
(50,257 padded to 128).  ``gelu_new`` is the tanh approximation, as
published.

Only a process that holds the chip (or a CPU rehearsal) imports this.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-6


def _ln(x, p):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _dense(x, p):
    out = x @ p["kernel"].astype(jnp.float32)
    return out + p["bias"] if "bias" in p else out


def logits(params, tokens, n_layer: int, n_head: int):
    """[B, T] token ids -> [B, T, rows] float32 logits."""
    B, T = tokens.shape
    x = params["wte"]["embedding"][tokens] + params["wpe"]["embedding"][jnp.arange(T)[None]]
    x = x.astype(jnp.float32)
    d = x.shape[-1]
    causal = jnp.tril(jnp.ones((T, T), bool))
    for i in range(n_layer):
        blk = params[f"h_{i}"]
        qkv = _dense(_ln(x, blk["ln_1"]), blk["attn"]["qkv"])
        q, k, v = (t.reshape(B, T, n_head, d // n_head) for t in jnp.split(qkv, 3, axis=-1))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(d // n_head)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        att = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, T, d)
        x = x + _dense(att, blk["attn"]["attn_out"])
        h = jax.nn.gelu(_dense(_ln(x, blk["ln_2"]), blk["mlp"]["mlp_up"]), approximate=True)
        x = x + _dense(h, blk["mlp"]["mlp_down"])
    return _dense(_ln(x, params["ln_f"]), params["lm_head"])


def token_losses(params, tokens, targets, n_layer: int, n_head: int, chunk: int = 2):
    """Next-token cross entropy of every position, [B, T] float32 on the
    host, `chunk` sequences at a time so that the float32 logits fit
    beside the training state."""
    import numpy as np

    def losses(params, tok, tgt):
        lg = logits(params, tok, n_layer, n_head)
        return cross_entropy(lg, tgt)

    out = []
    # on the TPU a float32 matmul runs as bf16 passes unless told otherwise
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(losses)
        for i in range(0, tokens.shape[0], chunk):
            out.append(np.asarray(fn(params, tokens[i:i + chunk], targets[i:i + chunk])))
    return np.concatenate(out)


def cross_entropy(lg, targets):
    """[.., rows] logits of any type and [..] targets -> float32 losses."""
    lg = lg.astype(jnp.float32)
    picked = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return jax.nn.logsumexp(lg, axis=-1) - picked


def full_logits(params, tokens, n_layer: int, n_head: int):
    """float32 logits of whole sequences, for the serving check."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(logits, static_argnums=(2, 3))(params, tokens, n_layer, n_head)

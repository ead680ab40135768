"""The plain reference of the GLM-5 family (``model_type:
glm_moe_dsa``): forward pass in float32 ``jax.numpy`` at ``highest``
matmul precision, attention in its NON-absorbed form (keys and values
expanded for every head from the latent, one softmax a query over the
positions its index chose), the choice by a FULL SORT of a query's index
scores (stable, so a tie goes to the lower position), the experts the
dense way (every held expert's output for every token times the token's
weight for it, zero where the expert is not among its 8).  No kernel, no
cache, no chunks, no pages, no counting passes, no grouped matmul, and
nothing imported from the program.  It reads the program's parameter
tree (``embed``, ``layers`` of ``w_in, wdq, w_qn, wuq, wdkv, w_kvn, wukv,
wo, wq_idx, wk_idx, k_idx_w, k_idx_b, w_idx, w_post`` and ``wgu_dense,
wd_dense`` or ``router, router_bias, wgu_shared, wd_shared, wgu, wd``,
``norm``, ``lm_head``): that tree is the interface.

The model (zai-org/GLM-5 ``config.json``; keys in brackets), a layer on
the residual stream x of one sequence, ``rmsnorm(x, w) = w x
rsqrt(mean(x^2) + 1e-5)``:

    h  = rmsnorm(x, w_in)
    cq = rmsnorm(h Wdq, w_qn)                        [q_lora_rank 2048]
    q  = cq Wuq: 64 heads of [q_nope 192 | q_rope 64] [qk_nope_head_dim, qk_rope_head_dim]
    [c | kr] = h Wdkv;  c = rmsnorm(c, w_kvn)        [kv_lora_rank 512 | 64]
    q_rope, kr rotated at the position over pairs (2i, 2i+1) [rope_interleave],
        frequencies 1e6^(-2i/64)                     [rope_parameters: rope_theta, rope_type default]
    qI = cq WqI: 32 heads of 128                     [index_n_heads, index_head_dim]
    kI = layernorm(h WkI) (weight, bias, eps 1e-6), one for all heads
    the first 64 values of every qI head and of kI rotated likewise [indexer_rope_interleave]
    w  = (h Ww) 32^-0.5 128^-0.5
    I(t, s<=t) = sum_j w_j(t) relu(qI_j(t) . kI(s))
    S(t) = the 2,048 positions of largest I(t, .)    [index_topk]
    [k_nope 192 | v 256] of head i = c Wukv[i]       [v_head_dim]
    score(t, s in S(t)) = (q_nope.k_nope + q_rope.kr) 256^-0.5
    x  = x + (softmax over S(t) of score, times v, heads side by side) Wo
    h2 = rmsnorm(x, w_post)
    a leading dense layer [first_k_dense_replace]:  x = x + SwiGLU(h2), width 12,288 [intermediate_size]
    an expert layer:
    s  = sigmoid(h2 Wr) over all 256                 [n_routed_experts, scoring_func]
    the 8 largest of s + b                           [num_experts_per_tok, topk_method noaux_tc]
    g_e = 2.5 s_e / sum of the chosen s              [norm_topk_prob, routed_scaling_factor]
    x  = x + SwiGLU_shared(h2) + sum over those of the 8 that are HELD of g_e SwiGLU_e(h2)
         SwiGLU(h) = (silu(h Wg) * (h Wu)) Wd, width 2048 [moe_intermediate_size, n_shared_experts 1]

then rmsnorm and the untied head over the rows of the vocabulary held.

The SHARE (``first``, ``count`` of the routed experts; the rows of the
vocabulary the tree holds): the router scores all 256 experts and keeps
8 a token; of those, the experts ``first .. first + count - 1`` alone
are in the tree and add their part; the others add nothing, here as in
the program.  Which layers are dense is read off the tree (a layer with
``wgu_dense``).

DEPARTURES from the published description, each ASSUMED because the
catalog's row of the source does not carry it (``benchmark/configs/
glm-5.json`` lists the same):

- bf16 parameters; weights seeded random (normal 0.02, norm weights 1,
  the LayerNorm's bias 0); ``b`` (``e_score_correction_bias``) seeded
  normal std 0.02, so that it does choose;
- the indexer rotates the FIRST 64 values of a head; its LayerNorm's eps
  is 1e-6; ``w`` is scaled by ``heads^-0.5 head_dim^-0.5``;
- the family's Hadamard rotation of ``qI`` and ``kI`` is left out (an
  orthogonal map leaves their products as they are), and so is their
  FP8 storage;
- a tie at the 2,048th score goes to the lower position;
- ``n_group`` and ``topk_group`` 1 mean no group limit; the multi-token
  prediction layer is left out (the next-token logits do not depend on
  it).

The weights stay in the program's dtype; slices of them are cast to
float32 as they are used: the projections and the feed-forward go a
block of ``ROWS`` positions at a time, the index and the attention
``QUERIES`` queries at a time and the attention ``HEADS`` heads at a
time, so that the reference of a 9k-token sequence fits beside the
engine's weights and cache on the chip.  Only a process that holds the
chip (or a CPU rehearsal) imports this.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROWS = 1024  # positions a block of the projections and the feed-forward
QUERIES = 128  # queries a block of the index and of attention
HEADS = 8  # heads whose keys and values are expanded at once
COLUMNS = 2048  # columns of the dense layer's width taken at once


def rmsnorm(x, w, eps):
    return w.astype(F32) * x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)


def rotate(x, c):
    """x [T, ..., D] at positions 0..T-1 over pairs (2i, 2i + 1)."""
    T, d = x.shape[0], x.shape[-1]
    f = c["rope_theta"] ** (-2.0 * jnp.arange(d // 2, dtype=F32) / d)
    ang = (jnp.arange(T, dtype=F32)[:, None] * f[None, :]).reshape(T, *([1] * (x.ndim - 2)), -1)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def rotate_first(x, n, c):
    """The first n values of x [T, ..., D] rotated, the others as they are."""
    return jnp.concatenate([rotate(x[..., :n], c), x[..., n:]], axis=-1)


def by_rows(f, x, rows=ROWS):
    """f over x [T, ...] a block of ``rows`` positions at a time."""
    T = x.shape[0]
    pad = -T % rows
    xp = jnp.concatenate([x, jnp.zeros((pad, *x.shape[1:]), x.dtype)]) if pad else x
    out = jax.lax.map(f, xp.reshape(-1, rows, *x.shape[1:]))
    return jax.tree.map(lambda o: o.reshape(-1, *o.shape[2:])[:T], out)


def chosen(q_i, w, k_i, c, choice="index"):
    """The index's choice: q_i [T, Hi, Di], w [T, Hi], k_i [T, Di] ->
    [T, T] bool, query t's ``index_topk`` positions of largest ``I(t,
    .)`` among ``s <= t`` by a full stable sort (every ``s <= t`` where
    there are no more).  ``choice`` "recent": the latest ``index_topk``
    positions instead, and "all": every position (the builder's
    wrong-on-purpose readings)."""
    T, k = q_i.shape[0], c["index_topk"]
    pos = jnp.arange(T)

    def rows(xs):
        qb, wb, tb = xs
        valid = pos[None, :] <= tb[:, None]
        if choice == "all":
            return valid
        if choice == "recent":
            return valid & (pos[None, :] > tb[:, None] - k)
        s = jnp.einsum("qhd,kd->qhk", qb, k_i)
        scores = (jax.nn.relu(s) * wb[:, :, None]).sum(1)
        scores = jnp.where(valid, jnp.where(scores == 0, 0.0, scores), -jnp.inf)
        order = jnp.argsort(-scores, axis=-1, stable=True)  # largest first, equals by position
        rank = jnp.zeros_like(order).at[jnp.arange(order.shape[0])[:, None], order].set(pos[None, :])
        return valid & (rank < k)

    pad = -T % QUERIES
    blocks = [jnp.concatenate([a, jnp.zeros((pad, *a.shape[1:]), a.dtype)]).reshape(-1, QUERIES, *a.shape[1:])
              for a in (q_i, w)]
    keep = jax.lax.map(rows, (*blocks, jnp.arange(T + pad).reshape(-1, QUERIES)))
    return keep.reshape(T + pad, T)[:T]


def attention(c_q, c_lat, k_r, keep, lp, c):
    """What attention adds to x: c_q [T, q_lora_rank], c_lat [T,
    kv_lora_rank], k_r [T, rope] rotated, keep [T, T] bool -> [T, d].
    ``HEADS`` heads at a time, one group after another (a scan: no two
    groups' keys and values are alive at once): their queries, keys and
    values expanded, one softmax a query over its kept positions, and
    their rows of Wo."""
    T, H = c_q.shape[0], c["n_head"]
    nope, rope, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    scale = (nope + rope) ** -0.5
    pad = -T % QUERIES
    # a padded query keeps position 0: its row is dropped
    keep_b = jnp.concatenate([keep, jnp.zeros((pad, T), bool).at[:, 0].set(True)]).reshape(-1, QUERIES, T)
    groups = H // HEADS
    # the weights by group of heads, in the program's dtype: a group's are cast as it is used
    wuq = lp["wuq"].reshape(-1, groups, HEADS, nope + rope).transpose(1, 0, 2, 3)
    wukv = lp["wukv"].reshape(-1, groups, HEADS, nope + dv).transpose(1, 0, 2, 3)
    wo = lp["wo"].reshape(groups, HEADS, dv, -1)

    def group(out, ws):
        wuq_g, wukv_g, wo_g = (w.astype(F32) for w in ws)
        q = by_rows(lambda cb: jnp.einsum("tc,chd->thd", cb, wuq_g), c_q)
        q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], c)], axis=-1)
        knv = by_rows(lambda cb: jnp.einsum("tc,chd->thd", cb, wukv_g), c_lat)
        k = jnp.concatenate([knv[..., :nope], jnp.broadcast_to(k_r[:, None, :], (T, HEADS, rope))], axis=-1)
        v = knv[..., nope:]

        def rows(xs):
            qb, kb = xs
            s = jnp.einsum("thd,khd->htk", qb, k) * scale
            s = jnp.where(kb[None], s, -jnp.inf)
            return jnp.einsum("htk,khd->thd", jax.nn.softmax(s, axis=-1), v)

        qp = jnp.concatenate([q, jnp.zeros((pad, *q.shape[1:]), F32)]).reshape(-1, QUERIES, HEADS, nope + rope)
        o = jax.lax.map(rows, (qp, keep_b)).reshape(T + pad, HEADS, dv)[:T]
        return out + by_rows(lambda ob: jnp.einsum("thd,hdm->tm", ob, wo_g), o), None

    out, _ = jax.lax.scan(group, jnp.zeros((T, lp["wo"].shape[1]), F32), (wuq, wukv, wo))
    return out


def expert_weights(h2, lp, c):
    """[N, E] float32 over ALL the router's experts: a token's weight
    for each of its chosen experts, zero for the others; and the experts
    chosen [N, k] (lowest number first among equals, as ``top_k``)."""
    s = jax.nn.sigmoid(h2 @ lp["router"].astype(F32))
    _, top_e = jax.lax.top_k(s + lp["router_bias"].astype(F32), c["num_experts_per_tok"])
    top_p = jnp.take_along_axis(s, top_e, axis=-1)
    if c["norm_topk_prob"]:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    top_p = top_p * c["routed_scaling_factor"]
    w = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], top_e].set(top_p)
    return w, top_e


def expert_half(x, lp, c):
    """What the experts add to x [N, d]: the shared expert's output and
    the held routed experts' weighted ones; and the experts chosen."""
    first, count = c["experts_first"], lp["wgu"].shape[0]
    h2 = rmsnorm(x, lp["w_post"], c["rms_norm_eps"])
    w, top_e = expert_weights(h2, lp, c)
    gate, up = jnp.split(h2 @ lp["wgu_shared"].astype(F32), 2, axis=-1)
    y = (jax.nn.silu(gate) * up) @ lp["wd_shared"].astype(F32)

    def one_expert(e, y):
        gate, up = jnp.split(h2 @ lp["wgu"][e].astype(F32), 2, axis=-1)
        return y + w[:, first + e, None] * ((jax.nn.silu(gate) * up) @ lp["wd"][e].astype(F32))

    return jax.lax.fori_loop(0, count, one_expert, y), top_e


def dense_half(x, lp, c):
    """What a leading dense layer's SwiGLU adds to x [N, d], ``COLUMNS``
    of its width at a time; and -1 where an expert layer names experts."""
    h2 = rmsnorm(x, lp["w_post"], c["rms_norm_eps"])
    width = lp["wd_dense"].shape[0]
    y = jnp.zeros_like(x)
    for lo in range(0, width, COLUMNS):
        hi = min(lo + COLUMNS, width)
        gate = h2 @ lp["wgu_dense"][:, lo:hi].astype(F32)
        up = h2 @ lp["wgu_dense"][:, width + lo:width + hi].astype(F32)
        y = y + (jax.nn.silu(gate) * up) @ lp["wd_dense"][lo:hi].astype(F32)
    return y, jnp.full((x.shape[0], c["num_experts_per_tok"]), -1, jnp.int32)


@functools.partial(jax.jit, static_argnames=("cfg", "choice"))
def layer(x, lp, *, cfg, choice="index"):
    """One block on x [T, d] float32 -> (x, the experts each token chose
    [T, k] (-1 in a dense layer), the positions each token attended [T,
    T] bool).  `cfg`: a tuple of (name, value) pairs."""
    c = dict(cfg)
    eps, T = c["rms_norm_eps"], x.shape[0]
    kv, rope, Hi, Di = c["kv_lora_rank"], c["qk_rope_head_dim"], c["index_n_heads"], c["index_head_dim"]
    wdq, wdkv = lp["wdq"].astype(F32), lp["wdkv"].astype(F32)
    wq_idx, wk_idx, w_idx = lp["wq_idx"].astype(F32), lp["wk_idx"].astype(F32), lp["w_idx"].astype(F32)

    def project(xb):
        h = rmsnorm(xb, lp["w_in"], eps)
        c_q = rmsnorm(h @ wdq, lp["w_qn"], eps)
        ckr = h @ wdkv
        k_i = h @ wk_idx
        mean = k_i.mean(-1, keepdims=True)
        k_i = (k_i - mean) * jax.lax.rsqrt(jnp.square(k_i - mean).mean(-1, keepdims=True) + c["index_norm_eps"])
        k_i = k_i * lp["k_idx_w"].astype(F32) + lp["k_idx_b"].astype(F32)
        w = (h @ w_idx) * (Hi ** -0.5 * Di ** -0.5)
        return c_q, rmsnorm(ckr[:, :kv], lp["w_kvn"], eps), ckr[:, kv:], c_q @ wq_idx, k_i, w

    c_q, c_lat, k_r, q_i, k_i, w = by_rows(project, x)
    q_i = q_i.reshape(T, Hi, Di)
    q_i, k_i = rotate_first(q_i, rope, c), rotate_first(k_i, rope, c)
    keep = chosen(q_i, w, k_i, c, choice)
    x = x + attention(c_q, c_lat, rotate(k_r, c), keep, lp, c)
    half = dense_half if "wgu_dense" in lp else expert_half
    y, top_e = by_rows(lambda xb: half(xb, lp, c), x)
    return x + y, top_e, keep


_KEYS = ("rms_norm_eps", "index_norm_eps", "n_head", "qk_nope_head_dim", "qk_rope_head_dim", "kv_lora_rank",
         "v_head_dim", "index_n_heads", "index_head_dim", "index_topk", "num_experts_per_tok",
         "norm_topk_prob", "routed_scaling_factor", "experts_first", "rope_theta")


def full_logits(params, tokens, cfg, positions=None, choice="index"):
    """tokens [T] of ONE sequence -> (logits [len(positions), rows held]
    float32 at `positions` (all of them when None), the experts every
    token chose in every layer [L, T, k] (-1 in a dense layer), the
    positions the tokens at `positions` attended in every layer [L,
    len(positions), T] bool).  `cfg` gives the attributes named in _KEYS;
    the experts held are ``cfg.experts_first`` on, as many as the tree
    holds."""
    sizes = tuple((k, getattr(cfg, k)) for k in _KEYS)
    # on the TPU a float32 matmul runs as bf16 passes unless told otherwise
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        chose, kept = [], []
        for lp in params["layers"]:
            x, top_e, keep = layer(x, lp, cfg=sizes, choice=choice)
            chose.append(top_e)
            kept.append(keep if positions is None else keep[jnp.asarray(positions)])
        if positions is not None:
            x = x[jnp.asarray(positions)]
        return (_head(x, params["norm"], params["lm_head"], eps=float(cfg.rms_norm_eps)), jnp.stack(chose),
                jnp.stack(kept))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, lm_head, *, eps):
    # under jit the head's cast to float32 fuses into the matmul
    return rmsnorm(x, norm, eps) @ lm_head.astype(F32)

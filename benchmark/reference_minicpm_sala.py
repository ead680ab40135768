"""The plain reference of the MiniCPM-SALA family: forward pass in
float32 ``jax.numpy`` at ``highest`` matmul precision, the lightning
recurrence as a loop over positions, the sparse layers' selection token
by token, every position attending under its own mask.  No kernel, no
cache, no chunks, no pages, and nothing imported from the program.  It
reads the program's parameter tree (``embed``, ``layers`` of ``w_in,
wqkvz, w_qn, w_kn, wo, w_post, wgu, wd`` and ``w_on`` in a lightning
layer, ``norm``, ``lm_head``): that tree is the interface.

The model (openbmb/MiniCPM-SALA ``config.json``; keys in brackets), on
the residual stream x of one sequence, ``rmsnorm(x, w) = w x
rsqrt(mean(x^2) + 1e-6)``, ``r = scale_depth / sqrt(32)`` [1.4; the
published 32 layers whatever is held]:

    x  = scale_emb E[tok]                                        [12]
    every layer:  y = rmsnorm(x, w_in);  x = x + r Mixer(y)
                  y = rmsnorm(x, w_post); x = x + r Wd(silu(Wg y) * Wu y)
    logits = W_head rmsnorm(x, norm) / (hidden_size / dim_model_base)

``lightning-attn``: q, k, v = Wq y, Wk y, Wv y in 32 heads of 128;
rmsnorm over each head of q and k [qk_norm]; both rotated at the token's
position (rotate_half, theta 10000) [lightning_use_rope]; for each head
``S_t = lambda_h S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t / sqrt(128)``
[lightning_scale]; rmsnorm over each head of o [use_output_norm]; ``o *=
sigmoid(Wz y)`` [use_output_gate]; Wo.

``minicpm4``: q in 32 heads of 128, k and v in 2; rmsnorm over each head
of q and k; no rotation [attn_use_rope false]; query head h reads K/V
head h // 16.  A query at position t < dense_len attends every position
<= t, scale 1/sqrt(128).  Otherwise: compressed keys ``c_j = mean(k[16j
: 16j + 32])`` for every window with ``16j + 31 <= t``; for K/V head g,
``p_j = sum over its 16 query heads of softmax_j(q . c_j / sqrt(128))``;
a block of 64 positions scores the largest p_j of the windows that
overlap it; block 0 and the blocks that hold positions ``t - 2047 .. t``
score infinity; the 64 highest blocks are kept (ties to the lower block
number); the query attends the positions <= t of the kept blocks.  ``o *=
sigmoid(Wz y)`` [attn_use_output_gate]; Wo.

ASSUMED, because the catalog's row of the source does not carry it
(``benchmark/configs/minicpm-sala.json`` lists the same): ``lambda_h =
exp(-2^(-8h/32))``, h = 1..32, alike in every layer (the fixed decay of
Lightning Attention-2); no activation on q, k, v; gates of one value an
output column; the sparse sizes 32 / 16 / 64 / 1 / 2048 / 64 / 8192
(MiniCPM4.1's published ``sparse_config``), forced blocks counted among
the 64; the state in float32; norm weights of one value a head column.
Departures from the published code as far as the builder knows it: the
published prefill chooses dense or sparse by the PROMPT's length, this
by the query's position, so that a prefix gives the same answer whatever
follows it (a cache needs that); the weights are seeded random.

The weights stay in the program's dtype; one layer's are cast to
float32 at a time, and the projections and the feed-forward go a block
of ``ROWS`` positions at a time, so that the reference of a 9k-token
sequence fits beside the engine's 12.7 GB on the chip.  Only a process
that holds the chip (or a CPU rehearsal) imports this.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROWS = 1024  # positions a block of the projections and the feed-forward
QUERIES = 256  # queries a block of a sparse layer: their scores over every position are [QUERIES, 32, T]


def rmsnorm(x, w, eps):
    return w.astype(F32) * x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)


def rope(x, theta):
    """x [T, H, D] at positions 0..T-1, rotate_half convention."""
    T, half = x.shape[0], x.shape[-1] // 2
    inv_freq = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def by_rows(f, x):
    """f over x [T, ...] a block of ROWS positions at a time."""
    T = x.shape[0]
    pad = -T % ROWS
    xp = jnp.concatenate([x, jnp.zeros((pad, *x.shape[1:]), x.dtype)]) if pad else x
    out = jax.lax.map(f, xp.reshape(-1, ROWS, *x.shape[1:]))
    return jax.tree.map(lambda o: o.reshape(-1, *o.shape[2:])[:T], out)


def lightning(q, k, v, n_head):
    """q, k, v [T, H, D] -> o [T, H, D]: the recurrence, position by position."""
    h = jnp.arange(1, n_head + 1, dtype=F32)
    lam = jnp.exp(-(2.0 ** (-8.0 * h / n_head)))[:, None, None]

    def step(S, qkv):
        qt, kt, vt = qkv  # [H, D]
        S = lam * S + kt[:, :, None] * vt[:, None, :]
        return S, jnp.einsum("hd,hde->he", qt, S) / jnp.sqrt(F32(q.shape[-1]))

    D = q.shape[-1]
    return jax.lax.scan(step, jnp.zeros((n_head, D, D), F32), (q, k, v))[1]


def keep_blocks(q_t, c, t, sp):
    """One token's selection: q_t [G, R, D] its query heads by K/V head,
    c [NW, G, D] the compressed keys, t its position.  -> [G, NB] bool,
    the blocks it reads (NB: the blocks of the whole sequence)."""
    size, stride, bsz = sp["kernel_size"], sp["kernel_stride"], sp["block_size"]
    NW, NB = c.shape[0], sp["n_blocks"]
    j = jnp.arange(NW)
    whole = stride * j + size - 1 <= t
    s = jnp.einsum("grd,jgd->grj", q_t, c) / jnp.sqrt(F32(q_t.shape[-1]))
    s = jnp.where(whole, s, -jnp.inf)
    p = jnp.where(whole, jax.nn.softmax(s, axis=-1), 0.0).sum(1)  # [G, NW]; no whole window: zeros
    p = jnp.where(whole.any(), p, 0.0)
    b = jnp.arange(NB)
    # window j covers [stride j, stride j + size); block b covers [bsz b, bsz b + bsz)
    overlaps = (stride * j[None, :] < bsz * (b[:, None] + 1)) & (stride * j[None, :] + size > bsz * b[:, None])
    score = jnp.where(overlaps[None], p[:, None, :], 0.0).max(-1)  # [G, NB]
    forced = (b < sp["init_blocks"]) | (bsz * (b + 1) > t - (sp["window_size"] - 1))
    score = jnp.where(forced, jnp.inf, score)
    score = jnp.where(bsz * b <= t, score, -1.0)  # a block after t does not exist
    # rank: how many blocks come before this one (higher score, or equal and a lower number)
    before = (score[:, None, :] > score[:, :, None]) | (
        (score[:, None, :] == score[:, :, None]) & (b[None, None, :] < b[None, :, None]))
    return (before.sum(-1) < sp["topk"]) & (bsz * b <= t)


def sparse_attention(q, k, v, sp):
    """q [T, G, R, D], k, v [T, G, D] -> (o [T, G, R, D], keep [T, G,
    NB]): every query under its own mask, QUERIES queries at a time."""
    T, D = q.shape[0], q.shape[-1]
    size, stride, bsz = sp["kernel_size"], sp["kernel_stride"], sp["block_size"]
    NW = max((T - size) // stride + 1, 1)
    windows = stride * jnp.arange(NW)[:, None] + jnp.arange(size)[None, :]
    c = k[windows].mean(1) if T >= size else jnp.zeros((1, *k.shape[1:]), F32)
    sp = dict(sp, n_blocks=-(-T // bsz))
    pos = jnp.arange(T)

    def rows(xs):
        qb, tb = xs  # [QUERIES, G, R, D], [QUERIES]
        keep = jax.vmap(lambda qt, t: keep_blocks(qt, c, t, sp))(qb, tb)  # [QUERIES, G, NB]
        sparse = jnp.repeat(keep, bsz, axis=-1)[..., :T]
        mask = jnp.where((tb < sp["dense_len"])[:, None, None], True, sparse) & (
            pos[None, None, :] <= tb[:, None, None])
        s = jnp.einsum("tgrd,kgd->tgrk", qb, k) / jnp.sqrt(F32(D))
        s = jnp.where(mask[:, :, None, :], s, -jnp.inf)
        return jnp.einsum("tgrk,kgd->tgrd", jax.nn.softmax(s, axis=-1), v), keep

    pad = -T % QUERIES
    qp = jnp.concatenate([q, jnp.zeros((pad, *q.shape[1:]), F32)])
    o, keep = jax.lax.map(rows, (qp.reshape(-1, QUERIES, *q.shape[1:]), jnp.arange(T + pad).reshape(-1, QUERIES)))
    return o.reshape(-1, *o.shape[2:])[:T], keep.reshape(-1, *keep.shape[2:])[:T]


@functools.partial(jax.jit, static_argnames=("kind", "cfg"))
def layer(x, lp, *, kind, cfg):
    """One layer on x [T, d] float32 -> (x, the sparse layer's kept
    blocks [T, G, NB] or None).  `cfg`: a tuple of (name, value) pairs."""
    c = dict(cfg)
    eps, T = c["rms_norm_eps"], x.shape[0]
    r = c["scale_depth"] / (c["published_layers"] ** 0.5)
    w = lp["wqkvz"].astype(F32)
    proj = by_rows(lambda xb: rmsnorm(xb, lp["w_in"], eps) @ w, x)
    keep = None
    if kind == "lightning-attn":
        H, D = c["lightning_nh"], c["lightning_head_dim"]
        q, k, v, z = (t.reshape(T, H, D) for t in jnp.split(proj, 4, axis=-1))
        q, k = rope(rmsnorm(q, lp["w_qn"], eps), c["rope_theta"]), rope(rmsnorm(k, lp["w_kn"], eps), c["rope_theta"])
        o = rmsnorm(lightning(q, k, v, H), lp["w_on"], eps)
    else:
        H, G, D = c["n_head"], c["n_kv_head"], c["head_dim"]
        q, k, v, z = jnp.split(proj, [H * D, (H + G) * D, (H + 2 * G) * D], axis=-1)
        q = rmsnorm(q.reshape(T, G, H // G, D), lp["w_qn"], eps)
        k = rmsnorm(k.reshape(T, G, D), lp["w_kn"], eps)
        o, keep = sparse_attention(q, k, v.reshape(T, G, D), c)
    o = o.reshape(T, -1) * jax.nn.sigmoid(z.reshape(T, -1))
    wo = lp["wo"].astype(F32)
    x = x + r * by_rows(lambda ob: ob @ wo, o)
    wgu, wd = lp["wgu"].astype(F32), lp["wd"].astype(F32)

    def feed_forward(xb):
        gate, up = jnp.split(rmsnorm(xb, lp["w_post"], eps) @ wgu, 2, axis=-1)
        return (jax.nn.silu(gate) * up) @ wd

    return x + r * by_rows(feed_forward, x), keep


_KEYS = ("rms_norm_eps", "scale_depth", "published_layers", "lightning_nh", "lightning_head_dim",
         "n_head", "n_kv_head", "head_dim", "rope_theta", "kernel_size", "kernel_stride", "block_size",
         "init_blocks", "window_size", "topk", "dense_len")


def full_logits(params, tokens, cfg, positions=None):
    """tokens [T] of ONE sequence -> (logits [len(positions), vocab]
    float32 at `positions` (all of them when None), keep: each sparse
    layer's kept blocks [Lp, T, G, NB] bool).  `cfg` gives the
    attributes named in _KEYS, ``mixer_types``, ``scale_emb``,
    ``d_model`` and ``dim_model_base``."""
    sizes = tuple((k, getattr(cfg, k)) for k in _KEYS)
    # on the TPU a float32 matmul runs as bf16 passes unless told otherwise
    with jax.default_matmul_precision("highest"):
        x = cfg.scale_emb * params["embed"][tokens].astype(F32)
        kept = []
        for kind, lp in zip(cfg.mixer_types, params["layers"]):
            x, keep = layer(x, lp, kind=kind, cfg=sizes)
            if keep is not None:
                kept.append(keep)
        if positions is not None:
            x = x[jnp.asarray(positions)]
        return _head(x, params["norm"], params["lm_head"], eps=float(cfg.rms_norm_eps),
                     divisor=cfg.d_model / cfg.dim_model_base), jnp.stack(kept)


@functools.partial(jax.jit, static_argnames=("eps", "divisor"))
def _head(x, norm, lm_head, *, eps, divisor):
    # under jit the head's cast to float32 fuses into the matmul: 1.2 GB is never made
    return rmsnorm(x, norm, eps) @ lm_head.astype(F32) / divisor

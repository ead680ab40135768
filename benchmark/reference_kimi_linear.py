"""The plain reference of the Kimi-Linear family (``model_type:
kimi_linear``): forward pass in float32 ``jax.numpy`` at ``highest``
matmul precision, the delta rule as its RECURRENCE (one position after
another, no chunked form, no triangular system), the latent attention in
its NON-absorbed form (keys and values expanded for every head from the
latent, one softmax a query), the experts the dense way (every held
expert's output for every token times the token's weight for it, zero
where the expert is not among its 8).  No kernel, no cache, no chunks,
no pages, no grouped matmul, and nothing imported from the program.  It
reads the program's parameter tree (``embed``, ``layers`` of ``w_in,
w_post`` with ``wqkv, conv_q, conv_k, conv_v, wf_down, wf_up, A_log,
dt_bias, w_beta, wg_down, wg_up, w_on, wo`` or ``wq, wdkv, w_kvn, wukv,
wo``, and ``wgu_dense, wd_dense`` or ``router, router_bias, wgu_shared,
wd_shared, wgu, wd``; ``norm``, ``lm_head``): that tree is the
interface.

The model (moonshotai/Kimi-Linear-48B-A3B-Instruct ``config.json``; keys
in brackets), a layer on the residual stream x of one sequence,
``rmsnorm(x, w) = w x rsqrt(mean(x^2) + 1e-5)`` [rms_norm_eps]:

    h  = rmsnorm(x, w_in)
    a KDA layer [linear_attn_config.kda_layers; num_heads 32, head_dim 128]:
    q  = l2norm(silu(conv4(h Wq)))  k alike  v = silu(conv4(h Wv))  [short_conv_kernel_size 4]
         conv4: y_t = sum_j w[:, j] x_{t-3+j}, depthwise, zeros before position 0
    a  = -exp(A_log[head]) softplus((h Wf1) Wf2 + dt_bias)   [32, 128]: a log-decay a CHANNEL
    b  = sigmoid(h Wbeta)                                     [32]
    S' = Diag(exp(a_t)) S_{t-1};  S_t = S' + b_t k_t (v_t - S'^T k_t)^T;  o_t = S_t^T q_t 128^-0.5
    x  = x + Wo [rmsnorm_head(o_t, w_on) * sigmoid((h Wg1) Wg2)]
    an MLA layer [linear_attn_config.full_attn_layers; q_lora_rank null]:
    q  = h Wq: 32 heads of [q_nope 128 | q_pe 64]     [qk_nope_head_dim, qk_rope_head_dim]
    [c | k_pe] = h Wdkv;  c = rmsnorm(c, w_kvn)       [kv_lora_rank 512 | 64]
    [k_nope 128 | v 128] of head i = c Wukv[i]        [v_head_dim]
    score(t, s<=t) = (q_nope.k_nope + q_pe.k_pe) 192^-0.5     [mla_use_nope: NOTHING is rotated]
    x  = x + (softmax of score, times v, heads side by side) Wo
    h2 = rmsnorm(x, w_post)
    the first layer [first_k_dense_replace 1]:  x = x + SwiGLU(h2), width 9,216 [intermediate_size]
    an expert layer:
    s  = sigmoid(h2 Wr) over all 256                  [num_experts, moe_router_activation_func]
    the 8 largest of s + b                            [num_experts_per_token; one group]
    g_e = 2.446 s_e / sum of the chosen s             [moe_renormalize, routed_scaling_factor]
    x  = x + SwiGLU_shared(h2) + sum over those of the 8 that are HELD of g_e SwiGLU_e(h2)
         SwiGLU(h) = (silu(h Wg) * (h Wu)) Wd, width 1024 [moe_intermediate_size, num_shared_experts 1]

then rmsnorm and the untied head over the rows of the vocabulary held.

The SHARE (``first``, ``count`` of the routed experts; the rows of the
vocabulary the tree holds): the router scores all 256 experts and keeps
8 a token; of those, the experts ``first .. first + count - 1`` alone
are in the tree and add their part; the others add nothing, here as in
the program.  Which mixer a layer has and whether it is dense is read
off the tree (a layer with ``wqkv``; one with ``wgu_dense``).

DEPARTURES from the published description, each ASSUMED because the
catalog's row of the source does not carry it
(``benchmark/configs/kimi-linear-48b-a3b.json`` lists the same): bf16
parameters, seeded random (normal 0.02, norm weights 1, convolutions
uniform, ``A_log = log U(1, 16)``, ``dt_bias`` the inverse softplus of a
log-uniform step); the low-rank maps' inner width 128; convolutions
without bias; the state float32; ``b`` (``e_score_correction_bias``)
seeded normal std 0.02, so that it does choose; ``num_expert_group`` and
``topk_group`` 1 mean no group limit.

``wrong`` makes one of the builder's wrong-on-purpose readings, each a
different MODEL and not a rounding: "no_correction" (``S_t = S' + b k
v^T``, the delta rule's correction left out), "head_decay" (one decay a
head, the mean of a channel's, where the model has one a channel),
"bf16_state" (the state rounded to bfloat16 after every position) and
"rotated" (``q_pe`` and ``k_pe`` rotated at their positions, as every
other latent family here does).

The weights stay in the program's dtype; slices of them are cast to
float32 as they are used: the projections and the feed-forward go a
block of ``ROWS`` positions at a time, the attention ``QUERIES`` queries
and ``HEADS`` heads at a time, so that the reference of a 9k-token
sequence fits beside the engine's weights and cache on the chip.  Only a
process that holds the chip (or a CPU rehearsal) imports this.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROWS = 1024  # positions a block of the projections and the feed-forward
QUERIES = 128  # queries a block of attention
HEADS = 8  # heads whose keys and values are expanded at once
COLUMNS = 2304  # columns of the dense layer's width taken at once
WRONG = (None, "no_correction", "head_decay", "bf16_state", "rotated")


def rmsnorm(x, w, eps):
    return w.astype(F32) * x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)


def by_rows(f, x, rows=ROWS):
    """f over x [T, ...] a block of ``rows`` positions at a time."""
    T = x.shape[0]
    pad = -T % rows
    xp = jnp.concatenate([x, jnp.zeros((pad, *x.shape[1:]), x.dtype)]) if pad else x
    out = jax.lax.map(f, xp.reshape(-1, rows, *x.shape[1:]))
    return jax.tree.map(lambda o: o.reshape(-1, *o.shape[2:])[:T], out)


def conv4(x, w):
    """x [T, C] -> ``silu(sum_j w[:, j] x_{t-K+1+j})``, zeros before position 0."""
    T, K = x.shape[0], w.shape[1]
    xp = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), F32), x])
    return jax.nn.silu(sum(w[:, j].astype(F32) * xp[j:j + T] for j in range(K)))


def l2norm(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, a, beta, wrong=None):
    """The recurrence, a position at a time: q, k, a [T, H, dk], v [T, H,
    dv], beta [T, H] -> (o [T, H, dv], the state after the last position
    [H, dk, dv])."""
    T, H, dk = q.shape
    if wrong == "head_decay":
        a = jnp.broadcast_to(a.mean(-1, keepdims=True), a.shape)

    def step(S, xs):
        qt, kt, vt, at, bt = xs
        S = jnp.exp(at)[..., None] * S
        r = 0.0 if wrong == "no_correction" else (kt[..., None] * S).sum(-2)
        S = S + kt[..., None] * (bt[:, None] * (vt - r))[:, None, :]
        if wrong == "bf16_state":
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return S, (qt[..., None] * S).sum(-2) * dk ** -0.5

    state, o = jax.lax.scan(step, jnp.zeros((H, dk, v.shape[-1]), F32), (q, k, v, a, beta))
    return o, state


def kda(x, lp, c, wrong):
    """What a KDA layer's mixer adds to x [T, d], and its state after
    the last position [H, dk, dv]."""
    T, H, dk = x.shape[0], c["kda_num_heads"], c["kda_head_dim"]
    wqkv, wf1, wf2 = lp["wqkv"].astype(F32), lp["wf_down"].astype(F32), lp["wf_up"].astype(F32)
    wb, wg1, wg2 = lp["w_beta"].astype(F32), lp["wg_down"].astype(F32), lp["wg_up"].astype(F32)

    def project(xb):
        h = rmsnorm(xb, lp["w_in"], c["rms_norm_eps"])
        f = jax.nn.softplus((h @ wf1) @ wf2 + lp["dt_bias"].astype(F32))
        return h @ wqkv, f, jax.nn.sigmoid(h @ wb), jax.nn.sigmoid((h @ wg1) @ wg2)

    qkv, f, beta, gate = by_rows(project, x)
    q, k, v = (conv4(t, lp["conv_" + n]).reshape(T, H, dk) for n, t in zip("qkv", jnp.split(qkv, 3, axis=-1)))
    a = -jnp.exp(lp["A_log"].astype(F32))[:, None] * f.reshape(T, H, dk)
    o, state = delta_rule(l2norm(q), l2norm(k), v, a, beta, wrong)
    o = rmsnorm(o, lp["w_on"], c["rms_norm_eps"]).reshape(T, H * dk) * gate
    return by_rows(lambda ob: ob @ lp["wo"].astype(F32), o), state


def rotate(x, theta=10000.0):
    """x [T, ..., D] at positions 0..T-1 over pairs (2i, 2i + 1): what
    this model does NOT do (``wrong`` "rotated")."""
    T, d = x.shape[0], x.shape[-1]
    f = theta ** (-2.0 * jnp.arange(d // 2, dtype=F32) / d)
    ang = (jnp.arange(T, dtype=F32)[:, None] * f[None, :]).reshape(T, *([1] * (x.ndim - 2)), -1)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def mla(x, lp, c, wrong):
    """What an MLA layer's mixer adds to x [T, d]: ``HEADS`` heads at a
    time, one group after another (a scan: no two groups' keys and values
    are alive at once), their queries, keys and values expanded, one
    causal softmax a query, and their rows of Wo."""
    T, H = x.shape[0], c["n_head"]
    nope, pe, dv, kv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"]
    scale = (nope + pe) ** -0.5
    wdkv = lp["wdkv"].astype(F32)

    def project(xb):
        h = rmsnorm(xb, lp["w_in"], c["rms_norm_eps"])
        ckp = h @ wdkv
        return h, rmsnorm(ckp[:, :kv], lp["w_kvn"], c["rms_norm_eps"]), ckp[:, kv:]

    h, c_lat, k_pe = by_rows(project, x)
    if wrong == "rotated":
        k_pe = rotate(k_pe)
    pad = -T % QUERIES
    groups = H // HEADS
    wq = lp["wq"].reshape(-1, groups, HEADS, nope + pe).transpose(1, 0, 2, 3)
    wukv = lp["wukv"].reshape(-1, groups, HEADS, nope + dv).transpose(1, 0, 2, 3)
    wo = lp["wo"].reshape(groups, HEADS, dv, -1)
    pos = jnp.arange(T)

    def group(out, ws):
        wq_g, wukv_g, wo_g = (w.astype(F32) for w in ws)
        q = by_rows(lambda hb: jnp.einsum("tc,chd->thd", hb, wq_g), h)
        if wrong == "rotated":
            q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:])], axis=-1)
        knv = by_rows(lambda cb: jnp.einsum("tc,chd->thd", cb, wukv_g), c_lat)
        k = jnp.concatenate([knv[..., :nope], jnp.broadcast_to(k_pe[:, None, :], (T, HEADS, pe))], axis=-1)
        v = knv[..., nope:]

        def rows(xs):
            qb, tb = xs
            s = jnp.einsum("thd,khd->htk", qb, k) * scale
            s = jnp.where((pos[None, :] <= tb[:, None])[None], s, -jnp.inf)
            return jnp.einsum("htk,khd->thd", jax.nn.softmax(s, axis=-1), v)

        qp = jnp.concatenate([q, jnp.zeros((pad, *q.shape[1:]), F32)]).reshape(-1, QUERIES, HEADS, nope + pe)
        o = jax.lax.map(rows, (qp, jnp.arange(T + pad).reshape(-1, QUERIES))).reshape(T + pad, HEADS, dv)[:T]
        return out + by_rows(lambda ob: jnp.einsum("thd,hdm->tm", ob, wo_g), o), None

    out, _ = jax.lax.scan(group, jnp.zeros((T, lp["wo"].shape[1]), F32), (wq, wukv, wo))
    return out, None  # an MLA layer holds no state


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
    """What the dense layer's SwiGLU adds to x [N, d], ``COLUMNS`` of its
    width at a time; and -1 where an expert layer names experts."""
    h2 = rmsnorm(x, lp["w_post"], c["rms_norm_eps"])
    width = lp["wd_dense"].shape[0]
    y = jnp.zeros_like(x)
    for lo in range(0, width, COLUMNS):
        hi = min(lo + COLUMNS, width)
        gate = h2 @ lp["wgu_dense"][:, lo:hi].astype(F32)
        up = h2 @ lp["wgu_dense"][:, width + lo:width + hi].astype(F32)
        y = y + (jax.nn.silu(gate) * up) @ lp["wd_dense"][lo:hi].astype(F32)
    return y, jnp.full((x.shape[0], c["num_experts_per_tok"]), -1, jnp.int32)


@functools.partial(jax.jit, static_argnames=("cfg", "wrong"))
def layer(x, lp, *, cfg, wrong=None):
    """One block on x [T, d] float32 -> (x, the experts each token chose
    [T, k], -1 in a dense layer; a KDA layer's state after the last
    position, None of an MLA layer).  `cfg`: a tuple of (name, value)
    pairs."""
    c = dict(cfg)
    out, state = (kda if "wqkv" in lp else mla)(x, lp, c, wrong)
    x = x + out
    half = dense_half if "wgu_dense" in lp else expert_half
    y, top_e = by_rows(lambda xb: half(xb, lp, c), x)
    return x + y, top_e, state


_KEYS = ("rms_norm_eps", "n_head", "kda_num_heads", "kda_head_dim", "qk_nope_head_dim", "qk_rope_head_dim",
         "kv_lora_rank", "v_head_dim", "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
         "experts_first")


def full_logits(params, tokens, cfg, positions=None, wrong=None):
    """tokens [T] of ONE sequence -> (logits [len(positions), rows held]
    float32 at `positions` (all of them when None), the experts every
    token chose in every layer [L, T, k], -1 in a dense layer, the KDA
    layers' states after the last token, [H, dk, dv] each, in the layers'
    order).  `cfg` gives the attributes named in _KEYS; the experts held
    are ``cfg.experts_first`` on, as many as the tree holds."""
    if wrong not in WRONG:
        raise ValueError(f"no wrong-on-purpose reading named {wrong!r} (one of {WRONG[1:]})")
    sizes = tuple((k, getattr(cfg, k)) for k in _KEYS)
    # on the TPU a float32 matmul runs as bf16 passes unless told otherwise
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        chose, states = [], []
        for lp in params["layers"]:
            x, top_e, state = layer(x, lp, cfg=sizes, wrong=wrong)
            chose.append(top_e)
            if state is not None:
                states.append(state)
        if positions is not None:
            x = x[jnp.asarray(positions)]
        return _head(x, params["norm"], params["lm_head"], eps=float(cfg.rms_norm_eps)), jnp.stack(chose), states


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, lm_head, *, eps):
    # under jit the head's cast to float32 fuses into the matmul
    return rmsnorm(x, norm, eps) @ lm_head.astype(F32)

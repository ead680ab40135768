"""The layers the served families share (``models/layers.py`` and the
array math behind them in ``ops/attention.py`` and ``ops/mamba2.py``),
each on its own: CPU, tiny shapes.  The families' own files check them
again through whole engines."""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import layers  # noqa: E402
from ray_tpu.ops import attention, block_sparse, dsa, mla  # noqa: E402

BLOCK = 16  # positions a page


def _distance(a, b):
    a, b = np.asarray(a, np.float32).ravel(), np.asarray(b, np.float32).ravel()
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-9))


@dataclasses.dataclass(frozen=True)
class Cfg:
    """What the shared layers read of a family's config."""

    d_model: int = 32
    n_head: int = 4
    n_kv_head: int = 2
    head_dim: int = 8
    mamba_num_heads: int = 4
    mamba_head_dim: int = 8
    ssm_state_size: int = 8
    n_groups: int = 2
    chunk_size: int = 8
    layer_norm_epsilon: float = 1e-5

    @property
    def d_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size


CFG = Cfg()


# ----------------------------------------------------------------------
# (a) the chunk's online softmax against one dense softmax a query
# ----------------------------------------------------------------------
K = attention.K_BLOCK


@pytest.mark.parametrize("R", [1, 4, 20])
@pytest.mark.parametrize("start, T, n_valid", [
    (0, 8, 8),  # a prompt's first chunk
    (37, 8, 8),  # a start inside a key block
    (K - 5, 16, 16),  # a chunk that crosses a key block's edge
    (K + 3, 24, 9),  # pads behind the real tokens
    (0, 2 * attention.Q_BLOCK, attention.Q_BLOCK + 7),  # two query blocks, the second mostly pads
])
def test_chunk_attention_is_the_dense_causal_softmax(start, T, n_valid, R):
    """``chunk_attention`` (blocks of 512 keys inside an online softmax,
    blocks past a query block's last real position not visited) against
    one float32 softmax a query over the positions up to its own, for R
    queries a K/V head of 1, 4 (Granite) and 20 (Jamba).  What lies
    behind the last real position in the context is noise: it must not
    be read."""
    rng = np.random.default_rng(start * 100 + T + R)
    G, hd = 2, 8
    q = jnp.asarray(rng.normal(size=(T, G, R, hd)), jnp.float32)
    C = -(-(start + T) // K) * K
    ctx_k = rng.normal(size=(C, G, hd)).astype(np.float32)
    ctx_v = rng.normal(size=(C, G, hd)).astype(np.float32)
    ctx_k[start + n_valid:] *= 50
    ctx_v[start + n_valid:] *= 50
    got = np.asarray(jax.jit(attention.chunk_attention)(
        q, jnp.asarray(ctx_k), jnp.asarray(ctx_v), jnp.int32(start), jnp.int32(n_valid)))
    assert got.shape == (T, G * R * hd)
    for t in range(n_valid):
        keys, vals = ctx_k[:start + t + 1], ctx_v[:start + t + 1]
        s = np.einsum("grd,kgd->grk", np.asarray(q[t]), keys) / np.sqrt(hd)
        p = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("grk,kgd->grd", p / p.sum(-1, keepdims=True), vals).reshape(-1)
        assert _distance(got[t], want) < 1e-5, t


def test_chunk_attention_takes_a_scale():
    """Granite's ``attention_multiplier``: the scores times ``scale``,
    not ``hd ** -0.5``."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(8, 2, 4, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(K, 2, 8)), jnp.float32) for _ in range(2))
    plain = attention.chunk_attention(q, k, v, jnp.int32(0), jnp.int32(8))
    assert _distance(attention.chunk_attention(q, k, v, jnp.int32(0), jnp.int32(8), scale=8 ** -0.5), plain) < 1e-6
    assert _distance(attention.chunk_attention(q * 2, k, v, jnp.int32(0), jnp.int32(8), scale=0.5 * 8 ** -0.5),
                     plain) < 1e-6


# ----------------------------------------------------------------------
# (b) a chunk's context
# ----------------------------------------------------------------------
@pytest.mark.parametrize("row_shape", [(24,), (2, 12)], ids=["flat", "heads"])
@pytest.mark.parametrize("key_block", [attention.K_BLOCK, mla.K_BLOCK, block_sparse.K_BLOCK, dsa.KEY_BLOCK],
                         ids=["gqa-512", "mla-512", "block-sparse-1024", "dsa-2048"])
def test_a_chunks_context_is_the_pages_in_order_then_room_with_the_chunk_at_start(key_block, row_shape):
    """``chunk_slots`` and ``chunk_context`` at each owner's key block:
    the cached rows are the table's, in page order, whatever the pages'
    order in the pool; the chunk's rows land at ``start``; what is
    behind them is zeros; the length is whole key blocks and holds the
    chunk wherever it starts (a flat row as the latent families cache
    it, and a row of heads as the grouped-query layer takes it)."""
    rng = np.random.default_rng(key_block)
    L, pages_held, T, D = 3, 5, 40, 24
    n_pages = 12
    pool = jnp.asarray(rng.normal(size=(L, n_pages * BLOCK, D)), jnp.float32)
    table = jnp.asarray([7, 2, 9, 0, 0], jnp.int32)  # three pages held, then the scratch page
    C = pages_held * BLOCK
    where, room = layers.chunk_slots(table, BLOCK, T, key_block)
    assert where.shape == (C,) and (C + room) % key_block == 0 and room >= T and room - T < key_block
    assert list(np.asarray(where[:BLOCK])) == list(range(7 * BLOCK, 8 * BLOCK))
    assert list(np.asarray(where[BLOCK:2 * BLOCK])) == list(range(2 * BLOCK, 3 * BLOCK))
    rows = jnp.asarray(rng.normal(size=(T, *row_shape)), jnp.float32)
    for layer, start in ((0, 0), (2, 37), (1, C)):  # the last: a chunk wholly behind the pages, which fits too
        ctx = np.asarray(jax.jit(layers.chunk_context, static_argnums=(1, 3))(
            pool, layer, where, room, rows, jnp.int32(start)))
        assert ctx.shape == (C + room, *row_shape)
        want = np.concatenate([np.asarray(pool[layer])[np.asarray(where)].reshape(C, *row_shape),
                               np.zeros((room, *row_shape), np.float32)])
        want[start:start + T] = np.asarray(rows)
        np.testing.assert_array_equal(ctx, want)
        assert not ctx[max(C, start + T):].any()


# ----------------------------------------------------------------------
# (c), (d) a chunk, then a decode step, is the longer chunk
# ----------------------------------------------------------------------
def _cache(n_tokens, lanes=2):
    """A pool of two paged layers holding nothing yet, a table of pages
    out of order, and lane state of two Mamba layers holding noise (a
    chunk at position 0 must read it as zeros)."""
    rng = np.random.default_rng(5)
    pages = -(-n_tokens // BLOCK)
    table = jnp.asarray(rng.permutation(np.arange(1, 2 * pages + 1))[:pages], jnp.int32)
    width = CFG.n_kv_head * CFG.head_dim
    cache = {"k_pages": jnp.zeros((2, (2 * pages + 1) * BLOCK, width), jnp.float32),
             "v_pages": jnp.zeros((2, (2 * pages + 1) * BLOCK, width), jnp.float32)}
    for i in range(2):
        cache[layers.tail_name(i)] = jnp.asarray(rng.normal(size=(lanes, 3 * CFG.conv_dim)), jnp.float32)
        cache[layers.state_name(i)] = jnp.asarray(rng.normal(size=(
            lanes, CFG.mamba_num_heads, CFG.mamba_head_dim, CFG.ssm_state_size)), jnp.float32)
    return cache, table


def _written(cache, i, table, start, k, v):
    """The cache after the engine wrote a chunk's or a step's rows k, v
    [n, G, hd] of layer i at positions ``start ..``."""
    pos = start + np.arange(k.shape[0])
    slots = np.asarray(table)[pos // BLOCK] * BLOCK + pos % BLOCK
    return {**cache, "k_pages": cache["k_pages"].at[i, slots].set(k.reshape(k.shape[0], -1)),
            "v_pages": cache["v_pages"].at[i, slots].set(v.reshape(v.shape[0], -1))}


def _attention_params():
    rng = np.random.default_rng(1)
    H, G, hd, d = CFG.n_head, CFG.n_kv_head, CFG.head_dim, CFG.d_model
    return {"wqkv": jnp.asarray(rng.normal(size=(d, (H + 2 * G) * hd)) * 0.2, jnp.float32),
            "wo": jnp.asarray(rng.normal(size=(H * hd, d)) * 0.2, jnp.float32)}


@pytest.mark.parametrize("n, scale", [(8, None), (29, None), (BLOCK, 0.05), (2 * BLOCK + 5, None)])
def test_attention_chunk_then_attention_decode_is_the_longer_chunk(n, scale):
    """The grouped-query layer over n + 1 tokens as one chunk, against n
    tokens as chunks (the second from where the first ended, reading the
    first's rows through the table) and token n as a decode step over
    the pages (the gather path, as on any CPU): the same outputs and the
    same rows to cache, for a prompt inside a page, one that ends at a
    page's edge, and with a scale of the family's own."""
    lp, layer = _attention_params(), 1
    y = jnp.asarray(np.random.default_rng(n).normal(size=(n + 1, CFG.d_model)), jnp.float32)
    cache, table = _cache(n + 1)

    @jax.jit
    def chunk(cache, rows, start, n_valid):
        where, room = layers.chunk_slots(table, BLOCK, rows.shape[0], attention.K_BLOCK)
        return layers.attention_chunk(rows, lp, CFG, cache, layer, where, room, start, n_valid, scale)

    whole, k_all, v_all = chunk(cache, y, 0, n + 1)
    # n tokens in two chunks, the second padded to the first's length
    first = -(-n // 2)
    out_a, k_a, v_a = chunk(cache, y[:first], 0, first)
    cache = _written(cache, layer, table, 0, k_a, v_a)
    rest = jnp.concatenate([y[first:n], jnp.zeros((2 * first - n, CFG.d_model), jnp.float32)])
    out_b, k_b, v_b = chunk(cache, rest, first, n - first)
    cache = _written(cache, layer, table, first, k_b[:n - first], v_b[:n - first])
    assert _distance(jnp.concatenate([out_a, out_b[:n - first]]), whole[:n]) < 1e-5
    assert _distance(jnp.concatenate([k_a, k_b[:n - first]]), k_all[:n]) < 1e-6
    # token n as a decode step of lane 1 of two; lane 0 does not run
    tables = jnp.stack([jnp.zeros_like(table), table])
    out, k, v = jax.jit(lambda rows, cache, lengths: layers.attention_decode(
        rows, lp, CFG, cache, layer, tables, lengths, BLOCK, scale))(
            jnp.stack([y[n] * 0, y[n]]), cache, jnp.asarray([0, n], jnp.int32))
    assert _distance(out[1], whole[n]) < 1e-5
    assert _distance(k[1], k_all[n]) < 1e-6 and _distance(v[1], v_all[n]) < 1e-6


def _mamba_params():
    rng = np.random.default_rng(2)
    d, Hm, inner, conv = CFG.d_model, CFG.mamba_num_heads, CFG.d_inner, CFG.conv_dim
    return {"in_proj": jnp.asarray(rng.normal(size=(d, inner + conv + Hm)) * 0.3, jnp.float32),
            "conv_w": jnp.asarray(rng.uniform(-0.5, 0.5, size=(conv, 4)), jnp.float32),
            "conv_b": jnp.asarray(rng.uniform(-0.5, 0.5, size=(conv,)), jnp.float32),
            "A_log": jnp.asarray(np.log(rng.uniform(1, 16, size=(Hm,))), jnp.float32),
            "D": jnp.ones((Hm,), jnp.float32),
            "dt_bias": jnp.asarray(rng.uniform(-4, -1, size=(Hm,)), jnp.float32),
            "w_gn": jnp.asarray(rng.uniform(0.5, 1.5, size=(inner,)), jnp.float32),
            "out_proj": jnp.asarray(rng.normal(size=(inner, d)) * 0.3, jnp.float32)}


@pytest.mark.parametrize("n, chunk", [(16, 16), (16, 8), (21, 16), (5, 8), (27, 8)])
def test_mamba_chunk_then_mamba_decode_is_the_longer_chunk(n, chunk):
    """The Mamba-2 layer over n + 1 tokens as one chunk from nothing,
    against n tokens as chunks of ``chunk`` (whole scan blocks of 8; the
    last padded where n is not whole chunks, ``n_valid < T``, each from
    the lane's tail and state as the one before left them) and token n
    as a decode step: the same outputs, and the same tail and state left
    in the lane.  Lane 0 holds another sequence's state, which neither
    the chunks nor the step may touch."""
    lp, i, lane = _mamba_params(), 1, 1
    y = jnp.asarray(np.random.default_rng(n).normal(size=(n + 1, CFG.d_model)), jnp.float32)
    cache, _ = _cache(n + 1)
    padded = -(-(n + 1) // CFG.chunk_size) * CFG.chunk_size
    mamba_chunk = jax.jit(lambda rows, cache, start, n_valid: layers.mamba_chunk(
        rows, lp, CFG, cache, i, lane, start, n_valid))
    whole, after = mamba_chunk(jnp.concatenate([y, jnp.zeros((padded - n - 1, CFG.d_model))]), cache, 0, n + 1)
    outs = []
    for start in range(0, n, chunk):
        real = min(chunk, n - start)
        rows = jnp.concatenate([y[start:start + real], jnp.zeros((chunk - real, CFG.d_model), jnp.float32)])
        out, left = mamba_chunk(rows, cache, start, real)
        outs.append(out[:real])
        cache = {**cache, **{name: cache[name].at[lane].set(value) for name, value in left.items()}}
    assert _distance(jnp.concatenate(outs), whole[:n]) < 2e-5
    other = {name: cache[name][0] for name in (layers.tail_name(i), layers.state_name(i))}
    out, left = jax.jit(lambda rows, cache, runs: layers.mamba_decode(rows, lp, CFG, cache, i, runs))(
        jnp.stack([y[n] * 0, y[n]]), cache, jnp.asarray([False, True]))
    assert _distance(out[1], whole[n]) < 2e-5
    for name in (layers.tail_name(i), layers.state_name(i)):
        assert _distance(left[name][1], after[name]) < 2e-5, name
    np.testing.assert_array_equal(left[layers.state_name(i)][0], other[layers.state_name(i)])


# ----------------------------------------------------------------------
# what a forward counted
# ----------------------------------------------------------------------
NAMES = ("moe_pairs_routed", "moe_pairs_held", "moe_pairs", "moe_experts_hit", "moe_expert_slots", "moe_peak_rows",
         "moe_layer_programs", "kv_positions_attended", "moe_pairs_skipped", "kv_blocks_walked", "kv_blocks_whole")


def test_counters_are_the_names_in_order_summed_over_the_expert_layers():
    per_layer = [jnp.asarray([10, 8, 7, 3, 4], jnp.int32), jnp.asarray([10, 6, 6, 2, 5], jnp.int32)]
    got = layers.counters(NAMES, per_layer, 16, kv_positions_attended=jnp.int32(99),
                          kv_blocks_walked=jnp.asarray([5, 4], jnp.int32))
    assert got.dtype == jnp.int32
    assert dict(zip(NAMES, got.tolist())) == {
        "moe_pairs_routed": 20, "moe_pairs_held": 14, "moe_pairs": 13, "moe_experts_hit": 5, "moe_expert_slots": 32,
        "moe_peak_rows": 9, "moe_layer_programs": 2, "kv_positions_attended": 99, "moe_pairs_skipped": 0,
        "kv_blocks_walked": 5, "kv_blocks_whole": 4}
    # a sixth count a layer is the pairs that chose no expert; a family without experts gives none
    six = [jnp.asarray([10, 8, 7, 3, 4, 2], jnp.int32)]
    assert layers.counters(NAMES, six, 16).tolist() == [10, 8, 7, 3, 16, 4, 1, 0, 2, 0, 0]
    assert layers.counters(NAMES[7:], kv_blocks_walked=(0, 0)).tolist() == [0, 0, 0, 0]
    with pytest.raises(KeyError, match="kv_positions_attnded"):
        layers.counters(NAMES, per_layer, 16, kv_positions_attnded=1)

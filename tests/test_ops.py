"""Numerics tests: ring attention and pallas flash attention vs the XLA
reference implementation, on a virtual 8-device CPU mesh."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops.attention import reference_causal_attention  # noqa: E402


def _rand_qkv(B=2, T=128, H=4, D=16, dtype=jnp.float32, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k1, (B, T, H, D), dtype)
    k = jax.random.normal(k2, (B, T, H, D), dtype)
    v = jax.random.normal(k3, (B, T, H, D), dtype)
    return q, k, v


def test_reference_attention_is_causal():
    q, k, v = _rand_qkv()
    out1 = reference_causal_attention(q, k, v)
    # Perturb the future: outputs at earlier positions must not change.
    k2 = k.at[:, 64:].set(0.0)
    v2 = v.at[:, 64:].set(0.0)
    out2 = reference_causal_attention(q, k2, v2)
    np.testing.assert_allclose(out1[:, :64], out2[:, :64], rtol=1e-5, atol=1e-5)


def test_ring_attention_matches_reference():
    from ray_tpu.ops.ring_attention import ring_causal_attention
    from ray_tpu.parallel import create_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs >=4 virtual devices")
    mesh = create_mesh({"sp": 4})
    q, k, v = _rand_qkv(B=2, T=128, H=4, D=16)
    ref = reference_causal_attention(q, k, v)
    out = jax.jit(lambda q, k, v: ring_causal_attention(q, k, v, mesh=mesh, axis="sp"))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_ring_attention_composes_with_dp():
    from ray_tpu.ops.ring_attention import ring_causal_attention
    from ray_tpu.parallel import create_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = create_mesh({"dp": 2, "sp": 4})
    q, k, v = _rand_qkv(B=4, T=64, H=2, D=8)
    ref = reference_causal_attention(q, k, v)
    out = jax.jit(lambda q, k, v: ring_causal_attention(q, k, v, mesh=mesh, axis="sp"))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


# The flash kernels against `reference_causal_attention` on float32
# upcasts at the highest matmul precision, forward and all three
# gradients (ROADMAP Queue 2 B4 (a): the backward's reference).  Tolerance
# by dtype: float32 inputs are computed in float32 throughout, and differ
# by the order of the sums alone (2e-5; 3e-6 seen).  bf16 inputs go to the
# MXU as they are and p/dS are rounded to bf16 for the second matmul, so a
# result of size 4-6 carries its own rounding (2^-8 relative) and that of
# its operands: 3e-2 + 2% (1.2e-2 seen).  A missing, shifted or transposed
# mask moves a result by 0.1-1.
_FLASH_TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5), jnp.bfloat16: dict(rtol=2e-2, atol=3e-2)}
# (block, chunk) as a function of T: one resident block walked in
# sub-tiles of 256 or of 128, and two blocks a side (K/V streamed, blocks
# under, on and above the diagonal) of sub-tiles of 128
_FLASH_TILES = {
    "resident-256": lambda T: (T, 256),
    "resident-128": lambda T: (T, 128),
    "streamed-128": lambda T: (T // 2, 128),
}


def _flash_cases(test):
    for name, values, ids in (
        ("tiles", list(_FLASH_TILES), None),
        ("D", [64, 128], None),
        ("T", [256, 512, 1024], None),
        ("dtype", [jnp.float32, jnp.bfloat16], ["f32", "bf16"]),
    ):
        test = pytest.mark.parametrize(name, values, ids=ids)(test)
    return test


def _flash_and_reference(flash, T, D, dtype, with_grads=True):
    """(kernel's, reference's) tuples of out [, dq, dk, dv]; `flash` is
    (q, k, v) -> out."""
    q, k, v = _rand_qkv(B=1, T=T, H=2, D=D, dtype=dtype, seed=T + D)
    g = jax.random.normal(jax.random.PRNGKey(7), q.shape, dtype)

    def run(fn, *xs):
        if not with_grads:
            return (fn(*xs[:3]),)
        out, vjp = jax.vjp(fn, *xs[:3])
        return (out,) + tuple(vjp(xs[3]))

    with jax.default_matmul_precision("highest"):
        want = run(reference_causal_attention, *(x.astype(jnp.float32) for x in (q, k, v, g)))
    return run(flash, q, k, v, g), want


def _tiled_flash(tiles, T):
    from ray_tpu.ops.pallas_attention import _flash

    block, chunk = _FLASH_TILES[tiles](T)
    return lambda q, k, v: _flash(q, k, v, True, block, chunk, True)


def _assert_close(got, want, dtype):
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == dtype
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b), err_msg=name,
                                   **_FLASH_TOL[dtype])


@_flash_cases
def test_pallas_flash_attention_interpret_matches_reference(dtype, T, D, tiles):
    _assert_close(*_flash_and_reference(_tiled_flash(tiles, T), T, D, dtype, with_grads=False),
                  dtype)


@_flash_cases
def test_pallas_flash_attention_grads_match_reference(dtype, T, D, tiles):
    _assert_close(*_flash_and_reference(_tiled_flash(tiles, T), T, D, dtype), dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_pallas_flash_attention_where_the_preferred_sub_tile_does_not_divide(dtype):
    """T = 384 is no multiple of 256: the public entry walks one block of
    three sub-tiles of 128."""
    from ray_tpu.ops.pallas_attention import flash_attention, flash_tiles

    assert flash_tiles(384, 64, dtype.dtype.itemsize) == (384, 128)
    flash = lambda q, k, v: flash_attention(q, k, v, interpret=True)  # noqa: E731
    _assert_close(*_flash_and_reference(flash, 384, 64, dtype), dtype)


def test_pallas_flash_attention_small_blocks_through_the_public_arguments():
    """`block_q`/`block_k` cap the block (and with it the sub-tile), which
    is all they mean."""
    from ray_tpu.ops.pallas_attention import flash_attention

    q, k, v = _rand_qkv(B=1, T=256, H=2, D=32)
    ref = reference_causal_attention(q, k, v)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("block", [256, 128], ids=["resident", "streamed"])
def test_pallas_flash_attention_without_the_mask(block):
    """causal=False: every block and every sub-tile is computed whole."""
    from ray_tpu.ops.pallas_attention import _flash

    q, k, v = _rand_qkv(B=1, T=256, H=2, D=64)
    g = jax.random.normal(jax.random.PRNGKey(7), q.shape)

    def plain(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / q.shape[-1] ** 0.5
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    out, vjp = jax.vjp(lambda q, k, v: _flash(q, k, v, False, block, 128, True), q, k, v)
    with jax.default_matmul_precision("highest"):
        ref, ref_vjp = jax.vjp(plain, q, k, v)
        want = (ref,) + tuple(ref_vjp(g))
    _assert_close((out,) + tuple(vjp(g)), want, jnp.float32)


@pytest.mark.parametrize("T, chunk, visited", [
    (1024, 256, (10, 16)), (1024, 128, (36, 64)), (1024, 512, (3, 4)), (256, 256, (1, 1)),
    (8192, 256, (528, 1024)),
])
def test_flash_visited_sub_tiles(T, chunk, visited):
    from ray_tpu.ops.pallas_attention import visited_sub_tiles

    assert visited_sub_tiles(T, chunk) == visited


@pytest.mark.parametrize("T, D, itemsize, tiles", [
    (1024, 64, 2, (1024, 256)),    # both train cells: a head resident, 10 of 16 sub-tiles
    (1024, 128, 2, (1024, 256)),   # llama's head size
    (256, 64, 2, (256, 256)),
    (8192, 128, 2, (1024, 256)),   # past one block: K/V streamed in blocks of 1024
    (768, 64, 2, (768, 256)),
    (1280, 64, 2, (256, 256)),     # the largest power-of-two fraction of 1024 that divides T
    (384, 64, 4, (384, 128)),
    (1024, 256, 4, (512, 256)),    # float32 at D = 256: a 1024 block is over the VMEM budget
])
def test_flash_tiles_come_from_the_shape(T, D, itemsize, tiles):
    from ray_tpu.ops.pallas_attention import _VMEM_BUDGET, _step_vmem_bytes, flash_tiles

    assert flash_tiles(T, D, itemsize) == tiles
    assert _step_vmem_bytes(*tiles, D, itemsize) <= _VMEM_BUDGET
    assert T % tiles[0] == 0 and tiles[0] % tiles[1] == 0



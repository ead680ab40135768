"""Partition-rule matching (train/sharding/rules.py): regex precedence,
unmatched-leaf typed error, scalar replication, mesh-divisibility
clipping, and the tested GPT-2 rule set."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from ray_tpu.train.sharding import (  # noqa: E402
    ShardingConfig,
    UnmatchedParamError,
    gpt2_partition_rules,
    match_partition_rules,
)


def _leaf(*shape):
    return jnp.zeros(shape, dtype=jnp.float32)


def test_first_match_wins_precedence():
    """Rules are ORDERED: an earlier, broader rule shadows a later,
    more specific one — precedence is the list order, not specificity."""
    params = {"attn": {"qkv": {"kernel": _leaf(8, 24)}}}
    spec = match_partition_rules(
        [(r"kernel", ("model", None)), (r"qkv/kernel", (None, "model"))], params
    )
    assert spec["attn"]["qkv"]["kernel"] == P("model", None)
    # Reversed order: the specific rule now wins.
    spec = match_partition_rules(
        [(r"qkv/kernel", (None, "model")), (r"kernel", ("model", None))], params
    )
    assert spec["attn"]["qkv"]["kernel"] == P(None, "model")


def test_unmatched_leaf_raises_typed_error_naming_all_gaps():
    params = {
        "a": {"kernel": _leaf(4, 4)},
        "b": {"mystery": _leaf(4, 4)},
        "c": {"enigma": _leaf(4,)},
    }
    with pytest.raises(UnmatchedParamError) as ei:
        match_partition_rules([(r"kernel", (None, "model"))], params)
    # One failure names EVERY gap, with paths.
    assert sorted(ei.value.paths) == ["b/mystery", "c/enigma"]
    assert "b/mystery" in str(ei.value)


def test_scalars_and_size_one_replicate_without_rules():
    params = {"count": _leaf(), "one": _leaf(1)}
    spec = match_partition_rules([], params)
    assert spec["count"] == P()
    assert spec["one"] == P()


def test_non_strict_replicates_unmatched():
    params = {"mystery": _leaf(4, 4)}
    spec = match_partition_rules([], params, strict=False)
    assert spec["mystery"] == P()


def test_spec_clipped_to_rank_and_mesh_divisibility():
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("batch", "model"))
    params = {
        "v": {"kernel": _leaf(6)},          # rank 1 < spec rank 2
        "odd": {"kernel": _leaf(7, 8)},     # 7 % 2 != 0 -> dim replicates
        "ghost": {"kernel": _leaf(8, 8)},   # axis not in mesh -> dropped
    }
    spec = match_partition_rules(
        [
            (r"v/kernel", (None, "model")),
            (r"odd/kernel", ("model", "model")),
            (r"ghost/kernel", ("expert", "model")),
        ],
        params,
        mesh,
    )
    assert spec["v"]["kernel"] == P(None)
    assert spec["odd"]["kernel"] == P(None, "model")
    assert spec["ghost"]["kernel"] == P(None, "model")


@pytest.mark.parametrize("on_mesh", [False, True], ids=["no_mesh", "batch2xmodel4"])
@pytest.mark.parametrize("preset", ["tiny", "medium", "large"])
def test_gpt2_rule_set_covers_and_shards_gpt2_tiny(preset, on_mesh):
    """The shipped rule set must cover EVERY gpt2 leaf (no
    UnmatchedParamError) and produce the Megatron pairing, for the tiny
    preset and for the two the train cells run (abstract trees); on a
    mesh whose model axis is 4 wide no dim of theirs is clipped back to
    replicated."""
    from ray_tpu.models import gpt2

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    cfg = getattr(gpt2.GPT2Config, preset)(remat=False)
    params = jax.eval_shape(lambda: gpt2.init_params(cfg))
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("batch", "model")) if on_mesh else None
    spec = match_partition_rules(gpt2_partition_rules(), params, mesh)
    assert spec["wte"]["embedding"] == P("model", None)
    assert spec["wpe"]["embedding"] == P(None, None)
    for i in range(cfg.n_layer):
        blk = spec[f"h_{i}"]
        # by rows: models/gpt2.py exchanges the weights, not q, k and v
        assert blk["attn"]["qkv"]["kernel"] == P("model", None)
        assert blk["attn"]["attn_out"]["kernel"] == P("model", None)
        assert blk["mlp"]["mlp_up"]["kernel"] == P(None, "model")
        assert blk["mlp"]["mlp_down"]["kernel"] == P("model", None)
        # norms/biases replicate (specs pad to rank: P(None) == replicated)
        assert all(a is None for a in blk["ln_1"]["scale"])
        assert all(a is None for a in blk["attn"]["qkv"]["bias"])
    assert spec["lm_head"]["kernel"] == P(None, "model")
    assert all(a is None for a in spec["ln_f"]["bias"])


def test_qkv_rule_stores_the_kernel_as_the_exchange_takes_it():
    """The layout of the fused kernel is decided twice: by the rule that
    stores it and by the `in_specs` of the shard_map that exchanges it
    (`models/gpt2.py:_qkv_by_head`).  Where the two part, the result is
    still right and the partitioner reshards the weights every step; so
    they are held to each other here."""
    from ray_tpu.models import gpt2
    from ray_tpu.ops.attention import mesh_split

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    cfg = gpt2.GPT2Config.tiny(remat=False)
    qkv = jax.eval_shape(lambda: gpt2.init_params(cfg))["h_0"]["attn"]["qkv"]
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("batch", "model"))
    stored = match_partition_rules(gpt2_partition_rules(), {"qkv": qkv}, mesh)["qkv"]
    x = jax.ShapeDtypeStruct((4, 16, cfg.d_model), cfg.dtype)

    def shard_maps(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "shard_map":
                yield eqn
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):
                    yield from shard_maps(v.jaxpr)

    with jax.set_mesh(mesh):
        traced = jax.make_jaxpr(
            lambda p, x: gpt2._qkv_by_head(x, p, cfg.dtype, *mesh_split(x.shape[0], cfg.n_head))
        )(qkv, x)
    (eqn,) = shard_maps(traced.jaxpr)
    _x, kernel, bias = eqn.params["in_specs"]
    assert kernel == stored["kernel"] == P("model", None)
    # the bias is stored whole and handed in as [3, n, d/n], a device's own columns
    assert all(a is None for a in stored["bias"]) and bias == P(None, "model", None)


def test_sharding_config_validation_and_defaults():
    with pytest.raises(ValueError, match="batch_axis"):
        ShardingConfig(mesh=("data", "model"), batch_axis="batch")
    with pytest.raises(ValueError, match="mesh_shape"):
        ShardingConfig(mesh_shape={"expert": 2})
    cfg = ShardingConfig()
    shape = cfg.resolve_shape(8)
    assert shape == {"batch": -1, "model": 8} or shape["model"] in (2, 4, 8)
    # A partial shape must not silently idle devices: the unpinned
    # batch axis absorbs the remainder ({"model": 2} on 8 devices is a
    # 4x2 mesh, not 1x2 with 6 chips dark).
    cfg = ShardingConfig(mesh_shape={"model": 2})
    assert cfg.resolve_shape(8) == {"model": 2, "batch": -1}
    # ... unless the batch axis is pinned, or another axis already
    # carries the -1 (at most one absorber).
    cfg = ShardingConfig(mesh_shape={"batch": 4})
    assert cfg.resolve_shape(8) == {"batch": 4, "model": 1}
    cfg = ShardingConfig(mesh_shape={"model": -1})
    assert cfg.resolve_shape(8) == {"model": -1, "batch": 1}

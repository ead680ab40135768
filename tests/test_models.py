"""GPT-2 through the sharding plan on the virtual 8-device CPU mesh, a
second parameter tree (OLMoE's) through the same plan, and the seam
between the served families."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


def _batch(cfg, B=4, T=64, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab_size, size=(B, T + 1), dtype=np.int32)
    return jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:])


def test_gpt2_forward_shapes():
    from ray_tpu.models import gpt2

    cfg = gpt2.GPT2Config.tiny(dtype=jnp.float32)
    params = gpt2.init_params(cfg)
    tokens, _ = _batch(cfg, B=2, T=32)
    logits = gpt2.GPT2(cfg).apply({"params": params}, tokens)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, dtype=np.float32)).all()


def test_gpt2_sharded_train_step_dp_tp_sp():
    """Full batch x model x sequence train step through the sharding
    plan: params sharded over `model` by the one rule table, batch over
    `batch`, the sequence over `seq` through ring attention inside the
    plan-jitted step; the first loss is the unsharded model's and it
    decreases."""
    from ray_tpu.models import gpt2
    from ray_tpu.train import sharding

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    plan = sharding.build_plan(sharding.ShardingConfig(
        mesh=("batch", "model", "seq"), mesh_shape={"batch": 2, "model": 2, "seq": 2},
    ))
    assert dict(plan.mesh.shape) == {"batch": 2, "model": 2, "seq": 2}
    cfg = gpt2.GPT2Config.tiny(dtype=jnp.float32, mesh=plan.mesh, sp_axis="seq")
    opt = gpt2.make_adamw(lr=1e-2)
    params, opt_state = plan.shard_init(lambda rng: gpt2.init_params(cfg, rng), opt)
    assert params["h_0"]["attn"]["qkv"]["kernel"].sharding.spec == ("model", None)
    step = plan.jit_train_step(gpt2.make_train_step(cfg, opt), params, opt_state)
    tokens, targets = _batch(cfg, B=4, T=64)
    text = step.lower(params, opt_state, tokens, targets).as_text()
    assert "collective_permute" in text, "no ring over the sequence axis in the step"
    # the same function as the unsharded model with einsum attention
    want = float(gpt2.loss_fn(
        jax.device_get(params), tokens, targets, gpt2.GPT2Config.tiny(dtype=jnp.float32)
    ))
    losses = []
    for i in range(5):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[0] == pytest.approx(want, abs=1e-4)
    assert losses[-1] < losses[0], f"loss did not decrease: {losses}"


# ---------------------------------------------------------------------------
# a second parameter tree through the plan; what the families share


def test_a_second_tree_trains_sharded_under_rules_given_with_the_config():
    """The one rule table serves a second parameter tree: OLMoE's, whose
    leaves the default (GPT-2) rules do not name, under partition rules
    given with the ShardingConfig and the same plan recipe.  The loss is
    the next token's from ``olmoe.prefill_forward``'s logits at the last
    position."""
    from ray_tpu.models import common, olmoe
    from ray_tpu.train import sharding

    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs 4 devices")
    plan = sharding.build_plan(
        sharding.ShardingConfig(
            mesh_shape={"batch": 2, "model": 2},
            partition_rules=[
                (r"embed", ("model", None)),
                (r"(wqkv|router)", (None, "model")),
                (r"layers/\d+/wo", ("model", None)),
                (r"(wgu|wd)", ("model", None, None)),  # an expert's matrices whole, the experts split
                (r"lm_head", (None, "model")),
                (r"(w_in|w_qn|w_kn|w_post)", ()),
                (r"^norm", ()),
            ],
        ),
        devs[:4],
    )
    cfg = olmoe.OlmoeConfig.olmoe_tiny(dtype=jnp.float32)

    def loss_fn(params, tokens, targets, cfg):
        return common.next_token_loss(olmoe.prefill_forward(params, cfg, tokens)[0], targets)

    opt = __import__("optax").sgd(1e-1)
    params, opt_state = plan.shard_init(lambda rng: olmoe.init_params(cfg, rng), opt)
    step = plan.jit_train_step(common.make_train_step(loss_fn, cfg, opt), params, opt_state)
    toks = np.random.default_rng(1).integers(0, cfg.vocab_size, (4, 33), dtype=np.int32)
    t, y = jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, -1])
    losses = []
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, t, y)
        losses.append(float(loss))
    assert losses[-1] < losses[0]  # learns on the repeated batch
    # the model axis hit the projections and the experts, each leaf half a device
    layer = params["layers"][0]
    assert layer["wqkv"].sharding.spec == (None, "model")
    assert layer["wo"].sharding.spec == ("model", None)
    assert layer["wgu"].sharding.spec == ("model", None, None)
    # a tree the rules do not cover is refused, not replicated
    with pytest.raises(sharding.UnmatchedParamError, match="norm"):
        sharding.match_partition_rules(plan.config.rules()[:-1], params)


def test_rope_rotation_properties():
    from ray_tpu.models.common import rope

    x = jnp.asarray(np.random.default_rng(2).standard_normal((1, 8, 2, 16)), dtype=jnp.float32)
    r = rope(x, jnp.arange(8)[None], 10000.0)
    # Norm-preserving per position...
    assert np.allclose(np.linalg.norm(np.asarray(r), axis=-1),
                       np.linalg.norm(np.asarray(x), axis=-1), atol=1e-4)
    # ...and position 0 is the identity rotation.
    assert np.allclose(np.asarray(r[:, 0]), np.asarray(x[:, 0]), atol=1e-6)


def _model_files():
    import pathlib

    import ray_tpu.models

    return sorted(pathlib.Path(ray_tpu.models.__file__).parent.glob("*.py"))


@pytest.mark.parametrize("path", _model_files(), ids=lambda p: p.name)
def test_a_models_module_imports_from_no_family_and_no_private_name_of_ops(path):
    """What families share is no family's (``models/common.py``,
    ``models/layers.py``, ``ops/``): no module under ``ray_tpu/models``
    imports ANY name from a module named in MODEL_FAMILIES (but itself),
    so an edit for one family's cell is an edit to no other cell's
    program; and none imports an underscore name from ``ray_tpu.ops``,
    so a constant a family sizes its context by has a public name in the
    module that owns it."""
    import ast

    from ray_tpu.serve.llm.config import MODEL_FAMILIES

    families = {module for module, *_ in MODEL_FAMILIES} - {"ray_tpu.models." + path.stem}
    tree = ast.parse(path.read_text())
    borrowed, ops_modules = [], set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            borrowed += [a.name for a in node.names if a.name in families]
        elif isinstance(node, ast.ImportFrom):
            module = node.module or ""
            for a in node.names:
                private = module.startswith("ray_tpu.ops") and a.name.startswith("_")
                if module in families or f"{module}.{a.name}" in families or private:
                    borrowed.append(f"{module}.{a.name}")
                if module == "ray_tpu.ops":
                    ops_modules.add(a.asname or a.name)
    # and reaches for none through the module's name (``block_sparse._K_BLOCK``)
    borrowed += [f"{node.value.id}.{node.attr}" for node in ast.walk(tree)
                 if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name)
                 and node.value.id in ops_modules and node.attr.startswith("_")]
    assert not borrowed, borrowed

"""GPT-2, Llama and ViT models; sharded train steps through the sharding
plan on the virtual 8-device CPU mesh."""

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
# Llama family


def test_llama_forward_and_loss():
    from ray_tpu.models import llama

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 33), dtype=np.int32))
    logits = llama.Llama(cfg).apply({"params": params}, toks[:, :-1])
    assert logits.shape == (2, 32, cfg.vocab_size)
    loss = float(llama.loss_fn(params, toks[:, :-1], toks[:, 1:], cfg))
    assert np.isfinite(loss)
    # Untrained loss should be near ln(vocab) for a random model.
    assert abs(loss - np.log(cfg.vocab_size)) < 1.5


def test_llama_gqa_kv_heads_smaller():
    from ray_tpu.models import llama

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg)
    blk = params["h_0"]["attn"]
    d_head = cfg.d_model // cfg.n_head
    assert blk["q_proj"]["kernel"].shape[1] == cfg.n_head * d_head
    assert blk["k_proj"]["kernel"].shape[1] == cfg.n_kv_head * d_head
    assert cfg.n_kv_head < cfg.n_head


def test_llama_sharded_train_step():
    """The one rule table serves a second parameter tree: partition
    rules given with the ShardingConfig, the same plan recipe."""
    from ray_tpu.models import llama
    from ray_tpu.train import sharding

    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs 4 devices")
    plan = sharding.build_plan(
        sharding.ShardingConfig(
            mesh_shape={"batch": 2, "model": 2},
            partition_rules=[
                (r"token_embed/embedding", ("model", None)),
                (r"(q_proj|k_proj|v_proj|gate_proj|up_proj)/kernel", (None, "model")),
                (r"(o_proj|down_proj)/kernel", ("model", None)),
                (r"lm_head/kernel", (None, "model")),
                (r"(ln_attn|ln_mlp|ln_f)/scale", ()),
            ],
        ),
        devs[:4],
    )
    cfg = llama.LlamaConfig.tiny()
    opt = __import__("optax").sgd(1e-2)
    params, opt_state = plan.shard_init(lambda rng: llama.init_params(cfg, rng), opt)
    step = plan.jit_train_step(llama.make_train_step(cfg, opt), params, opt_state)
    toks = np.random.default_rng(1).integers(0, cfg.vocab_size, (4, 65), dtype=np.int32)
    t, y = jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])
    losses = []
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, t, y)
        losses.append(float(loss))
    assert losses[-1] < losses[0]  # learns on the repeated batch
    # the model axis hit the projections, each leaf half a device
    attn = params["h_0"]["attn"]
    assert attn["q_proj"]["kernel"].sharding.spec == (None, "model")
    assert attn["o_proj"]["kernel"].sharding.spec == ("model", None)
    # a tree the rules do not cover is refused, not replicated
    with pytest.raises(sharding.UnmatchedParamError, match="ln_f/scale"):
        sharding.match_partition_rules(plan.config.rules()[:-1], params)


def test_llama_rope_rotation_properties():
    from ray_tpu.models.llama import rope

    x = jnp.asarray(np.random.default_rng(2).standard_normal((1, 8, 2, 16)), dtype=jnp.float32)
    r = rope(x, 10000.0)
    # Norm-preserving per position...
    assert np.allclose(np.linalg.norm(np.asarray(r), axis=-1),
                       np.linalg.norm(np.asarray(x), axis=-1), atol=1e-4)
    # ...and position 0 is the identity rotation.
    assert np.allclose(np.asarray(r[:, 0]), np.asarray(x[:, 0]), atol=1e-6)


def test_vit_overfits_synthetic_batch():
    """ViT (models/vit.py): forward shapes + a few steps overfit a tiny
    labeled batch (the standard can-it-learn smoke for a new model
    family; reference trains ViTs through the Train library)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models import vit

    cfg = vit.ViTConfig.tiny(image_size=16, patch_size=4, num_classes=4,
                             dtype=jnp.float32)
    params = vit.init_params(cfg)
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.normal(size=(16, 16, 16, 3)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 4, 16))

    logits = vit.ViT(cfg).apply({"params": params}, images)
    assert logits.shape == (16, 4)

    opt = optax.adam(1e-3)
    opt_state = opt.init(params)
    step = jax.jit(vit.make_train_step(cfg, opt))
    first = None
    for _ in range(40):
        params, opt_state, loss = step(params, opt_state, images, labels)
        first = first if first is not None else float(loss)
    last = float(loss)
    assert last < first * 0.5, (first, last)


def test_no_family_imports_a_private_name_of_another():
    """What two families share has a public name and one home
    (``models/common.py``; the two hybrids' layers ``models/nemotron_h.py``):
    no module under ``ray_tpu/models`` imports a name that starts with an
    underscore from another one there, so an edit to a private helper is
    an edit to its own family alone."""
    import ast
    import pathlib

    import ray_tpu.models

    borrowed = []
    for path in sorted(pathlib.Path(ray_tpu.models.__file__).parent.glob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.ImportFrom) and (node.module or "").startswith("ray_tpu.models"):
                borrowed += [f"{path.name}: {node.module}.{a.name}" for a in node.names if a.name.startswith("_")]
    assert not borrowed, borrowed

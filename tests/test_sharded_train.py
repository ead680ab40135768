"""GSPMD pjit path (train/sharding/gspmd.py + checkpoint.py): GPT-2
sharded over a batch x model mesh trains with LOSS PARITY vs the
data-parallel baseline, and per-shard checkpoints re-shard onto a
different mesh (the elastic resize semantics).

All tests run single-process on the suite's 8 virtual CPU devices; the
multi-worker variant of the same plan is the trainer integration below
(capability-probe-xfailed on the CPU backend like its data-parallel
siblings)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import ray_tpu.train.sharding as sharding  # noqa: E402
from ray_tpu.models import gpt2  # noqa: E402


def _tiny_cfg():
    # f32 end-to-end so parity checks are exact-ish, not bf16-fuzzy.
    return gpt2.GPT2Config(
        vocab_size=256, n_layer=2, n_head=2, d_model=64, max_seq_len=64,
        dtype=jnp.float32, remat=False,
    )


def _init_fn(cfg):
    def init(rng):
        tokens = jnp.zeros((2, 16), dtype=jnp.int32)
        return gpt2.GPT2(cfg).init(rng, tokens)["params"]

    return init


def _data(steps=3, batch=8, seq=17, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (steps, batch, seq)).astype(np.int32)


def _run(plan, cfg, data):
    opt = gpt2.make_adamw(1e-3)
    params, opt_state = plan.shard_init(_init_fn(cfg), opt)
    step = plan.jit_train_step(gpt2.make_train_step(cfg, opt), params, opt_state)
    losses = []
    for toks in data:
        params, opt_state, loss = step(
            params, opt_state, toks[:, :-1], toks[:, 1:]
        )
        losses.append(float(loss))
    return params, opt_state, losses


def test_gspmd_mesh_shards_params_and_state():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    plan = sharding.build_plan(
        sharding.ShardingConfig(mesh_shape={"batch": -1, "model": 2})
    )
    assert dict(plan.mesh.shape) == {"batch": 4, "model": 2}
    cfg = _tiny_cfg()
    opt = gpt2.make_adamw(1e-3)
    params, opt_state = plan.shard_init(_init_fn(cfg), opt)
    qkv = params["h_0"]["attn"]["qkv"]["kernel"]
    # the model axis really splits the leaf: each shard holds half the rows
    assert qkv.sharding.spec == jax.sharding.PartitionSpec("model", None)
    shard_rows = {s.data.shape[0] for s in qkv.addressable_shards}
    assert shard_rows == {qkv.shape[0] // 2}
    # optimizer moments follow the SAME layout; scalars replicate
    flat = jax.tree_util.tree_leaves(opt_state)
    assert all(
        getattr(l.sharding, "mesh", None) is plan.mesh
        or l.sharding.is_fully_replicated
        for l in flat
    )


@pytest.mark.parametrize("platform, n_devices, overlapped", [
    ("tpu", 4, True), ("tpu", 1, False), ("cpu", 4, False), ("cpu", 1, False),
])
def test_step_compile_options_follow_the_mesh(platform, n_devices, overlapped):
    """The overlap options go to a mesh of several TPUs and to nothing
    else: one device has no sums, and the CPU compiler refuses the
    `xla_tpu_*` names."""
    from types import SimpleNamespace

    from ray_tpu.train.sharding import gspmd

    devices = np.array([SimpleNamespace(platform=platform)] * n_devices, dtype=object)
    plan = gspmd.GspmdPlan(sharding.ShardingConfig(), SimpleNamespace(size=n_devices, devices=devices))
    options = plan._step_compiler_options()
    assert options == (gspmd._OVERLAP_COLLECTIVES if overlapped else None)
    # the pair that works only together
    assert set(gspmd._OVERLAP_COLLECTIVES) >= {
        "xla_enable_async_all_reduce", "xla_tpu_enable_async_collective_fusion_fuse_all_reduce"}


@pytest.mark.parametrize("mesh_shape", [{"batch": 1}, {"batch": 2, "model": 2}], ids=["one-device", "cpu-2x2"])
def test_step_on_one_device_or_a_cpu_mesh_hands_jit_no_options(monkeypatch, mesh_shape):
    """What `jit_train_step` hands `jax.jit` on one device and on a CPU
    mesh of several is the parent's call: no `compiler_options`.  The
    step compiles and trains here."""
    n = int(np.prod(list(mesh_shape.values())))
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices")
    plan = sharding.build_plan(sharding.ShardingConfig(mesh_shape=mesh_shape), devices=jax.devices()[:n])
    handed = []
    real_jit = jax.jit

    def spy(fn, **kwargs):
        handed.append(kwargs)
        return real_jit(fn, **kwargs)

    monkeypatch.setattr(jax, "jit", spy)
    _, _, losses = _run(plan, _tiny_cfg(), _data())
    steps = [kw for kw in handed if kw.get("donate_argnums") == (0, 1)]
    assert len(steps) == 1 and steps[0].get("compiler_options") is None
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]


@pytest.mark.parametrize("n_head", [20, 8])
@pytest.mark.parametrize("mesh_shape", [(2, 2), (2, 4), (1, 8)])
def test_qkv_exchange_matches_plain_projection(mesh_shape, n_head):
    """Under a mesh whose `model` axis divides the heads, Attention
    exchanges the fused projection's weights (one all-to-all over
    `model`) and emits q, k, v by head: they, and the gradients with
    respect to x, kernel and bias, are the plain Dense + split's.  An
    axis that does not divide the heads (8 under 20) takes the plain
    path."""
    import flax.linen as nn
    from jax.sharding import Mesh

    from ray_tpu.ops.attention import mesh_split

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    exchanged = n_head % mesh_shape[1] == 0
    cfg = gpt2.GPT2Config(
        vocab_size=256, n_layer=1, n_head=n_head, d_model=8 * n_head, max_seq_len=16,
        dtype=jnp.float32, remat=False,
    )
    attn = gpt2.Attention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
    params = attn.init(jax.random.PRNGKey(0), x)["params"]
    params["qkv"]["bias"] = jax.random.normal(jax.random.PRNGKey(2), (3 * cfg.d_model,))
    # a weight for every element of q, k and v, so that each has a gradient of its own
    probe = jax.random.normal(jax.random.PRNGKey(3), (3, *x.shape))

    def plain(p, x):
        return jnp.stack(jnp.split(nn.Dense(3 * cfg.d_model).apply({"params": p}, x), 3, axis=-1))

    def by_head(p, x):
        return jnp.stack(gpt2._qkv_by_head(x, p, cfg.dtype, *mesh_split(x.shape[0], n_head)))

    def with_grads(fn):
        def scalar(p, x):
            out = fn(p, x)
            return (out * probe).sum(), out

        return jax.jit(jax.value_and_grad(scalar, argnums=(0, 1), has_aux=True))

    want = with_grads(plain)(params["qkv"], x)
    want_out = attn.apply({"params": params}, x)
    devices = np.array(jax.devices()[:mesh_shape[0] * mesh_shape[1]]).reshape(mesh_shape)
    with jax.set_mesh(Mesh(devices, ("batch", "model"))):
        module = jax.jit(lambda p, x: attn.apply({"params": p}, x)).lower(params, x)
        assert ("all_to_all" in module.as_text()) == exchanged
        np.testing.assert_allclose(module.compile()(params, x), want_out, atol=1e-5)
        if exchanged:
            got = with_grads(by_head)(params["qkv"], x)
            for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize(
    "mesh_shape", [{"batch": -1, "model": 2}, {"batch": 1, "model": 2}],
    ids=["batch4xmodel2", "model2"],
)
def test_gspmd_loss_parity_vs_data_parallel(mesh_shape):
    """The acceptance bar: batch x model sharded GPT-2 trains to the
    same losses as the pure data-parallel layout (same seed/data); with
    batch 1 the model axis alone carries the step, so the sharded
    program computes the same function as the unsharded one."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    cfg = _tiny_cfg()
    data = _data()
    plan_tp = sharding.build_plan(sharding.ShardingConfig(mesh_shape=mesh_shape))
    assert plan_tp.mesh.shape["model"] == 2
    plan_dp = sharding.build_plan(
        sharding.ShardingConfig(
            mesh=("batch",), mesh_shape={"batch": 8},
            partition_rules=[(r".*", ())],
        )
    )
    _, _, losses_tp = _run(plan_tp, cfg, data)
    _, _, losses_dp = _run(plan_dp, cfg, data)
    assert losses_tp == pytest.approx(losses_dp, abs=1e-4)


def test_sharded_checkpoint_reshards_on_mesh_resize(tmp_path):
    """Per-shard save on a model=2 mesh, restore onto a model=4 mesh
    (shrink/grow-whole-hosts resize): values identical, new layout."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    cfg = _tiny_cfg()
    data = _data(steps=2)
    plan_a = sharding.build_plan(
        sharding.ShardingConfig(mesh_shape={"batch": -1, "model": 2})
    )
    params_a, opt_a, _ = _run(plan_a, cfg, data)
    plan_a.save_checkpoint({"params": params_a, "opt": opt_a}, str(tmp_path))

    plan_b = sharding.build_plan(
        sharding.ShardingConfig(mesh_shape={"batch": -1, "model": 4})
    )
    opt = gpt2.make_adamw(1e-3)
    like_p, like_o = plan_b.shard_init(_init_fn(cfg), opt)
    restored = plan_b.load_checkpoint(
        str(tmp_path), {"params": like_p, "opt": like_o}
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(restored["params"]),
        jax.tree_util.tree_leaves(params_a),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    qkv = restored["params"]["h_0"]["attn"]["qkv"]["kernel"]
    assert qkv.sharding.mesh.shape["model"] == 4
    # training continues from the restored state on the NEW mesh
    step = plan_b.jit_train_step(
        gpt2.make_train_step(cfg, opt), restored["params"], restored["opt"]
    )
    toks = _data(steps=1)[0]
    _, _, loss = step(
        restored["params"], restored["opt"], toks[:, :-1], toks[:, 1:]
    )
    assert np.isfinite(float(loss))


def test_checkpoint_leaf_mismatch_is_typed(tmp_path):
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    plan = sharding.build_plan(
        sharding.ShardingConfig(mesh_shape={"batch": -1, "model": 2})
    )
    tree = {"a": jnp.zeros((4, 4))}
    plan.save_checkpoint(tree, str(tmp_path))
    with pytest.raises(ValueError, match="leaves"):
        sharding.load_sharded(str(tmp_path), {"a": tree["a"], "b": tree["a"]})


def _sharded_trainer_loop(config):
    """Multi-worker GSPMD: the trainer carried the ShardingConfig; every
    rank binds it to the global device view via plan_from_context."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import train
    from ray_tpu.models import gpt2
    from ray_tpu.train import sharding

    ctx = train.get_context()
    assert ctx.get_sharding_config() is not None
    plan = sharding.plan_from_context()
    assert plan.mesh.shape["model"] == 2
    assert len(jax.devices()) == 8 * config["num_workers"]
    cfg = gpt2.GPT2Config(
        vocab_size=256, n_layer=2, n_head=2, d_model=64, max_seq_len=64,
        dtype=jnp.float32, remat=False,
    )
    opt = gpt2.make_adamw(1e-3)

    def init(rng):
        return gpt2.GPT2(cfg).init(
            rng, jnp.zeros((2, 16), dtype=jnp.int32)
        )["params"]

    params, opt_state = plan.shard_init(init, opt)
    step = plan.jit_train_step(
        gpt2.make_train_step(cfg, opt), params, opt_state
    )
    import numpy as np

    toks = np.random.default_rng(0).integers(0, 256, (8, 17)).astype(np.int32)
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices("sharded_trainer_loop")
    last = None
    for _ in range(2):
        params, opt_state, loss = step(
            params, opt_state, toks[:, :-1], toks[:, 1:]
        )
        last = float(jax.device_get(loss))
    train.report({"loss": last})


def test_jax_trainer_carries_sharding_config(ray_cluster, tmp_path):
    """JaxTrainer(sharding_config=...) reaches every rank's context and
    the 2-worker group forms one 16-device batch x model mesh."""
    from ray_tpu.train import RunConfig, ScalingConfig
    from ray_tpu.train.jax import JaxTrainer

    trainer = JaxTrainer(
        _sharded_trainer_loop,
        train_loop_config={"num_workers": 2},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="gspmd_cfg", storage_path=str(tmp_path)),
        sharding_config=sharding.ShardingConfig(
            mesh_shape={"batch": -1, "model": 2}
        ),
    )
    result = trainer.fit()
    assert np.isfinite(result.metrics["loss"])

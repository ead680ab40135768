"""Train library: JaxTrainer end-to-end on real worker processes.

The minimum end-to-end slice from SURVEY.md §7: a 2-worker
DataParallelTrainer MLP on CPU — but with the real jax.distributed
bootstrap (Gloo collectives between the two actor processes, global
16-device mesh) rather than a mock.
"""

import os

import numpy as np
import pytest

import ray_tpu
from ray_tpu import train
from ray_tpu.train import (
    Checkpoint,
    FailureConfig,
    RunConfig,
    ScalingConfig,
)
from ray_tpu.train.jax import JaxConfig, JaxTrainer


def _mlp_loop(config):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.models import mlp

    ctx = train.get_context()
    assert ctx.get_world_size() == config["num_workers"]

    mesh = Mesh(np.array(jax.devices()), ("dp",))
    cfg = mlp.MLPConfig(in_dim=16, hidden=(32,), num_classes=4)
    params = mlp.init_params(cfg, jax.random.PRNGKey(0))

    resume = train.get_checkpoint()
    if resume is not None:
        params = resume.to_pytree()

    opt = optax.sgd(0.1)
    opt_state = opt.init(params)
    step = jax.jit(mlp.make_train_step(cfg, opt))

    rng = np.random.default_rng(42)
    n_local = 8
    data_sharding = NamedSharding(mesh, P("dp"))

    for epoch in range(config["epochs"]):
        x_local = rng.standard_normal((n_local, 16)).astype(np.float32)
        y_local = (x_local.sum(axis=1) > 0).astype(np.int32)
        x = jax.make_array_from_process_local_data(data_sharding, x_local)
        y = jax.make_array_from_process_local_data(data_sharding, y_local)
        params, opt_state, loss = step(params, opt_state, x, y)
        loss_val = float(jax.device_get(loss))
        ckpt = None
        if ctx.get_world_rank() == 0 and epoch == config["epochs"] - 1:
            host_params = jax.device_get(params)
            ckpt = Checkpoint.from_pytree(host_params)
        train.report({"loss": loss_val, "epoch": epoch}, checkpoint=ckpt)


@pytest.mark.parametrize("num_workers", [2])
def test_jax_trainer_distributed_mlp(ray_cluster, tmp_path, num_workers):
    trainer = JaxTrainer(
        _mlp_loop,
        train_loop_config={"epochs": 3, "num_workers": num_workers},
        scaling_config=ScalingConfig(num_workers=num_workers),
        run_config=RunConfig(name="mlp_test", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.metrics is not None
    assert result.metrics["epoch"] == 2
    assert np.isfinite(result.metrics["loss"])
    assert result.checkpoint is not None
    tree = result.checkpoint.to_pytree()
    assert "dense_0" in tree


def test_jax_trainer_resume_from_checkpoint(ray_cluster, tmp_path):
    trainer = JaxTrainer(
        _mlp_loop,
        train_loop_config={"epochs": 2, "num_workers": 2},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="mlp_resume_a", storage_path=str(tmp_path)),
    )
    r1 = trainer.fit()
    trainer2 = JaxTrainer(
        _mlp_loop,
        train_loop_config={"epochs": 1, "num_workers": 2},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="mlp_resume_b", storage_path=str(tmp_path)),
        resume_from_checkpoint=r1.checkpoint,
    )
    r2 = trainer2.fit()
    assert r2.metrics["loss"] <= r1.metrics["loss"] + 0.5  # continued, not reset


def test_trainer_restore_from_experiment_dir(ray_cluster, tmp_path):
    """Trainer.restore(path) rebuilds the trainer from the saved
    trainer.pkl and resumes from the latest checkpoint (reference:
    train/base_trainer.py:250)."""
    trainer = JaxTrainer(
        _mlp_loop,
        train_loop_config={"epochs": 2, "num_workers": 2},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="mlp_restore", storage_path=str(tmp_path)),
    )
    r1 = trainer.fit()
    exp_dir = os.path.join(str(tmp_path), "mlp_restore")
    assert JaxTrainer.can_restore(exp_dir)
    restored = JaxTrainer.restore(exp_dir)
    assert restored.resume_from_checkpoint is not None
    assert restored.train_loop_config["epochs"] == 2
    r2 = restored.fit()
    # Restored run continued from r1's params (loss did not reset).
    assert r2.metrics["loss"] <= r1.metrics["loss"] + 0.5
    # Overrides replace saved fields.
    restored2 = JaxTrainer.restore(exp_dir, train_loop_config={"epochs": 1, "num_workers": 2})
    assert restored2.train_loop_config["epochs"] == 1


def _flaky_loop(config):
    marker = config["marker"]
    if not os.path.exists(marker):
        with open(marker, "w") as f:
            f.write("x")
        raise RuntimeError("injected first-attempt failure")
    train.report({"ok": 1})


def test_failure_config_retries(ray_cluster, tmp_path):
    trainer = JaxTrainer(
        _flaky_loop,
        train_loop_config={"marker": str(tmp_path / "marker")},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(
            name="flaky", storage_path=str(tmp_path),
            failure_config=FailureConfig(max_failures=1),
        ),
    )
    result = trainer.fit()
    assert result.metrics == {"ok": 1}


def _kill_rank1_once_loop(config):
    """First attempt: rank 1 dies HARD (os._exit — no exception, no
    teardown, the signature of an OOM/SIGKILL/preempted-host death)
    mid-training, after the jax.distributed rendezvous is up.  Second
    attempt: everyone trains to completion."""
    import jax

    ctx = train.get_context()
    # The re-rendezvous proof: every attempt sees the FULL world again —
    # process_count comes from the jax.distributed coordinator, so a
    # half-rebuilt group would fail here.
    assert jax.process_count() == config["num_workers"]
    marker = config["marker"]
    if ctx.get_world_rank() == 1 and not os.path.exists(marker):
        with open(marker, "w") as f:
            f.write("killed")
        os._exit(1)
    train.report({"ok": 1, "procs": jax.process_count()})


@pytest.mark.slow  # ~104 s whole-mesh restart drill: runs under `-m chaos`
@pytest.mark.chaos
def test_killed_worker_whole_mesh_restart(ray_cluster, tmp_path):
    """Recovery drill (ISSUE 1): a killed training worker triggers a
    clean WHOLE-mesh restart — XLA's world is static, so the dead rank
    cannot rejoin; the group is torn down, fresh workers are leased, and
    jax.distributed re-rendezvouses with a new coordinator — and the job
    completes."""
    marker = tmp_path / "rank1_killed"
    trainer = JaxTrainer(
        _kill_rank1_once_loop,
        train_loop_config={"marker": str(marker), "num_workers": 2},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(
            name="mesh_restart", storage_path=str(tmp_path),
            failure_config=FailureConfig(max_failures=1),
        ),
    )
    result = trainer.fit()
    assert result.metrics == {"ok": 1, "procs": 2}
    assert marker.exists(), "the fault was never injected"


def test_failure_without_retries_raises(ray_cluster, tmp_path):
    def always_fail(config):
        raise ValueError("nope")

    trainer = JaxTrainer(
        always_fail,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="fail", storage_path=str(tmp_path)),
    )
    with pytest.raises(train.TrainingFailedError):
        trainer.fit()


def _gpt2_data_loop(config):
    """The BASELINE configs[3] shape in miniature: every worker is one
    jax.distributed process of a single global mesh; the sharded GPT-2
    step (the trainer's batch x model plan) consumes batches straight
    from this rank's Dataset.streaming_split shard via iter_jax_batches
    (reference: train/data_parallel_trainer.py:428 + dataset.py:1482)."""
    import jax
    import numpy as np

    from ray_tpu import train
    from ray_tpu.models import gpt2
    from ray_tpu.train import sharding

    ctx = train.get_context()
    assert ctx.get_world_size() == config["num_workers"]
    n_global = len(jax.devices())
    assert n_global == 8 * config["num_workers"], n_global  # ONE global mesh

    # Align ranks before the (slow, 1-core CPU) compile: Gloo's clique
    # rendezvous times out if one rank reaches the first collective
    # long before its peer.
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices("gpt2_data_loop_start")

    plan = sharding.plan_from_context()
    assert dict(plan.mesh.shape) == {"batch": n_global // 2, "model": 2}
    cfg = gpt2.GPT2Config(
        vocab_size=256, n_layer=1, n_head=2, d_model=64, max_seq_len=64
    )
    opt = gpt2.make_adamw(1e-3)
    params, opt_state = plan.shard_init(lambda rng: gpt2.init_params(cfg, rng), opt)
    step = plan.jit_train_step(gpt2.make_train_step(cfg, opt), params, opt_state)

    shard = train.get_dataset_shard("train")
    steps, last_loss = 0, None
    for batch in shard.iter_jax_batches(
        batch_size=config["per_worker_batch"],
        sharding=plan.data_sharding(),
        dtypes={"data": np.int32},
    ):
        toks = batch["data"]  # global [B, T+1] assembled across ranks
        assert toks.shape[0] == config["per_worker_batch"] * config["num_workers"]
        params, opt_state, loss = step(params, opt_state, toks[:, :-1], toks[:, 1:])
        last_loss = float(jax.device_get(loss))
        steps += 1
    train.report({"loss": last_loss, "steps": steps})


def _batch_by_model():
    from ray_tpu.train.sharding import ShardingConfig

    return ShardingConfig(mesh_shape={"batch": -1, "model": 2})


def test_jax_trainer_sharded_gpt2_streaming_split(ray_cluster, tmp_path):
    """VERDICT r4 ask #2: trainer + data + mesh in ONE path — 2 worker
    processes form a 16-device global mesh, run the sharded GPT-2 step,
    fed by streaming_split shards."""
    import ray_tpu.data as rdata

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 256, (32, 33), dtype=np.int64)  # < vocab_size
    ds = rdata.from_numpy(tokens)

    trainer = JaxTrainer(
        _gpt2_data_loop,
        train_loop_config={"num_workers": 2, "per_worker_batch": 4},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="gpt2_stream", storage_path=str(tmp_path)),
        datasets={"train": ds},
        sharding_config=_batch_by_model(),
    )
    result = trainer.fit()
    assert result.metrics is not None
    # 32 rows / (4 per worker × 2 workers) = 4 global steps
    assert result.metrics["steps"] == 4, result.metrics
    assert np.isfinite(result.metrics["loss"])


def test_typed_restore_sharded_gpt2_with_closure_loop(ray_cluster, tmp_path):
    """VERDICT r4 ask #8: Trainer.restore re-binds unpicklable fields as
    a typed API.  The train loop is a CLOSURE (plain-pickle fails), so
    trainer.pkl records it by name; restore() without the override
    raises naming exactly that parameter, and the typed restore with a
    fresh loop + datasets resumes the sharded-GPT-2 run."""
    import ray_tpu.data as rdata

    rng = np.random.default_rng(1)
    tokens = rng.integers(0, 256, (32, 33), dtype=np.int64)
    cfg = {"num_workers": 2, "per_worker_batch": 4}

    def loop(config):  # closure over cfg -> not plain-picklable
        _gpt2_data_loop(cfg)

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="gpt2_typed_restore", storage_path=str(tmp_path)),
        datasets={"train": rdata.from_numpy(tokens)},
        sharding_config=_batch_by_model(),
    )
    r1 = trainer.fit()
    assert r1.metrics["steps"] == 4

    exp_dir = os.path.join(str(tmp_path), "gpt2_typed_restore")
    assert JaxTrainer.can_restore(exp_dir)
    # restoring without the unpicklable field is a TYPED error naming it
    with pytest.raises(ValueError, match="train_loop_per_worker"):
        JaxTrainer.restore(exp_dir)
    restored = JaxTrainer.restore(
        exp_dir,
        train_loop_per_worker=loop,
        datasets={"train": rdata.from_numpy(tokens)},
    )
    r2 = restored.fit()
    assert r2.metrics["steps"] == 4
    assert np.isfinite(r2.metrics["loss"])

"""chip_smoke.py and the rules it proves, as far as a CPU can show them:
the command fails without a chip; a worker whose lease carries no TPU is
held to the CPU backend and a chip owner exits with its lease; the
compile cache goes where the environment says; and the smoke's phase
bodies run end to end at a tiny size."""

import os
import shutil
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _run_smoke(cwd, env):
    return subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=60,
    )


def test_chip_smoke_fails_without_a_chip():
    out = _run_smoke(REPO, {**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU resource" in out.stderr


def test_chip_smoke_fails_alone_in_a_directory(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = _run_smoke(tmp_path, env)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


def test_compile_cache_env_wins_and_default_is_in_the_checkout(monkeypatch, tmp_path):
    from ray_tpu.util.compile_cache import ENV, count_cache_entries, place_compile_cache

    monkeypatch.setenv(ENV, "/placed/from/outside")
    assert place_compile_cache(str(tmp_path)) == "/placed/from/outside"
    assert os.environ[ENV] == "/placed/from/outside"

    monkeypatch.delenv(ENV)
    default = place_compile_cache(str(tmp_path))
    assert default == str(tmp_path / ".jax_cache")
    # set for every process started afterwards, and the same on every call
    assert os.environ[ENV] == default
    assert place_compile_cache(str(tmp_path)) == default

    assert count_cache_entries(default) == 0
    os.makedirs(default)
    for name in ("jit_step-abc-cache", "jit_step-abc-atime"):
        open(os.path.join(default, name), "w").close()
    assert count_cache_entries(default) == 1


def test_detection_counts_chips_and_assumes_no_type(monkeypatch):
    from ray_tpu._private.accelerators.tpu import TPUAcceleratorManager as Manager

    monkeypatch.setattr(Manager, "_cached", None)
    monkeypatch.setenv("TPU_CHIPS_PER_HOST", "4")
    monkeypatch.delenv("TPU_ACCELERATOR_TYPE", raising=False)
    monkeypatch.setenv("TPU_SKIP_MDS_QUERY", "true")
    assert Manager.get_current_node_num_accelerators() == 4
    assert Manager.get_current_node_accelerator_type() is None
    assert Manager.get_current_node_additional_resources() == {}

    monkeypatch.setattr(Manager, "_cached", None)
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    assert Manager.get_current_node_additional_resources() == {
        "TPU-v5litepod-4": 4.0, "TPU-v5litepod-4-head": 1.0,
    }
    monkeypatch.setattr(Manager, "_cached", None)


def _pid_alive(pid):
    try:
        os.kill(pid, 0)
        return True
    except OSError:
        return False


def test_only_a_tpu_lease_leaves_a_worker_off_the_cpu_pin(monkeypatch):
    """The raylet's rule, seen through the workers' environment (no task
    here imports JAX, so nothing tries to open a chip this box lacks)."""
    import ray_tpu
    from ray_tpu._private import retry

    # what a TPU host's environment says; workers inherit it unless pinned
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    ray_tpu.init(num_cpus=4, num_tpus=1)
    try:
        @ray_tpu.remote
        def where():
            return os.getpid(), os.environ.get("JAX_PLATFORMS")

        @ray_tpu.remote(num_tpus=1)
        class Owner:
            def where(self):
                return os.getpid(), os.environ.get("JAX_PLATFORMS")

        task_pid, task_env = ray_tpu.get(where.options(num_tpus=1).remote())
        cpu_pid, cpu_env = ray_tpu.get(where.remote())
        assert task_env == "tpu,cpu" and cpu_env == "cpu"
        assert task_pid != cpu_pid
        # The actor needs the node's one chip: it starts only after the
        # task's lease was returned AND its process, which would still
        # hold the chip, is gone.
        owner = Owner.remote()
        actor_pid, actor_env = ray_tpu.get(owner.where.remote(), timeout=60)
        assert actor_env == "tpu,cpu" and actor_pid != task_pid
        assert not _pid_alive(task_pid)
        assert _pid_alive(cpu_pid)  # CPU workers go back to the idle pool
        assert ray_tpu.available_resources().get("TPU", 0) == 0
        ray_tpu.kill(owner)
        poll = retry.POLL.start(deadline_s=30)
        while ray_tpu.available_resources().get("TPU", 0) != 1:
            delay = poll.next_delay()
            assert delay is not None, "the chip never came back"
            time.sleep(delay)
        assert not _pid_alive(actor_pid)
    finally:
        ray_tpu.shutdown()


def test_shutdown_leaves_no_process_of_the_session():
    """Workers run in sessions of their own, so nothing but the raylet
    ends them: shutdown() returns only when the head and every worker,
    idle or holding a chip, are gone and were waited for."""
    import ray_tpu
    from ray_tpu._private.node import session_pids
    from ray_tpu._private.worker import get_global_worker

    ray_tpu.init(num_cpus=2, num_tpus=1)
    try:
        session = get_global_worker().session_info["session_dir"]

        @ray_tpu.remote
        def pid():
            return os.getpid()

        @ray_tpu.remote(num_tpus=1)
        class Owner:
            def pid(self):
                return os.getpid()

        owner = Owner.remote()
        pids = [ray_tpu.get(pid.remote()), ray_tpu.get(owner.pid.remote())]
        assert set(pids) < set(session_pids(session))  # and the head
    finally:
        ray_tpu.shutdown()
    assert session_pids(session) == []
    assert not any(_pid_alive(p) for p in pids)  # not even as zombies


def test_use_tpu_without_a_tpu_is_refused(ray_start_regular):
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.exceptions import TPUPlacementError

    with pytest.raises(TPUPlacementError, match="advertises"):
        ScalingConfig(num_workers=1, use_tpu=True)._worker_resources()


@pytest.fixture(scope="module")
def four_chip_cluster():
    """Four TPUs by declaration; their workers stay on this box's CPU."""
    import ray_tpu

    ray_tpu.init(num_cpus=4, num_tpus=4)
    yield ray_tpu
    ray_tpu.shutdown()


def test_tpu_trainer_refuses_workers_that_would_share_a_host(four_chip_cluster):
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.exceptions import TPUPlacementError
    from ray_tpu.train.jax import JaxTrainer

    one = ScalingConfig(num_workers=1, use_tpu=True)
    assert one._worker_resources() == {"TPU": 4.0}
    one.check_tpu_placement()

    trainer = JaxTrainer(
        lambda: None, scaling_config=ScalingConfig(num_workers=2, use_tpu=True)
    )
    with pytest.raises(TPUPlacementError, match="chip ids"):
        trainer.fit()
    with pytest.raises(TPUPlacementError):
        ScalingConfig(
            num_workers=4, resources_per_worker={"TPU": 1}
        ).check_tpu_placement()


def test_smoke_phases_at_a_tiny_size(four_chip_cluster):
    """The bodies chip_smoke.py runs on the chip, here on the CPU: same
    entry points, tiny model, float32."""
    import chip_smoke

    leases = chip_smoke.phase_leases()
    assert leases["num_tpus_0_while_chip_held"]["platform"] == "cpu"
    assert leases["num_tpus_1"]["pid"] != leases["num_tpus_0_while_chip_held"]["pid"]

    train = dict(
        chip_smoke.TRAIN, model="tiny", dtype="float32", batch=2, seq=64,
        warmup=1, steps=3, ref_tol=1e-3, seed=0,
    )
    facts = chip_smoke.phase_train(train)
    chip_smoke.check_train(facts, train)
    assert facts["count"] >= 1 and not facts["kernel_in_step"]

    serve = dict(
        chip_smoke.SERVE, model="tiny", dtype="float32", max_batch_size=4,
        block_size=8, pool_tokens=4 * 128, prompt_len=(4, 60),
        max_tokens=(4, 16), http_port=18433,
    )
    serve_facts = chip_smoke.phase_serve(serve, 0)
    chip_smoke.check_serve(serve_facts)
    # a replica on a TPU cluster holds one chip as a lease
    assert serve_facts["replica"]["platform"] == "cpu"


def test_sharded_smoke_phase_on_virtual_devices(four_chip_cluster):
    """--chips 4's body: conftest gives every process eight virtual CPU
    devices, so the mesh here is 4 x 2."""
    import chip_smoke

    config = dict(
        chip_smoke.SHARDED, model="tiny", dtype="float32", batch=8, seq=64,
        mesh_shape={"batch": 4, "model": 2}, seed=0,
    )
    chip_smoke.check_sharded(chip_smoke.phase_sharded(config), config)

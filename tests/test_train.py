"""Train subsystem tests.

Models the reference's train tests (train/v2/tests/ — controller state
machine, worker group lifecycle, checkpoint manager top-K, report/context
API, failure retry) on the in-process runtime with CPU workers.
"""

import os

import pytest

import ray_tpu
from ray_tpu import train as rt_train
from ray_tpu.train import (
    Checkpoint,
    CheckpointConfig,
    CheckpointManager,
    DataParallelTrainer,
    FailureConfig,
    JaxTrainer,
    RunConfig,
    ScalingConfig,
    StorageContext,
)


def _run_cfg(tmp_path, **kw):
    return RunConfig(name="t", storage_path=str(tmp_path), **kw)


def test_scaling_config_validation():
    with pytest.raises(ValueError):
        ScalingConfig(num_workers=0)
    with pytest.raises(ValueError):
        ScalingConfig(num_workers=1, topology="2x2")  # topology needs use_tpu
    sc = ScalingConfig(num_workers=2, use_tpu=True, topology="2x2")
    assert sc.placement_strategy == "SPREAD"
    assert sc.total_resources() == {"TPU": 8}


def test_checkpoint_roundtrip(tmp_path):
    src = tmp_path / "src"
    src.mkdir()
    (src / "weights.bin").write_bytes(b"abc")
    ckpt = Checkpoint.from_directory(str(src))
    ckpt.update_metadata({"step": 3})
    dest = ckpt.to_directory(str(tmp_path / "dst"))
    assert open(os.path.join(dest, "weights.bin"), "rb").read() == b"abc"
    assert Checkpoint(dest).get_metadata()["step"] == 3


def test_checkpoint_manager_topk(tmp_path):
    storage = StorageContext(str(tmp_path), "run")
    mgr = CheckpointManager(storage, num_to_keep=2,
                            score_attribute="acc", score_order="max")
    for i, acc in enumerate([0.1, 0.9, 0.5, 0.3]):
        d = tmp_path / f"w{i}"
        d.mkdir()
        (d / "f").write_text(str(i))
        mgr.register(Checkpoint(str(d)), {"acc": acc})
    best = mgr.best_checkpoints()
    accs = [m["acc"] for _, m in best]
    # top-2 by acc, plus the latest is always kept
    assert 0.9 in accs and 0.5 in accs and 0.3 in accs and 0.1 not in accs
    assert mgr.latest.metrics["acc"] == 0.3


def test_checkpoint_manager_restore(tmp_path):
    storage = StorageContext(str(tmp_path), "run")
    mgr = CheckpointManager(storage)
    d = tmp_path / "w"
    d.mkdir()
    (d / "f").write_text("x")
    mgr.register(Checkpoint(str(d)), {"loss": 1.0})
    mgr.write_state()
    mgr2 = CheckpointManager.restore_state(StorageContext(str(tmp_path), "run"))
    assert mgr2.latest is not None
    assert mgr2.latest.metrics == {"loss": 1.0}


def test_data_parallel_trainer_e2e(ray_start_regular, tmp_path):
    def train_fn(config):
        ctx = rt_train.get_context()
        assert ctx.get_world_size() == 2
        for step in range(3):
            rt_train.report({"step": step, "rank": ctx.get_world_rank(),
                             "loss": 1.0 / (step + 1)})

    trainer = DataParallelTrainer(
        train_fn, train_loop_config={"lr": 0.1},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=_run_cfg(tmp_path))
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["step"] == 2
    assert result.metrics["loss"] == pytest.approx(1.0 / 3)


def test_trainer_checkpoint_persistence(ray_start_regular, tmp_path):
    def train_fn(config):
        import tempfile

        ctx = rt_train.get_context()
        for step in range(2):
            if ctx.get_world_rank() == 0:
                d = tempfile.mkdtemp()
                with open(os.path.join(d, "model.txt"), "w") as f:
                    f.write(f"step={step}")
                rt_train.report({"step": step}, checkpoint=Checkpoint(d))
            else:
                rt_train.report({"step": step})

    trainer = DataParallelTrainer(
        train_fn, scaling_config=ScalingConfig(num_workers=2),
        run_config=_run_cfg(tmp_path, checkpoint_config=CheckpointConfig(
            num_to_keep=1)))
    result = trainer.fit()
    assert result.error is None
    assert result.checkpoint is not None
    content = open(os.path.join(result.checkpoint.path, "model.txt")).read()
    assert content == "step=1"
    # persisted under the run dir, not the worker temp dir
    assert result.checkpoint.path.startswith(str(tmp_path))


def test_trainer_failure_retry_and_resume(ray_start_regular, tmp_path):
    marker = tmp_path / "failed_once"

    def train_fn(config):
        import tempfile

        ctx = rt_train.get_context()
        start = 0
        ckpt = rt_train.get_checkpoint()
        if ckpt is not None:
            start = int(open(os.path.join(ckpt.path, "step.txt")).read()) + 1
        for step in range(start, 4):
            if ctx.get_world_rank() == 0:
                d = tempfile.mkdtemp()
                with open(os.path.join(d, "step.txt"), "w") as f:
                    f.write(str(step))
                rt_train.report({"step": step}, checkpoint=Checkpoint(d))
            else:
                rt_train.report({"step": step})
            if step == 1 and not os.path.exists(str(marker)):
                open(str(marker), "w").close()
                raise RuntimeError("injected failure at step 1")

    trainer = DataParallelTrainer(
        train_fn, scaling_config=ScalingConfig(num_workers=2),
        run_config=_run_cfg(tmp_path, failure_config=FailureConfig(max_failures=1)))
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["step"] == 3
    assert os.path.exists(str(marker))  # the failure really happened


def test_trainer_failure_exhausted(ray_start_regular, tmp_path):
    def train_fn(config):
        raise ValueError("boom")

    trainer = DataParallelTrainer(
        train_fn, scaling_config=ScalingConfig(num_workers=1),
        run_config=_run_cfg(tmp_path, failure_config=FailureConfig(max_failures=0)))
    result = trainer.fit()
    assert result.error is not None
    assert "boom" in str(result.error)


def test_sync_actor_barrier(ray_start_regular):
    from ray_tpu.train.sync import SynchronizationActor

    sync = SynchronizationActor.remote(2)

    @ray_tpu.remote
    def rendezvous(sync, rank):
        return ray_tpu.get(sync.broadcast_from_rank_zero.remote(
            rank, f"value-{rank}"))

    out = ray_tpu.get([rendezvous.remote(sync, r) for r in range(2)])
    assert out == ["value-0", "value-0"]


def test_jax_trainer_distributed_init_two_workers(ray_start_regular,
                                                  tmp_path):
    """The multi-host coordinator bootstrap path (reference
    _setup_jax_tpu_environment, train/v2/jax/config.py): rank 0 publishes a
    coordinator address through the sync actor and every worker runs
    jax.distributed.initialize. Two CPU-backend JAX processes form one
    distributed runtime — jax.process_count() must see both."""

    def train_fn(config):
        import jax

        ctx = rt_train.get_context()
        assert jax.process_count() == 2
        assert jax.process_index() == ctx.get_world_rank()
        # global device view proves both processes joined the coordination
        # service (initialize blocks until every process connects). Cross-
        # process CPU collectives aren't exercised — XLA's CPU backend
        # doesn't ship them; on TPU the same path runs over ICI.
        assert len(jax.devices()) == 2 * len(jax.local_devices())
        rt_train.report({"procs": jax.process_count(),
                         "rank": ctx.get_world_rank()})

    trainer = JaxTrainer(
        train_fn,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=_run_cfg(tmp_path),
        use_distributed=True)
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["procs"] == 2


def test_jax_trainer_cpu_spmd(ray_start_regular, tmp_path):
    """JaxTrainer with a real (tiny) pjit step on the worker's CPU devices."""

    def train_fn(config):
        import jax
        import jax.numpy as jnp

        ctx = rt_train.get_context()

        @jax.jit
        def step(w, x, y):
            def loss(w):
                return jnp.mean((x @ w - y) ** 2)
            l, g = jax.value_and_grad(loss)(w)
            return w - 0.1 * g, l

        key = jax.random.PRNGKey(0)
        w = jnp.zeros((4, 1))
        x = jax.random.normal(key, (16, 4))
        y = x @ jnp.ones((4, 1))
        for i in range(5):
            w, l = step(w, x, y)
        rt_train.report({"loss": float(l), "rank": ctx.get_world_rank()})

    trainer = JaxTrainer(
        train_fn, scaling_config=ScalingConfig(num_workers=1),
        run_config=_run_cfg(tmp_path))
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["loss"] < 1.0


def test_jax_trainer_tpu_bundle_places_its_worker(tmp_path):
    """ScalingConfig(use_tpu=True, resources_per_worker={"TPU": n}) — the
    normal TPU entry — reserves a {"TPU": n} bundle; the rank actor must
    ask for exactly that (no default CPU on top), or its lease never fits
    the bundle and fit() waits forever. The TPU here is a declared
    resource on a CPU node: placement is what is under test."""
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, resources={"TPU": 1})
    try:
        def train_fn():
            rt_train.report({"node": ray_tpu.get_runtime_context().node_id})

        result = JaxTrainer(
            train_fn,
            scaling_config=ScalingConfig(use_tpu=True,
                                         resources_per_worker={"TPU": 1}),
            run_config=_run_cfg(tmp_path)).fit()
        assert result.error is None
        assert result.metrics["node"]
    finally:
        ray_tpu.shutdown()


def test_worker_group_execute(ray_start_regular):
    from ray_tpu.train.worker_group import WorkerGroup

    wg = WorkerGroup(ScalingConfig(num_workers=2))
    wg.start()
    try:
        out = wg.execute(lambda: os.getpid())
        assert len(out) == 2
        assert out[0] != out[1]  # distinct worker processes
    finally:
        wg.shutdown()


def test_elastic_resize_resumes_from_checkpoint(ray_start_regular, tmp_path):
    """ScalingPolicy resizes 4 -> 2 mid-run (restart-the-world); the resumed
    2-rank gang continues from the checkpoint instead of step 0, and every
    rank's shard lands in a merged sharded checkpoint."""
    import tempfile

    from ray_tpu.train import FunctionScalingPolicy

    def train_fn(config):
        ctx = rt_train.get_context()
        start = 0
        ckpt = rt_train.get_checkpoint()
        if ckpt is not None:
            meta = ckpt.get_metadata()
            assert meta.get("sharded"), "expected merged sharded checkpoint"
            shard0 = os.path.join(ckpt.path, "shard-00000")
            start = int(open(os.path.join(shard0, "step.txt")).read()) + 1
        import time as _time
        for step in range(start, 6):
            d = tempfile.mkdtemp()
            with open(os.path.join(d, "step.txt"), "w") as f:
                f.write(str(step))
            with open(os.path.join(d, "rank.txt"), "w") as f:
                f.write(str(ctx.get_world_rank()))
            ckpt = Checkpoint(d)
            # opt into the merged sharded layout (every rank's payload is a
            # shard, not a full checkpoint)
            ckpt.update_metadata({"shard": True})
            rt_train.report(
                {"step": step, "world": ctx.get_world_size()},
                checkpoint=ckpt)
            # slow enough that the controller polls mid-run (the resize
            # decision must land before the run finishes)
            _time.sleep(0.3)

    def decide(statuses, num_workers):
        # once any rank reported step >= 2 at world 4, shrink to 2
        if num_workers == 4:
            for st in statuses:
                if st is not None and st.reports:
                    if any(r.metrics.get("step", 0) >= 2 for r in st.reports):
                        return 2
        return None

    trainer = DataParallelTrainer(
        train_fn, scaling_config=ScalingConfig(num_workers=4),
        run_config=_run_cfg(tmp_path),
        scaling_policy=FunctionScalingPolicy(decide))
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["step"] == 5
    assert result.metrics["world"] == 2  # finished at the resized world size
    # the final checkpoint is sharded with 2 shards
    meta = result.checkpoint.get_metadata()
    assert meta.get("sharded") and meta["num_shards"] == 2


def test_async_checkpoint_writer(ray_start_regular, tmp_path):
    from ray_tpu.train import AsyncCheckpointWriter

    def train_fn(config):
        writer = AsyncCheckpointWriter()
        for step in range(3):
            def save(path, step=step):
                with open(os.path.join(path, "step.txt"), "w") as f:
                    f.write(str(step))
            writer.write_and_report(save, {"step": step})
        writer.finish()

    trainer = DataParallelTrainer(
        train_fn, scaling_config=ScalingConfig(num_workers=1),
        run_config=_run_cfg(tmp_path))
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["step"] == 2
    assert open(os.path.join(result.checkpoint.path, "step.txt")).read() == "2"

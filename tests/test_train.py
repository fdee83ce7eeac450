"""JaxTrainer end-to-end tests: 2-worker data-parallel training with
gradient allreduce over the cpu collective fake — the FashionMNIST-DDP
config shape at test scale."""

import os

import numpy as np
import pytest

import ray_tpu
from ray_tpu.train import (
    Checkpoint,
    CheckpointConfig,
    FailureConfig,
    JaxTrainer,
    RunConfig,
    ScalingConfig,
)


@pytest.fixture(scope="module")
def rt():
    ray_tpu.init(num_cpus=16)
    yield ray_tpu
    ray_tpu.shutdown()


def _dp_train_loop(config):
    """Runs inside each worker actor: tiny linear-regression DP training."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import ray_tpu.collective as collective
    from ray_tpu import train

    ctx = train.get_context()
    rank, world = ctx.get_world_rank(), ctx.get_world_size()

    rng = np.random.RandomState(42 + rank)  # different data per worker
    true_w = np.arange(1, 5, dtype=np.float64)
    X = rng.randn(64, 4)
    y = X @ true_w

    w = jnp.zeros(4, dtype=jnp.float64) if False else jnp.zeros(4)
    start = train.get_checkpoint()
    start_step = 0
    if start is not None:
        state = start.to_dict()
        w = jnp.asarray(state["w"])
        start_step = state["step"]

    def loss_fn(w):
        pred = X @ w
        return jnp.mean((pred - y) ** 2)

    grad_fn = jax.grad(loss_fn)
    lr = config["lr"]
    for step in range(start_step, config["steps"]):
        g = np.asarray(grad_fn(w))
        # DDP: average gradients across workers through the collective
        g = collective.allreduce(g, group_name=ctx.collective_group) / world
        w = w - lr * g
        if step % 5 == 4 or step == config["steps"] - 1:
            ckpt = Checkpoint.from_dict({"w": np.asarray(w), "step": step + 1})
            train.report({"loss": float(loss_fn(w)), "step": step}, checkpoint=ckpt)
    return float(loss_fn(w))


def test_jax_trainer_dp(rt, tmp_path):
    trainer = JaxTrainer(
        _dp_train_loop,
        train_loop_config={"lr": 0.1, "steps": 40},
        scaling_config=ScalingConfig(num_workers=2, collective_backend="cpu"),
        run_config=RunConfig(
            name="dp_test",
            storage_path=str(tmp_path / "ckpts"),
            checkpoint_config=CheckpointConfig(num_to_keep=2),
        ),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["loss"] < 1.0
    assert result.checkpoint is not None
    state = result.checkpoint.to_dict()
    np.testing.assert_allclose(state["w"], [1, 2, 3, 4], atol=0.5)
    # top-K retention
    assert len(os.listdir(tmp_path / "ckpts")) <= 2


def test_jax_trainer_single_worker(rt, tmp_path):
    def loop(config):
        from ray_tpu import train

        train.report({"answer": config["x"] * 2})
        return None

    trainer = JaxTrainer(
        loop,
        train_loop_config={"x": 21},
        scaling_config=ScalingConfig(num_workers=1, collective_backend="cpu"),
        run_config=RunConfig(storage_path=str(tmp_path / "c2")),
    )
    result = trainer.fit()
    assert result.metrics["answer"] == 42


def test_jax_trainer_worker_failure_restarts(rt, tmp_path):
    """FailureConfig path: worker 1 dies once, group restarts and resumes
    from the last checkpoint (ref: Train v2 FailurePolicy semantics).

    The ranks keep in step through the group's barrier, as a data-parallel
    loop does through its all-reduce: ``train.report`` holds nobody back, so
    without it rank 0 runs its six steps alone, and where rank 1 came to its
    crash a poll later (a busy host; here it always starts half a second
    late) the restart resumed from rank 0's LAST checkpoint, had nothing
    left to do and reported nothing."""
    marker = str(tmp_path / "crashed_once")

    def flaky_loop(config):
        import os
        import time

        import ray_tpu.collective as collective
        from ray_tpu import train
        from ray_tpu.train import Checkpoint

        ctx = train.get_context()
        start = train.get_checkpoint()
        step0 = start.to_dict()["step"] if start else 0
        if ctx.get_world_rank() == 1 and not os.path.exists(config["marker"]):
            time.sleep(0.5)
        for step in range(step0, 6):
            if step == 3 and ctx.get_world_rank() == 1 and not os.path.exists(config["marker"]):
                open(config["marker"], "w").close()
                os._exit(1)  # hard crash, not an exception
            collective.barrier(group_name=ctx.collective_group)
            ckpt = Checkpoint.from_dict({"step": step + 1})
            train.report({"step": step}, checkpoint=ckpt)
        return "done"

    trainer = JaxTrainer(
        flaky_loop,
        train_loop_config={"marker": marker},
        scaling_config=ScalingConfig(num_workers=2, collective_backend="cpu"),
        run_config=RunConfig(
            storage_path=str(tmp_path / "c3"),
            failure_config=FailureConfig(max_failures=2),
        ),
    )
    result = trainer.fit()
    assert result.error is None
    assert os.path.exists(marker)  # crash really happened
    assert result.metrics["step"] == 5  # and training still completed


def test_trainer_failure_exhausts(rt, tmp_path):
    def always_fails(config):
        raise RuntimeError("nope")

    trainer = JaxTrainer(
        always_fails,
        train_loop_config={},
        scaling_config=ScalingConfig(num_workers=1, collective_backend="cpu"),
        run_config=RunConfig(
            storage_path=str(tmp_path / "c4"),
            failure_config=FailureConfig(max_failures=1),
        ),
    )
    result = trainer.fit()
    assert result.error is not None


@pytest.mark.parametrize("dies, starts", [(1, 2), (99, 3)])
def test_worker_group_that_does_not_start_is_started_again(
        rt, tmp_path, monkeypatch, dies, starts):
    """A worker that dies in set-up (on a TPU host: a chip its last holder
    still has) has run no user code: the group is started again after each
    pause of ``_START_BACKOFF_S``, apart from ``max_failures`` (0 here), and
    a group that never starts is an error that says so."""
    from ray_tpu.train import trainer as trainer_mod

    counter = str(tmp_path / "setups")

    class DiesInSetup(trainer_mod.TrainWorker):
        def setup(self, checkpoint_path):
            import os

            with open(counter, "a") as f:
                f.write("x")
            if os.path.getsize(counter) <= dies:
                os._exit(1)  # as a failed backend start ends the worker
            return super().setup(checkpoint_path)

    monkeypatch.setattr(trainer_mod, "TrainWorker", DiesInSetup)
    monkeypatch.setattr(trainer_mod, "_START_BACKOFF_S", (0.05, 0.05))

    def loop(config):
        from ray_tpu import train

        train.report({"answer": 2 * config["x"]})

    result = JaxTrainer(
        loop,
        train_loop_config={"x": 21},
        scaling_config=ScalingConfig(num_workers=1, collective_backend="cpu"),
        run_config=RunConfig(storage_path=str(tmp_path / "c5")),
    ).fit()
    assert os.path.getsize(counter) == starts
    if dies < starts:
        assert result.error is None and result.metrics["answer"] == 42
    else:
        assert "did not start in 3 tries" in str(result.error)

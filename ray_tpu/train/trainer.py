"""JaxTrainer: controller + worker-group actors.

The reference's Train-v2 controller shape (ref: train/v2/_internal/execution/
controller/controller.py:93 run:469 — poll workers, apply FailurePolicy;
worker group ref: worker_group.py:105; v1 BackendExecutor ref:
_internal/backend_executor.py:146): a driver-side controller creates N
worker actors in a placement group, initializes the collective rendezvous
(GCS-KV -> jax.distributed on pods; named-actor CPU fake in tests), runs
``train_loop_per_worker`` on each, streams back report()s, keeps top-K
checkpoints, and restarts the whole group at the same world size on worker
failure up to FailureConfig.max_failures (elastic world-size changes imply
an XLA recompile, so group restart is the honest recovery unit —
SURVEY §7 "hard parts").
"""

from __future__ import annotations

import logging
import random
import time
import traceback
from typing import Any, Callable

import ray_tpu
from ray_tpu.core.ref import ActorError, GetTimeoutError, TaskError
from ray_tpu.train.checkpoint import Checkpoint, CheckpointManager
from ray_tpu.train.config import RunConfig, ScalingConfig
from ray_tpu.train.session import TrainContext, init_session
from ray_tpu.utils import tracing


log = logging.getLogger(__name__)

#: pauses before a worker group that did not come up is started again. Such
#: a group has run no user code, so these tries are apart from
#: FailureConfig.max_failures, which counts failures of the training loop
#: (the reference's controller_failure_limit beside max_failures). On a TPU
#: host the case is a chip its last holder has not let go of yet: libtpu
#: refuses the second process at once ("libtpu multi-process lockfile": the
#: worker's creation fails in seconds) or waits (set-up outlasts its limit).
_START_BACKOFF_S = (5.0, 10.0, 20.0)
_SETUP_TIMEOUT_S = 120.0


class TrainingFailedError(RuntimeError):
    pass


class Result:
    def __init__(self, metrics: dict, checkpoint: Checkpoint | None,
                 metrics_history: list[dict], error: Exception | None = None):
        self.metrics = metrics
        self.checkpoint = checkpoint
        self.metrics_history = metrics_history
        self.error = error

    def __repr__(self):
        return f"Result(metrics={self.metrics}, checkpoint={self.checkpoint})"


class TrainWorker:
    """Actor hosting one training process (one TPU host's worth of chips)."""

    def __init__(self, rank: int, world_size: int, trial_name: str, backend: str,
                 group_name: str):
        self.rank = rank
        self.world_size = world_size
        self.trial_name = trial_name
        self.backend = backend
        self.group_name = group_name
        self._done = False
        self._result: Any = None
        self._error: str | None = None
        self._session, self._stage = None, ("created", time.monotonic())

    def _stand_in(self, name: str):
        """A stage of ``setup``, to be entered with ``with``: timed as
        ``tracing.stage(name)``, and what ``setup_stage`` answers while it
        lasts and after it raised."""
        self._stage = (name, time.monotonic())
        return tracing.stage(name)

    def setup_stage(self) -> dict:
        """The stage of bring-up this worker stands in and for how long:
        ``created`` before ``setup``, ``set_up`` after it, else a name of
        ``tracing.STAGES``. Served while ``setup`` blocks (the actor runs
        two calls at a time): a group that does not start asks every worker
        (``JaxTrainer._where_workers_stand``)."""
        name, since = self._stage
        return {"stage": name, "seconds": time.monotonic() - since}

    def setup(self, checkpoint_path: str | None):
        import ray_tpu.collective as collective
        from ray_tpu.utils.device import configure_jax

        with self._stand_in("train_jax_import"):
            # a train worker runs jax (checkpoints alone need it): pay for
            # the import here, while the group is still in step, not inside
            # the loop's first report, where seconds of skew let one rank run
            # ahead. Imported before configure_jax() so that a worker pinned
            # to the CPU without it hears of its programs too
            import jax  # noqa: F401

            configure_jax()
        with self._stand_in("train_session"):
            ckpt = (Checkpoint.from_directory(checkpoint_path)
                    if checkpoint_path else None)
            context = TrainContext(
                world_rank=self.rank,
                world_size=self.world_size,
                local_rank=0,
                trial_name=self.trial_name,
                collective_group=self.group_name,
            )
            self._session = init_session(context, ckpt)
        if self.world_size > 1 or self.backend == "xla":
            with self._stand_in("train_collective"):
                collective.init_collective_group(
                    self.world_size, self.rank, backend=self.backend,
                    group_name=self.group_name,
                )
        self._stage = ("set_up", time.monotonic())
        return True

    def run(self, train_loop, config: dict):
        """Blocking execution of the user loop (runs on the actor's executor
        thread; poll() is served concurrently by the async loop)."""
        try:
            self._result = train_loop(config) if config is not None else train_loop()
            return {"ok": True}
        except Exception as e:  # noqa: BLE001
            self._error = f"{type(e).__name__}: {e}\n{traceback.format_exc()}"
            return {"ok": False, "error": self._error}
        finally:
            self._done = True

    def poll(self):
        """Drain report() outbox (ref: controller _poll_workers :249).
        _done is read BEFORE draining: a report enqueued between the drain
        and the done-check would otherwise be lost on the final poll."""
        done = self._done
        out = []
        if self._session is not None:
            while not self._session.outbox.empty():
                metrics, ckpt = self._session.outbox.get_nowait()
                out.append((metrics, ckpt.path if ckpt else None))
        return {"reports": out, "done": done, "error": self._error}


#: what a group that did not start waits for its workers to say where they
#: stand (``TrainWorker.setup_stage``), all of them together
_ASK_STAGE_TIMEOUT_S = 5.0


class JaxTrainer:
    def __init__(
        self,
        train_loop_per_worker: Callable,
        *,
        train_loop_config: dict | None = None,
        scaling_config: ScalingConfig | None = None,
        run_config: RunConfig | None = None,
        resume_from_checkpoint: Checkpoint | None = None,
        datasets: dict | None = None,
    ):
        self.train_loop = train_loop_per_worker
        self.train_loop_config = train_loop_config
        self.scaling = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.resume_from_checkpoint = resume_from_checkpoint
        self.datasets = datasets or {}

    # ------------------------------------------------------------------ fit
    def fit(self) -> Result:
        if not ray_tpu.is_initialized():
            ray_tpu.init()
        name = self.run_config.name or f"train_{int(time.time())}"
        storage = self.run_config.storage_path or f"/tmp/ray_tpu/{name}"
        ckpt_cfg = self.run_config.checkpoint_config
        manager = CheckpointManager(
            storage,
            num_to_keep=ckpt_cfg.num_to_keep,
            score_attribute=ckpt_cfg.checkpoint_score_attribute,
            score_order=ckpt_cfg.checkpoint_score_order,
        )
        max_failures = self.run_config.failure_config.max_failures
        attempt = 0
        history: list[dict] = []
        while True:
            try:
                metrics = self._run_attempt(name, attempt, manager, history)
                return Result(metrics, manager.latest(), history)
            except (ActorError, TaskError, TrainingFailedError) as e:
                attempt += 1
                if max_failures >= 0 and attempt > max_failures:
                    return Result(
                        history[-1] if history else {}, manager.latest(), history,
                        error=TrainingFailedError(str(e)),
                    )
                # elastic restart of the whole group (same world size);
                # backoff widens with consecutive failures so a node still
                # draining its last group isn't hammered at a fixed rate
                time.sleep(min(5.0, 0.5 * (2 ** (attempt - 1)))
                           * (0.5 + random.random()))

    def _start_group(self, name: str, attempt: int, manager: CheckpointManager):
        """Placement, the worker actors and their set-up: ``(pg, workers)``
        once every worker is set up. A worker whose creation failed or a
        set-up that outlasted its limit tears the group down and starts it
        again after each pause of ``_START_BACKOFF_S``; no placement is not
        tried again (waiting is what ``pg.ready`` did)."""
        scaling = self.scaling
        n = scaling.num_workers
        for tries in range(len(_START_BACKOFF_S) + 1):
            # a group's name is its rendezvous key: never one a dead try left
            group_name = f"{name}_g{attempt}" + (f"r{tries}" if tries else "")
            with tracing.stage("group_placement", workers=n):
                pg = ray_tpu.placement_group(
                    [scaling.worker_resources() for _ in range(n)],
                    strategy=scaling.placement_strategy,
                )
                placed = pg.ready(timeout=60)
            if not placed:
                ray_tpu.remove_placement_group(pg)
                raise TrainingFailedError(
                    f"no placement for {n} worker(s) of {scaling.worker_resources()} "
                    f"within 60s; the cluster has {ray_tpu.available_resources()} free")
            WorkerCls = ray_tpu.remote(TrainWorker)
            workers = []
            try:
                with tracing.stage("group_setup", workers=n):
                    workers += [
                        # per-worker bundle_index: options differ every iteration
                        WorkerCls.options(  # raylint: disable=RT009
                            num_cpus=scaling.worker_resources().get("CPU", 1.0),
                            resources={k: v for k, v in
                                       scaling.worker_resources().items()
                                       if k != "CPU"},
                            placement_group=pg,
                            placement_group_bundle_index=i,
                            # poll() and setup_stage() must be servable while
                            # run() or setup() blocks an executor thread
                            max_concurrency=2,
                        ).remote(i, n, name, scaling.backend(), group_name)
                        for i in range(n)
                    ]
                    resume = manager.latest() or self.resume_from_checkpoint
                    ray_tpu.get(
                        [w.setup.remote(resume.path if resume else None)
                         for w in workers],
                        timeout=_SETUP_TIMEOUT_S,
                    )
                return pg, workers
            except (ActorError, GetTimeoutError) as e:
                why = (f"{type(e).__name__}: {e}; "
                       f"{self._where_workers_stand(workers)}")
                self._stop_group(pg, workers)
                if tries == len(_START_BACKOFF_S):
                    raise TrainingFailedError(
                        f"the worker group did not start in {tries + 1} tries: "
                        f"{why}") from e
                log.warning("worker group %s did not start (%s): again in %g s",
                            group_name, why, _START_BACKOFF_S[tries])
                time.sleep(_START_BACKOFF_S[tries])
            except BaseException:
                self._stop_group(pg, workers)
                raise

    @staticmethod
    def _where_workers_stand(workers) -> str:
        """What each worker of a group that did not start says of itself
        (``TrainWorker.setup_stage``), in words: "worker 0 stood in
        train_collective for 118.2 s" — or, of one that gives no answer
        within ``_ASK_STAGE_TIMEOUT_S`` for all, that it was never created.
        Asking must not fail the start a second time."""
        refs = [w.setup_stage.remote() for w in workers]
        deadline = time.monotonic() + _ASK_STAGE_TIMEOUT_S
        said = []
        for rank, ref in enumerate(refs):
            try:
                # each answer alone: one worker's failure must not hide the
                # others' stages
                at = ray_tpu.get(  # raylint: disable=RT002
                    ref, timeout=max(0.1, deadline - time.monotonic()))
                said.append(f"worker {rank} stood in {at['stage']} for "
                            f"{at['seconds']:.1f} s")
            except Exception as e:  # raylint: disable=RT012 — a diagnosis, never a second failure
                said.append(f"worker {rank} was never created "
                            f"({type(e).__name__})")
        return ", ".join(said)

    @staticmethod
    def _stop_group(pg, workers) -> None:
        for w in workers:
            try:
                ray_tpu.kill(w)
            except Exception:  # raylint: disable=RT012 — teardown: worker may already be dead
                pass
        try:
            ray_tpu.remove_placement_group(pg)
        except Exception:  # raylint: disable=RT012 — teardown: PG dies with the cluster anyway
            pass

    def _run_attempt(self, name: str, attempt: int, manager: CheckpointManager,
                     history: list[dict]) -> dict:
        pg, workers = self._start_group(name, attempt, manager)
        try:
            run_refs = [
                w.run.remote(self.train_loop, self.train_loop_config) for w in workers
            ]
            final = self._poll_loop(workers, run_refs, manager, history)
            return final
        finally:
            self._stop_group(pg, workers)

    def _poll_loop(self, workers, run_refs, manager: CheckpointManager,
                   history: list[dict]) -> dict:
        """Controller loop (ref: TrainController.run :469)."""
        last_metrics: dict = {}
        pending = list(run_refs)
        while True:
            # surface early run() failures (submission/unpickling errors)
            # instead of polling a worker that never started
            done_now, _ = ray_tpu.wait(pending, num_returns=len(pending), timeout=0.01)
            for r in ray_tpu.get(done_now):
                if not r.get("ok"):
                    raise TrainingFailedError(r.get("error", "unknown"))
            polls = ray_tpu.get([w.poll.remote() for w in workers], timeout=60)
            for rank, poll in enumerate(polls):
                for metrics, ckpt_path in poll["reports"]:
                    metrics = {**metrics, "world_rank": rank}
                    history.append(metrics)
                    last_metrics = metrics
                    if ckpt_path and rank == 0:
                        manager.register(Checkpoint(ckpt_path), metrics)
                if poll["error"]:
                    raise TrainingFailedError(f"worker {rank}: {poll['error']}")
            if all(p["done"] for p in polls):
                results = ray_tpu.get(pending, timeout=60)
                for r in results:
                    if not r.get("ok"):
                        raise TrainingFailedError(r.get("error", "unknown"))
                return last_metrics
            time.sleep(0.05)

"""Bring-up from the inside (PR 50): ``tracing.stage`` and its one family
``rt_bringup_seconds``, the jax.monitoring listeners that count every
executable once (read from the persistent compile cache, compiled, or too
small for the cache to hold), the engine's ``program_builds`` records, and a
worker group that does not start saying where each worker stood. CPU."""
import asyncio
import logging
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.llm.engine import ContinuousBatchingEngine
from ray_tpu.models.llama import LlamaConfig, llama_init
from ray_tpu.utils import device, metrics, tracing

FAMILY = "rt_bringup_seconds"
PROGRAM_STAGES = ["program_trace", "program_lower", "program_cache_read",
                  "program_compile", "program_compile_small"]


def _counts() -> dict:
    table = metrics.family_totals(metrics.bringup_seconds)
    return {s: table.get(s, {}).get("count", 0) for s in tracing.STAGES}


def _grown(before: dict) -> dict:
    return {s: n - before[s] for s, n in _counts().items() if n != before[s]}


@pytest.fixture
def cache_dir(tmp_path):
    """A persistent compile cache of this test's own, with this process
    listening to what jax builds; the process's own settings come back."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    device.configure_jax()
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    yield tmp_path / "cache"
    for n, v in saved.items():
        jax.config.update(n, v)
    cc.reset_cache()


def _program(scale: float):
    """A program no other test has built."""
    return jax.jit(lambda x: jnp.tanh(x * scale) @ x.T + scale)


def test_an_executable_counts_once_compiled_then_once_read(cache_dir):
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    f, x = _program(50.25), jnp.ones((8, 8))
    before = _counts()
    f.lower(x).compile()
    first = _grown(before)
    assert first.get("program_compile") == 1
    assert "program_cache_read" not in first
    assert "program_compile_small" not in first
    assert first["program_trace"] >= 1 and first["program_lower"] >= 1
    jax.clear_caches()  # jit's own: the next build asks the directory
    before = _counts()
    f.lower(x).compile()
    second = _grown(before)
    assert second.get("program_cache_read") == 1
    assert "program_compile" not in second  # the retrieval, not counted twice
    assert "program_compile_small" not in second


def test_a_program_too_small_for_the_cache_enters_no_share(cache_dir):
    """The rule is jax's own: under the minimum compile time it holds NOW,
    nothing is written. The repo's minimum is 0.1 s, which a trivial compile
    can pass on a loaded test machine, so the rule is held to a minimum that
    no compile of this program reaches."""
    assert device._CACHE_MIN_COMPILE_SECS == 0.1
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 60.0)
    f, x = _program(50.5), jnp.ones((8, 8))
    before = _counts()
    f.lower(x).compile()
    grown = _grown(before)
    assert grown.get("program_compile_small") == 1
    assert "program_compile" not in grown and "program_cache_read" not in grown
    assert not list(cache_dir.glob("*"))  # and jax wrote nothing


def test_a_trace_inside_a_trace_counts_in_the_outer_one_alone(cache_dir):
    """jax times every jitted function it traces, the ones called while it
    traces another too: the outer one's seconds hold theirs."""
    inner = jax.jit(lambda x: jnp.cos(x) * 50.75)
    middle = jax.jit(lambda x: inner(x) + inner(x * 2))
    outer = jax.jit(lambda x: middle(x) @ middle(x).T)
    before = _counts()
    sums = metrics.family_totals(metrics.bringup_seconds)
    outer.lower(jnp.ones((8, 8)))
    assert _grown(before) == {"program_trace": 1, "program_lower": 1}
    traced = (metrics.family_totals(metrics.bringup_seconds)["program_trace"]
              ["sum"] - sums.get("program_trace", {}).get("sum", 0.0))
    assert 0 < traced < 5


def test_a_stage_counts_its_own_seconds_not_the_builds_inside_it(cache_dir):
    import time

    x = jnp.ones((8, 8))

    @jax.jit
    def long_program(x):  # some tenths of a second to build
        for i in range(40):
            x = jnp.tanh(x @ x.T + 51.25 + i)
        return x

    t0 = time.perf_counter()
    with tracing.stage("weights") as st:
        built = tracing._built_here()
        long_program.lower(x).compile()
        built = tracing._built_here() - built
    wall = time.perf_counter() - t0
    assert built > 0.05
    assert st.seconds == pytest.approx(wall - built, abs=0.4 * built)


def test_stage_of_an_unknown_name_raises():
    with pytest.raises(ValueError, match="not a stage of bring-up"):
        tracing.stage("warm_up")


def test_a_stage_lands_in_both_doors():
    before = _counts()["weights"]
    with tracing.stage("weights") as st:
        pass
    assert st.seconds >= 0.0
    assert metrics.bringup_seconds in metrics.STAGE_FAMILIES
    for table in (metrics.stage_totals()[FAMILY],
                  device.device_report()["bringup"]):
        assert table["weights"]["count"] == before + 1
        assert table["weights"]["sum"] >= st.seconds


def test_stage_shares_phases_body():
    """One implementation of the clock reads, the observe and the
    annotation: ``stage`` adds a name check and what to take off."""
    import inspect

    assert issubclass(tracing.stage, tracing.phase)
    for method in (tracing.stage.__enter__, tracing.stage.__exit__):
        source = inspect.getsource(method)
        assert "super()" in source and "perf_counter" not in source
        assert "observe" not in source
    assert tracing.stage.set is tracing.phase.set


def _window_metric(start: dict, end: dict):
    from benchmarks.readers import bringup_stage

    return bringup_stage.read(
        {"counters": {"start": {"stages": start}, "end": {"stages": end}}},
        stages=PROGRAM_STAGES, window=True)


def test_the_engine_says_where_each_program_came_from():
    device.configure_jax()
    jax.clear_caches()  # what an earlier test built here reads "memory"
    cfg = LlamaConfig.tiny()
    eng = ContinuousBatchingEngine(
        llama_init(jax.random.PRNGKey(0), cfg), cfg, max_batch=2, page_size=8,
        n_pages=32, max_seq_len=64, eos_id=None, block_buckets=(4,))

    async def serve(prompt, n):
        await eng.start()
        out = await eng.generate(prompt, max_tokens=n)
        await eng.stop()
        return out

    assert eng.program_builds() == []
    start = metrics.stage_totals()
    asyncio.run(serve(list(range(3, 9)), 5))
    builds = eng.program_builds()
    assert len(builds) == len(eng._compiled) >= 2
    for b in builds:
        assert b["program"] in ("paged_prefill_batch", "paged_decode_multi",
                                "merge_carry")
        assert b["shape"].startswith("(") and b["t"] > 0
        assert b["source"] in ("cache", "compiled")
        assert b["trace_s"] > 0 and b["lower_s"] > 0
        assert (b["cache_read_s"] > 0) == (b["source"] == "cache")
        assert (b["compile_s"] > 0) == (b["source"] == "compiled")
    assert [b["t"] for b in builds] == sorted(b["t"] for b in builds)
    # a program first used between two snapshots shows in the window's guard
    first_use = metrics.stage_totals()
    assert _window_metric(start, first_use) > 0
    # the same shapes again: no build, no record, and the guard reads 0
    asyncio.run(serve(list(range(3, 9)), 5))
    assert eng.program_builds() == builds
    assert _window_metric(first_use, metrics.stage_totals()) == 0


@pytest.fixture(scope="module")
def rt():
    import ray_tpu

    ray_tpu.init(num_cpus=8)
    yield ray_tpu
    ray_tpu.shutdown()


def _fit(tmp_path):
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    return JaxTrainer(
        lambda: None,
        scaling_config=ScalingConfig(num_workers=1, collective_backend="cpu"),
        run_config=RunConfig(storage_path=str(tmp_path / "ckpt")),
    ).fit()


def test_a_setup_that_times_out_names_the_stage_it_stood_in(
        rt, tmp_path, monkeypatch, caplog):
    from ray_tpu.train import trainer as trainer_mod

    class HangsInSession(trainer_mod.TrainWorker):
        def setup(self, checkpoint_path):
            import time

            with self._stand_in("train_session"):
                time.sleep(60)

    monkeypatch.setattr(trainer_mod, "TrainWorker", HangsInSession)
    monkeypatch.setattr(trainer_mod, "_SETUP_TIMEOUT_S", 3.0)
    monkeypatch.setattr(trainer_mod, "_START_BACKOFF_S", (0.05,))
    before = _counts()
    with caplog.at_level(logging.WARNING, logger=trainer_mod.__name__):
        result = _fit(tmp_path)
    error = str(result.error)
    assert "did not start in 2 tries" in error and "GetTimeoutError" in error
    stood = re.compile(r"worker 0 stood in train_session for \d+\.\d s")
    assert stood.search(error)
    warned = [r.getMessage() for r in caplog.records
              if "did not start" in r.getMessage()]
    assert len(warned) == 1
    assert stood.search(warned[0])
    # one observe a try: count - 1 is the retries
    assert _grown(before) == {"group_placement": 2, "group_setup": 2}


def test_a_worker_that_is_never_created_is_named(rt, tmp_path, monkeypatch):
    from ray_tpu.train import trainer as trainer_mod

    class NeverCreated(trainer_mod.TrainWorker):
        def __init__(self, *args):
            raise RuntimeError("no chip for you")

    monkeypatch.setattr(trainer_mod, "TrainWorker", NeverCreated)
    monkeypatch.setattr(trainer_mod, "_START_BACKOFF_S", ())
    error = str(_fit(tmp_path).error)
    assert "did not start in 1 tries" in error
    assert "worker 0 was never created" in error

"""The two readers of bring-up (``readers/bringup_stage.py``,
``readers/bringup_rest.py``) on hand-made runs: one as the serve driver
leaves it (snapshots of ``engine_stats()`` under ``counters``), one as the
train driver does (``device_report()["bringup"]`` alone), and a parent that
has neither."""
import pytest

from benchmarks.lib import configs
from benchmarks.readers import bringup_rest, bringup_stage

PROGRAM = ["program_trace", "program_lower", "program_cache_read",
           "program_compile", "program_compile_small"]


def _stages(**sums):
    return {"rt_bringup_seconds": {
        name: {"sum": s, "count": n} for name, (s, n) in sums.items()}}


SERVE = {
    "setup_s": 70.0, "trace": None,
    "counters": {
        "before": {"stages": _stages(weights=(9.0, 1)), "t": 99.0},
        "start": {"t": 100.0, "stages": _stages(
            backend_start=(11.0, 1), weights=(8.0, 1), weights_prepare=(0.5, 1),
            pools=(0.25, 1), program_trace=(12.0, 900), program_lower=(6.0, 60),
            program_cache_read=(9.0, 21), program_compile=(3.0, 1),
            program_compile_small=(2.0, 300), parts_table=(4.0, 22)),
            "program_builds": [
                {"program": "paged_decode_multi", "shape": "((8,), 4)",
                 "source": "cache", "trace_s": 0.5, "lower_s": 0.25,
                 "cache_read_s": 0.4, "compile_s": 0.0, "t": 60.0}]},
        "end": {"t": 151.0, "stages": _stages(
            backend_start=(11.0, 1), program_trace=(12.0, 900),
            program_lower=(6.0, 60), program_cache_read=(9.0, 21),
            program_compile=(3.0, 1), program_compile_small=(2.125, 301))},
    },
}
TRAIN = {
    "setup_s": 30.0, "trace": None,
    "device": {"platform": "tpu", "bringup": _stages(
        backend_start=(10.0, 1), train_jax_import=(0.001, 1),
        train_session=(0.001, 1), program_trace=(2.0, 40),
        program_lower=(1.0, 9), program_compile=(12.0, 3),
        program_compile_small=(1.0, 30))["rt_bringup_seconds"]},
}
PARENT = {"setup_s": 70.0, "trace": None, "device": {"platform": "tpu"},
          "counters": {"start": {"stages": {}}, "end": {"stages": {}}}}


def _metric(name: str, run: dict):
    spec = configs.load_json("layer_metrics", name + ".json")
    return configs.load_module("readers", spec["reader"]).read(
        run, **spec["args"])


@pytest.mark.parametrize("name, serve, train", [
    ("setup.backend_start_s", 11.0, 10.0),
    ("setup.weights_s", 8.75, 0),
    ("setup.program_trace_lower_s", 18.0, 3.0),
    ("setup.program_cache_read_s", 9.0, 0),
    ("setup.program_compile_s", 5.0, 13.0),
    ("setup.programs_from_cache_share", 100.0 * 21 / 22, 0.0),
    ("setup.parts_table_s", 4.0, 0),
    # 70 less every stage but the overlapped parts_table; 30 less all
    ("setup.unaccounted_s", 70.0 - 51.75, 30.0 - 26.002),
])
def test_set_up_metrics_read_the_earliest_snapshot(name, serve, train):
    assert _metric(name, SERVE) == pytest.approx(serve)
    assert _metric(name, TRAIN) == pytest.approx(train)
    assert _metric(name, PARENT) is None  # no family: nothing, no raise


def test_the_serial_stages_and_the_rest_add_up_to_set_up():
    named = sum(_metric(n, SERVE) for n in (
        "setup.backend_start_s", "setup.weights_s",
        "setup.program_trace_lower_s", "setup.program_cache_read_s",
        "setup.program_compile_s"))
    assert named + _metric("setup.unaccounted_s", SERVE) == pytest.approx(70.0)


def test_a_build_inside_the_window_shows_whoever_built_it():
    assert bringup_stage.read(SERVE, stages=PROGRAM, window=True
                              ) == pytest.approx(0.125)
    quiet = {"counters": {"start": SERVE["counters"]["end"],
                          "end": SERVE["counters"]["end"]}}
    assert bringup_stage.read(quiet, stages=PROGRAM, window=True) == 0
    assert bringup_stage.read(TRAIN, stages=PROGRAM, window=True) is None
    assert bringup_stage.read(PARENT, stages=PROGRAM, window=True) is None


def test_counts_and_an_empty_share():
    assert bringup_stage.read(SERVE, stages=["program_cache_read"],
                              count=True) == 21
    none_built = {"device": {"bringup": {}}}
    assert bringup_stage.read(none_built, stages=["program_cache_read"],
                              of=["program_cache_read", "program_compile"],
                              count=True) is None
    assert bringup_rest.read({"device": {"bringup": {}}},
                             overlapped=[]) is None  # no setup_s to take from


def test_a_traced_run_prints_the_table_once(capsys):
    run = {**SERVE, "trace": {"busy_s": 1.0}}
    assert _metric("setup.weights_s", run) == pytest.approx(8.75)
    assert _metric("setup.unaccounted_s", run) is not None
    out = capsys.readouterr().out
    assert out.count("[bench] bring-up: stage program_trace: 12.000 s in 900") == 1
    assert "program paged_decode_multi ((8,), 4): cache, trace 0.50" in out
    assert "ready 40.0 s before the window" in out
    assert "built in the window: 0.125 s (must be 0)" in out
    assert out.count("[bench] bring-up: setup.unaccounted_s = ") == 1
    assert "setup.parts_table_s = 4.0" in out

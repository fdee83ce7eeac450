"""Seconds (or, with ``count``, how many) of the named stages of bring-up
that the program timed itself: ``rt_bringup_seconds`` by
``ray_tpu/utils/tracing.py`` ``STAGES`` — the backend's start, weights,
pools, and of every program jax built its trace, lowering and either its
read from the persistent compile cache or its compile.

Without ``window``: the family's totals at the EARLIEST snapshot the run
holds, i.e. everything before the measured window — ``counters.start``
(a replica's ``engine_stats()["stages"]``), else ``counters.before``, else
``run["device"]["bringup"]`` (``device_report()``: a train worker has no
engine). With ``window``: their growth between ``counters.start`` and
``counters.end`` — a program built ANYWHERE in the process inside the
window, which the count of the engine's own programs cannot see; must be 0.
With ``of``: as a share, in %, of the same reading of those stages.

A program without the family (the parent of the PR that added it) reads as
nothing. The first read of a traced run prints the stage table, each
program's build record, what was built in the window and every
``layer_metrics/setup.*.json`` as ``[bench]`` lines, whether the cell
lists it or not."""
import glob
import os

from benchmarks.lib.configs import BENCH_DIR, load_json, load_module

FAMILY = "rt_bringup_seconds"


def _family(snapshot):
    return ((snapshot or {}).get("stages") or {}).get(FAMILY)


def totals(run: dict):
    """``{stage: {"sum", "count"}}`` before the window, or nothing."""
    c = run.get("counters") or {}
    for snapshot in (c.get("start"), c.get("before")):
        if _family(snapshot) is not None:
            return _family(snapshot)
    return (run.get("device") or {}).get("bringup")


def _sum(table: dict, stages: list, field: str) -> float:
    return sum(table.get(s, {}).get(field, 0) for s in stages)


def _describe(run: dict, table: dict) -> None:
    def say(line: str) -> None:
        print(f"[bench] bring-up: {line}", flush=True)

    for stage, v in sorted(table.items(), key=lambda kv: -kv[1]["sum"]):
        say(f"stage {stage}: {v['sum']:.3f} s in {v['count']}")
    start = (run.get("counters") or {}).get("start") or {}
    for b in start.get("program_builds") or ():
        say(f"program {b['program']} {b['shape']}: {b['source']}, trace "
            f"{b['trace_s']:.2f} lower {b['lower_s']:.2f} cache read "
            f"{b['cache_read_s']:.2f} compile {b['compile_s']:.2f} s, ready "
            f"{start['t'] - b['t']:.1f} s before the window")
    inside = read(run, [s for s in table if s.startswith("program_")],
                  window=True)
    if inside is not None:
        say(f"built in the window: {inside:.3f} s (must be 0)")
    for path in sorted(glob.glob(
            os.path.join(BENCH_DIR, "layer_metrics", "setup.*.json"))):
        spec = load_json("layer_metrics", os.path.basename(path))
        value = load_module("readers", spec["reader"]).read(run, **spec["args"])
        if value is not None:
            say(f"{spec['name']} = {value}")


def read(run: dict, stages: list, count: bool = False, of: list | None = None,
         window: bool = False):
    field = "count" if count else "sum"
    if window:
        c = run.get("counters") or {}
        a, b = _family(c.get("start")), _family(c.get("end"))
        if a is None or b is None:
            return None
        return _sum(b, stages, field) - _sum(a, stages, field)
    table = totals(run)
    if table is None:
        return None
    if run.get("trace") and not run.get("bringup_described"):
        run["bringup_described"] = True  # before the files read through here
        _describe(run, table)
    if of is None:
        return _sum(table, stages, field)
    whole = _sum(table, of, field)
    return 100.0 * _sum(table, stages, field) / whole if whole else None

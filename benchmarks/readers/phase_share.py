"""Share of the replica's wall time that its engine loop spent inside the
named host phases (``tracing.phase`` in ``llm/engine.py``), in %: growth of
their seconds in ``rt_llm_engine_phase_seconds`` between ``counters.before``
and ``counters.after`` over the seconds between those two snapshots' own
``t`` stamps (the replica's monotonic clock)."""
from benchmarks.readers.stage_mean_ms import stage_delta

FAMILY = "rt_llm_engine_phase_seconds"


def read(run: dict, phases: list):
    deltas = [stage_delta(run, FAMILY, p) for p in phases]
    if all(d is None for d in deltas):
        return None
    c = run["counters"]
    wall = c["after"]["t"] - c["before"]["t"]
    if wall <= 0:
        return None
    return 100.0 * sum(d["sum"] for d in deltas if d) / wall

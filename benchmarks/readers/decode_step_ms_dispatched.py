"""``readers/decode_step_ms.py`` with a first guess that cannot land between
two buckets. ``readers/decode_steps.py`` starts from the trace's decode time
over the steps the engine COUNTED between the span's two snapshots; the
counter moves when a block is synced, two blocks behind its dispatch, so a
span that opens behind two 64-step blocks counts 127 steps it did not run.
In this cell that read 496 steps where the trace holds 369 (blocks of 8 and
32 at 19 ms a step): a guess of 14 ms took every block for twice its steps —
9.4 ms a step and a roofline share of 127 % (my chip run, PR 31).

The sure source is the ``steps`` argument of the ``engine.decode_dispatch``
annotations in the run's own profiler trace (``readers/idle_by_phase.py``
reads the same file): paired by rank with the programs' durations they give
a step time a block, and the median of those is a guess that no block at
either edge of the span can move; from it the same settling on the
buckets. A trace without those annotations reads as nothing."""
import statistics

from benchmarks.readers.program_named import resolve

HOST_PLANE, DISPATCH = "/host:CPU", "engine.decode_dispatch"


def dispatched_steps(planes) -> list[int]:
    """The ``steps`` of every decode dispatch annotated on the host plane."""
    out = []
    for plane in planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == DISPATCH:
                    steps = dict(ev.stats).get("steps")
                    if steps is not None:
                        out.append(int(steps))
    return out


def _dispatched(run: dict) -> list[int]:
    """The run's dispatched steps, read once and kept on the run itself."""
    if "dispatched_steps" not in run:
        from benchmarks.readers.idle_by_phase import load_planes

        try:
            run["dispatched_steps"] = dispatched_steps(load_planes())
        except (FileNotFoundError, ImportError):
            run["dispatched_steps"] = []
    return run["dispatched_steps"]


def steps_and_seconds(run: dict, program: str):
    program = resolve(run, program)
    if program is None:
        return None
    durations = run["trace"]["programs"][program]["durations"]
    dispatched = _dispatched(run)
    if not durations or not dispatched:
        return None
    buckets = sorted({1, *run["counters"]["after"]["block_buckets"]})
    # the blocks at the span's two edges may be in one list and not in the
    # other: pair durations and dispatched steps by rank, take the median
    d_sorted, s_sorted = sorted(durations), sorted(dispatched)
    scale = (len(s_sorted) - 1) / max(1, len(d_sorted) - 1)
    guess = statistics.median(
        d / s_sorted[round(i * scale)] for i, d in enumerate(d_sorted))
    for _ in range(4):
        steps = sum(min(buckets, key=lambda b: abs(d / b - guess))
                    for d in durations)
        guess = sum(durations) / steps
    return steps, sum(durations)


def read(run: dict, program: str):
    got = steps_and_seconds(run, program)
    return None if got is None else 1e3 * got[1] / got[0]

"""Where the host was while the device idled: the part of the traced span
in which no program ran on the device AND the engine's loop thread was
inside one of the named host phases, in % of the span.

The channel is the run's own profiler trace: both drivers write it to
``<tempdir>/bench_trace`` when ``run["trace"]`` is set, and the program
mirrors every ``tracing.phase`` of ``llm/engine.py`` into it as a
``TraceAnnotation`` (``engine.admit``, ``engine.block_sync``, ...) on the
loop thread's line of the ``/host:CPU`` plane — the same file and the same
clock as the device's ``XLA Modules`` line. The device's idle intervals are
taken as ``lib/xplane.py`` takes its idle gaps: the complement of the union
of the ``XLA Modules`` events on each device plane, averaged over the chips.

``phases: null`` is the rest: the cell's whole idle time, as
``readers/idle_share.py`` counts it (traced span less the union of the
device's operations), less the idle inside the six phases of the dispatch
path (``LOOP_PHASES``) — idle inside no phase, inside ``engine.yield`` (the
loop's turns for the replica's other coroutines), ``engine.idle`` or
``engine.compile``, between operations inside a program, and at the span's
two edges. So the four ``device.idle_*`` metrics of a cell add up to its
``device.idle_share.*``. A trace without ``engine.*`` annotations (the
program before it had them) reads as nothing.

The first read of a trace prints the whole table as ``[bench]`` lines:
idle seconds by phase, and by phase and the programs on either side."""
import bisect
import os
import tempfile

from benchmarks.lib.xplane import (DEVICE_PLANE, MODULES_LINE, find_xplane,
                                   program_name)

HOST_PLANE, PREFIX, NO_PHASE = "/host:CPU", "engine.", "(no phase)"
LOOP_PHASES = ("engine.free", "engine.admit", "engine.prefill_sync",
               "engine.decode_dispatch", "engine.block_sync", "engine.emit")


def _seconds(ev) -> tuple[float, float]:
    return ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9


def phase_intervals(planes) -> list[tuple[float, float, str]]:
    """(start, end, name) of every ``engine.*`` annotation on the host
    plane, sorted. One loop thread opens them and never two at once."""
    out = []
    for plane in planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            out += [(*_seconds(ev), ev.name) for ev in line.events
                    if ev.name.startswith(PREFIX)]
    return sorted(out)


def idle_gaps(planes) -> list[list[tuple[float, float, str]]]:
    """Per device plane: (start, end, "<program before>_-_<program after>")
    of every interval between two programs in which none ran."""
    chips = []
    for plane in planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        mods = sorted((*_seconds(ev), program_name(ev.name))
                      for line in plane.lines if line.name == MODULES_LINE
                      for ev in line.events)
        if not mods:
            continue
        gaps, end, last = [], None, None
        for s, e, name in mods:
            if end is not None and s > end:
                gaps.append((end, s, f"{last}_-_{name}"))
            if end is None or e > end:
                end, last = e, name
        chips.append(gaps)
    return chips


def idle_table(planes) -> dict | None:
    """Idle seconds between programs, by the phase the host was in and by
    (phase, programs on either side), averaged over the chips; nothing if
    the trace holds no device plane or no annotation."""
    phases, chips = phase_intervals(planes), idle_gaps(planes)
    if not phases or not chips:
        return None
    starts = [p[0] for p in phases]
    by_phase: dict[str, float] = {}
    by_pair: dict[tuple[str, str], float] = {}

    def add(name, programs, seconds):
        by_phase[name] = by_phase.get(name, 0.0) + seconds / len(chips)
        key = (name, programs)
        by_pair[key] = by_pair.get(key, 0.0) + seconds / len(chips)

    for gaps in chips:
        for s, e, programs in gaps:
            covered = 0.0
            # the annotations that can overlap [s, e): from the last one
            # starting at or before s onwards
            i = max(0, bisect.bisect_right(starts, s) - 1)
            while i < len(phases) and phases[i][0] < e:
                a, b, name = phases[i]
                lap = min(e, b) - max(s, a)
                if lap > 0:
                    add(name, programs, lap)
                    covered += lap
                i += 1
            if e - s > covered:
                add(NO_PHASE, programs, e - s - covered)
    return {"by_phase": by_phase, "by_pair": by_pair,
            "phase_seconds": {n: sum(e - s for s, e, m in phases if m == n)
                              for n in {p[2] for p in phases}}}


def load_planes() -> list:
    from jax.profiler import ProfileData

    path = find_xplane(os.path.join(tempfile.gettempdir(), "bench_trace"))
    return list(ProfileData.from_file(path).planes)  # a one-shot iterator


def _table(run: dict):
    """The run's table, made once and kept on the run itself."""
    if "idle_by_phase" not in run:
        try:
            table = idle_table(load_planes())
        except (FileNotFoundError, ImportError):
            table = None
        run["idle_by_phase"] = table
        if table:
            span = max(run["trace_span_s"], run["trace"]["window_s"])
            for name, s in sorted(table["phase_seconds"].items()):
                print(f"[bench] phase {name}: {s:.4f}s of the {span:.3f}s span, "
                      f"device idle inside it "
                      f"{table['by_phase'].get(name, 0.0):.5f}s", flush=True)
            for (name, programs), s in sorted(table["by_pair"].items(),
                                              key=lambda kv: -kv[1])[:24]:
                print(f"[bench] idle {s:.5f}s in {name} between {programs}",
                      flush=True)
    return run["idle_by_phase"]


def read(run: dict, phases: list | None):
    trace = run.get("trace")
    if not trace or not trace["busy_s"] or not run.get("trace_span_s"):
        return None
    table = _table(run)
    if not table:
        return None
    span = max(run["trace_span_s"], trace["window_s"])
    inside = sum(table["by_phase"].get(p, 0.0) for p in phases or LOOP_PHASES)
    if phases is None:
        return 100.0 * (span - trace["busy_s"] - inside) / span
    return 100.0 * inside / span

"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to what the
per-layer readers use: the traced window, the device's busy time (union of
the intervals in which an operation ran, averaged over the chips), time per
jitted program, time per device operation, and the longest idle gaps named
by the programs on either side. Read with nothing but jax's ``ProfileData``."""
from __future__ import annotations

import functools
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
CONTAINERS = ("while", "conditional", "call")
_SUFFIX = re.compile(r"[.(]\d+\)?$")


def find_xplane(path: str) -> str:
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return files[-1]


def _union(intervals: list[tuple[float, float]]) -> float:
    busy, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        busy += e - max(s, end)
        end = e
    return busy


def program_name(event_name: str) -> str:
    """``jit_paged_decode_multi(1234567)`` -> ``jit_paged_decode_multi``."""
    return _SUFFIX.sub("", event_name)


_SHAPE = re.compile(r"(pred|[a-z]+\d+)\[([\d,]*)\]")


def op_name(event) -> str:
    return _op_key(event.name)


@functools.lru_cache(maxsize=None)
def _op_key(instruction: str) -> str:
    """An operation's name, steady from build to build: the trace names an
    event by its whole HLO instruction (``%fusion.16 = bf16[16,128]{...}
    fusion(...)``); kept are the instruction's name with its number taken
    off and the first array shape of its result. Cached: a ten-second trace
    holds some 300,000 events of a few hundred instructions."""
    lhs, _, rhs = instruction.partition(" = ")
    name = _SUFFIX.sub("", lhs.strip().lstrip("%"))
    if 'custom_call_target="tpu_custom_call"' in rhs:
        name = "pallas:" + name  # a Pallas kernel, whatever jax named the call
    m = _SHAPE.search(rhs.split("(", 1)[0] if not rhs.startswith("(") else rhs)
    if m:
        name += ":" + m.group(1) + "".join("_" + d for d in m.group(2).split(",") if d)
    return name


def reduce_planes(planes) -> dict:
    """``planes``: objects with ``.name`` and ``.lines``; a line has ``.name``
    and ``.events``; an event ``.name``, ``.start_ns``, ``.duration_ns`` and
    ``.stats``. Kept apart from the file reading so that a test can feed it a
    trace written down by hand."""
    busy, windows = [], []
    programs: dict[str, list[float]] = {}
    ops: dict[str, float] = {}
    gaps: dict[str, float] = {}
    for plane in planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        spans = []
        for ev in lines[OPS_LINE].events:
            s, e = ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9
            spans.append((s, e))
            key = op_name(ev)
            if key.split(":")[0] in CONTAINERS:
                continue  # its body's operations are on the line themselves
            ops[key] = ops.get(key, 0.0) + (e - s)
        if not spans:
            continue
        busy.append(_union(spans))
        windows.append((min(s for s, _ in spans), max(e for _, e in spans)))
        mods = sorted((ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
                       program_name(ev.name))
                      for ev in (lines[MODULES_LINE].events
                                 if MODULES_LINE in lines else ()))
        for s, e, name in mods:
            c = programs.setdefault(name, [0, 0.0, []])
            c[0] += 1
            c[1] += e - s
            c[2].append(e - s)
        end, last = None, None
        for s, e, name in mods:
            if end is not None and s > end:
                key = f"{last}_-_{name}"
                gaps[key] = gaps.get(key, 0.0) + (s - end)
            if end is None or e > end:
                end, last = e, name
    if not busy:
        return {"busy_s": 0.0, "window_s": 0.0, "chips": 0, "programs": {},
                "ops": [], "idle_gaps": []}
    top = sorted(ops.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": sum(e - s for s, e in windows) / len(windows),
        "chips": len(busy),
        "programs": {k: {"count": c, "seconds": s, "durations": d[:2000]}
                     for k, (c, s, d) in programs.items()},
        "ops": [[k, v] for k, v in top[:400]],
        "idle_gaps": [[k, v] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:10]],
    }


def describe(path: str, limit: int = 12) -> str:
    """Planes, lines and a few events with their stats: for looking at one
    trace by hand before trusting a reader."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(find_xplane(path)).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  line {line.name!r}: {len(events)} events")
            for ev in events[:limit]:
                out.append(f"    {ev.name!r} {ev.duration_ns}ns "
                           f"{dict(list(ev.stats)[:8])}")
    return "\n".join(out)


def reduce_trace(path: str) -> dict:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(find_xplane(path)).planes)


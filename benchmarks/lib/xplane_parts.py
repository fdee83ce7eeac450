"""Device time by layer part: a profiler trace's operations joined to the
program's own table of which part of a layer each of its instructions
belongs to (``ray_tpu/utils/tracing.py`` ``PARTS``; the table is
``engine_stats()["program_parts"]``, sent only while a trace is on).

The profiler drops a ``jax.named_scope`` from the device's events but names
each event by its whole HLO instruction, and the compiled program's text
keeps the scope on the same instruction. So the join key is ``<instruction
name>|<first array shape of its result>`` within the program whose ``XLA
Modules`` event holds the operation in time. What finds no part — no table
for the program, a stale one (an executable out of a compile cache written
without the scopes), an instruction the compiler made and named nothing, a
key two shape variants of the program give different parts (``?``) — is
``unnamed``, never another part's. A program that sent no table at all (the
tree before the scopes) reads as nothing."""
from __future__ import annotations

import bisect
import functools
import re

from benchmarks.lib.xplane import (CONTAINERS, DEVICE_PLANE, MODULES_LINE,
                                   OPS_LINE, _op_key, program_name)

UNNAMED, NO_PROGRAM = "unnamed", "(no program)"
# ``tracing.instruction_key``'s shape, written again: these files also run
# over the tree before it, which has no such function
_SHAPE = re.compile(r"(pred|[a-z]+\d+)\[[\d,]*\]")
_NUMBER = re.compile(r"\.\d+$")


@functools.lru_cache(maxsize=None)
def event_key(instruction: str) -> tuple[str, bool]:
    """(join key, whether the event is a container whose body's operations
    are on the line themselves) of a device event's name: ``%fusion.16 =
    bf16[16,128]{1,0:T(8,128)(2,1)} fusion(...)`` -> ``fusion.16|bf16[16,
    128]``. Cached: a trace holds a few hundred instructions many times."""
    lhs, _, rhs = instruction.partition(" = ")
    name = lhs.strip().removeprefix("ROOT ").lstrip("%")
    m = _SHAPE.search(rhs.split("(", 1)[0] if not rhs.startswith("(") else rhs)
    return (f"{name}|{m.group(0) if m else ''}",
            _NUMBER.sub("", name) in CONTAINERS)


def part_seconds(planes, tables: dict) -> dict:
    """``{(program, part): seconds}`` over the device planes, averaged over
    the chips, and the largest operations that found no part.

    ``planes`` as ``lib/xplane.py`` ``reduce_planes`` takes them; ``tables``:
    ``{program: {"parts": {key: part}, "stale": bool}}``."""
    seconds: dict[tuple[str, str], float] = {}
    unnamed: dict[tuple[str, str], float] = {}
    chips = 0
    for plane in planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        chips += 1
        mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                       program_name(ev.name))
                      for ev in (lines[MODULES_LINE].events
                                 if MODULES_LINE in lines else ()))
        starts = [m[0] for m in mods]
        for ev in lines[OPS_LINE].events:
            key, container = event_key(ev.name)
            if container:
                continue
            i = bisect.bisect_right(starts, ev.start_ns) - 1
            program = (mods[i][2] if i >= 0 and ev.start_ns < mods[i][1]
                       else NO_PROGRAM)
            table = tables.get(program)
            part = None
            if table and not table.get("stale"):
                part = table["parts"].get(key)
            where = (program, part or UNNAMED)
            seconds[where] = seconds.get(where, 0.0) + ev.duration_ns * 1e-9
            if part is None:
                op = (program, _op_key(ev.name))
                unnamed[op] = unnamed.get(op, 0.0) + ev.duration_ns * 1e-9
    if not chips:
        return {"seconds": {}, "unnamed_ops": []}
    return {"seconds": {k: v / chips for k, v in seconds.items()},
            "unnamed_ops": [[*k, v / chips] for k, v in
                            sorted(unnamed.items(), key=lambda kv: -kv[1])[:10]]}


def describe(result: dict, tables: dict, trace: dict) -> list[str]:
    """The whole program x part table as lines: a program's parts in order of
    their seconds, their sum beside the program's seconds on ``XLA
    Modules``, the tables' size and cost, the largest unnamed operations."""
    out = []
    by_program: dict[str, list] = {}
    for (program, part), s in result["seconds"].items():
        by_program.setdefault(program, []).append((s, part))
    busy = trace["busy_s"] or float("nan")
    for program, rows in sorted(by_program.items(),
                                key=lambda kv: -sum(s for s, _ in kv[1])):
        total = sum(s for s, _ in rows)
        module = trace["programs"].get(program, {}).get("seconds")
        table = tables.get(program, {})
        out.append(
            f"parts of {program}: {total:.4f}s in operations"
            + (f", {module:.4f}s on XLA Modules" if module else "")
            + (f"; table of {len(table.get('parts', {}))} instructions over "
               f"{table.get('variants')} variants made in "
               f"{1e3 * table.get('seconds', 0.0):.1f}ms" if table else
               "; NO TABLE")
            + ("; STALE (a compile cache's executable without the scopes)"
               if table.get("stale") else ""))
        for s, part in sorted(rows, reverse=True):
            out.append(f"  part {program} {part}: {s:.4f}s "
                       f"{100 * s / busy:.2f}% of busy")
    for program, op, s in result["unnamed_ops"]:
        out.append(f"  unnamed {program} {op}: {s:.4f}s {100 * s / busy:.2f}% "
                   f"of busy")
    return out

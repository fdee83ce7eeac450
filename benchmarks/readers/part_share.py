"""Device time of the operations that belong to one of ``parts`` of a layer
(``ray_tpu/utils/tracing.py`` ``PARTS``; ``unnamed`` and ``?`` for what the
vocabulary does not reach), in the programs whose names hold one of
``programs`` (all of them when left out), as a share of the device's busy
time — ``op_share``'s denominator.

The table that says which instruction is which part travels with the
counters taken while the profiler is on (``run["counters"]["after"]
["program_parts"]``); the operations are read from the run's own trace, the
file ``readers/idle_by_phase.py`` opens. A run whose program sent no table
(the tree before it had the scopes) reads as nothing. So does a named part
where one of the programs asked for ran stale (its executable came out of a
compile cache written without the scopes, so its operations are all
``unnamed``): the part's share is then not known, and 0 would be wrong.

The first read of a trace prints the whole program x part table and the ten
largest unnamed operations as ``[bench]`` lines."""
from benchmarks.lib.xplane_parts import UNNAMED, describe, part_seconds
from benchmarks.readers.idle_by_phase import load_planes


def _table(run: dict):
    """The run's seconds by (program, part), made once and kept on the run."""
    if "part_seconds" not in run:
        tables = ((run.get("counters") or {}).get("after") or {}
                  ).get("program_parts")
        result = None
        if tables:
            try:
                result = part_seconds(load_planes(), tables)
            except (FileNotFoundError, ImportError):
                result = None
        run["part_seconds"] = result
        if result:
            result["stale"] = {p for p, t in tables.items() if t.get("stale")}
            for line in describe(result, tables, run["trace"]):
                print(f"[bench] {line}", flush=True)
    return run["part_seconds"]


def read(run: dict, parts: list, programs: list | None = None):
    trace = run.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    table = _table(run)
    if not table or not table["seconds"]:
        return None
    mine = {(program, part): s for (program, part), s in
            table["seconds"].items()
            if programs is None or any(p in program for p in programs)}
    if UNNAMED not in parts and any(
            program in table["stale"] for program, _ in mine):
        return None
    return 100.0 * sum(s for (_, part), s in mine.items() if part in parts
                       ) / trace["busy_s"]

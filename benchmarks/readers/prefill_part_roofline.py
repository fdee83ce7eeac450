"""``readers/prefill_roofline_admitted.py`` for some PARTS of a prefill
program: the least time ``roofline/<count>.py``'s function ``least`` gives
for the prompts the traced span admitted (the ``engine.admit`` annotations'
true lengths, as that reader takes them), over the device time of the
operations of ``program`` that belong to one of ``parts`` (``lib/
xplane_parts.py``'s seconds by part, ``readers/part_share.py``'s table) — a
layer part's share of ITS roofline. A trace without the annotation, the
program, the part table or those parts reads as nothing."""
from benchmarks.lib.configs import load_module
from benchmarks.readers.part_share import _table
from benchmarks.readers.prefill_roofline_admitted import _admitted


def read(run: dict, count: str, program: str, parts: list, least: str):
    trace = run.get("trace")
    if not trace or program not in trace["programs"]:
        return None
    table = _table(run)
    if not table or any(program in p for p in table["stale"]):
        return None
    took = sum(s for (prog, part), s in table["seconds"].items()
               if program in prog and part in parts)
    lens = _admitted(run) if took else None
    if not lens:
        return None
    fn = getattr(load_module("roofline", count), least)
    return 100.0 * fn(run["cfg"], run["peaks"], lens) / took

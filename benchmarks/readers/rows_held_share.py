"""What a cache of two kinds of pages holds, as a share of a cache with a row
for every position: rows drawn of both kinds (``rt_llm_pages_drawn_total``
under ``kinds``, times the page size) over the positions they stand for — the
pages drawn of the strided kind ``of``, whose every row stands for the
configuration's ``chunk_size`` positions and which a slot draws for its whole
prompt and reply — between ``counters.before`` and ``counters.after``, in %.
A program without the tagged counter reads as nothing."""
from benchmarks.readers.stage_mean_ms import stage_delta

DRAWN = "rt_llm_pages_drawn_total"


def read(run: dict, kinds: list, of: str):
    drawn = {k: stage_delta(run, DRAWN, k) for k in kinds}
    if None in drawn.values() or drawn[of]["sum"] <= 0:
        return None
    stride = run["cfg"].chunk_size
    return 100.0 * sum(d["sum"] for d in drawn.values()) / (
        drawn[of]["sum"] * stride)

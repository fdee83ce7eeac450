"""Growth of one of the program's counters that sums slots over decode steps
(``counter_ratio``'s channel: ``stage_delta``), as a share in percent of the
slot-steps there were between the two snapshots: the engine's own ``steps``
counter times its ``max_batch``. A program without the counter reads as
nothing."""
from benchmarks.readers.stage_mean_ms import stage_delta


def read(run: dict, num: str):
    a = stage_delta(run, num)
    if a is None:
        return None
    c = run["counters"]
    counted = c["after"]["steps"] - c["before"]["steps"]
    if counted <= 0:
        return None
    return 100.0 * a["sum"] / (counted * run["engine"]["max_batch"])

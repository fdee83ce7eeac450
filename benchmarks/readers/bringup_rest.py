"""What of ``setup_s`` no stage of bring-up names, in s: the run's
``setup_s`` less every stage the program timed before the window
(``readers/bringup_stage.py`` ``totals``) except those in ``overlapped``,
which ran beside another (``parts_table``: in its own thread, while the
program's first run held the device). The rest is the runtime's start, the
lease and the worker's spawn, the programs' first runs and the harness's
reference check — the guard of the stage table, as ``kernel.unnamed_share.*``
is of the part table: it grows when set-up gains work that nobody named."""
from benchmarks.readers.bringup_stage import totals


def read(run: dict, overlapped: list):
    table = totals(run)
    if table is None or "setup_s" not in run:
        return None
    return run["setup_s"] - sum(v["sum"] for stage, v in table.items()
                                if stage not in overlapped)

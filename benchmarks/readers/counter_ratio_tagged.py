"""``readers/counter_ratio.py`` between two TAGGED samples of the program's
counters (one family or two): growth of ``num`` under ``num_tag`` over growth
of ``den`` under ``den_tag`` between ``counters.before`` and
``counters.after``, times ``scale``."""
from benchmarks.readers.stage_mean_ms import stage_delta


def read(run: dict, num: str, num_tag: str, den: str, den_tag: str,
         scale: float = 1.0):
    a, b = stage_delta(run, num, num_tag), stage_delta(run, den, den_tag)
    if a is None or b is None or b["sum"] <= 0:
        return None
    return scale * a["sum"] / b["sum"]

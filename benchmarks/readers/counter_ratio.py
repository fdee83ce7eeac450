"""Growth of one of the program's counters over growth of another between
``counters.before`` and ``counters.after`` (families of
``engine_stats()["stages"]``, see ``stage_mean_ms``), times ``scale``;
``one_minus`` gives the complement first: 1 - true / padded is the share of
the prefill programs' rows x pad that was padding."""
from benchmarks.readers.stage_mean_ms import stage_delta


def read(run: dict, num: str, den: str, one_minus: bool = False,
         scale: float = 1.0):
    a, b = stage_delta(run, num), stage_delta(run, den)
    if a is None or b is None or b["sum"] <= 0:
        return None
    ratio = a["sum"] / b["sum"]
    return scale * (1.0 - ratio if one_minus else ratio)

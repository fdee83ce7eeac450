"""The largest expert's load over the mean expert's, a decode step a layer:
growth of ``rt_llm_moe_max_load_total`` (the largest load, summed over layers
and steps) over growth of ``rt_llm_moe_assignments_total`` / experts held (the
mean load, summed over the same layers and steps). 1 is an even spread; 32
tokens x 6 over 128 experts, drawn evenly, read about 4."""
from benchmarks.readers.stage_mean_ms import stage_delta


def read(run: dict):
    top = stage_delta(run, "rt_llm_moe_max_load_total")
    rows = stage_delta(run, "rt_llm_moe_assignments_total")
    if top is None or rows is None or rows["sum"] <= 0:
        return None
    lo, hi = run["cfg"].held
    return (hi - lo) * top["sum"] / rows["sum"]

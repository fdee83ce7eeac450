"""``readers/swa_moe_decode_roofline.py`` for the learned-sparse-attention
expert family: the least time the chip could take for the decode steps of the
traced span (``roofline/<count>.py``) as a share of the device time they took
— the whole program's (``patterns`` None), or that of its kernels whose
operation names hold ``pallas:`` and one of ``kernels``. Everything the count
needs the program counts itself between the span's two snapshots: positions
the indexer scored, rows in the selected sets, held experts touched, rows
routed to them. A program without those counters reads as nothing."""
from benchmarks.lib.configs import load_module
from benchmarks.readers.decode_step_ms_dispatched import steps_and_seconds
from benchmarks.readers.stage_mean_ms import stage_delta


def per_step(run: dict):
    """(positions scored, rows attended — both over slots and layers — held
    experts touched a layer, rows routed to held experts a layer) a decode
    step, or nothing."""
    c = run.get("counters") or {}
    scored = stage_delta(run, "rt_llm_sparse_positions_scored_total")
    attended = stage_delta(run, "rt_llm_sparse_rows_attended_total")
    hit = stage_delta(run, "rt_llm_moe_experts_touched_total")
    slots = stage_delta(run, "rt_llm_moe_expert_slots_total")
    rows = stage_delta(run, "rt_llm_moe_assignments_total")
    if None in (scored, attended, hit, slots, rows) or slots["sum"] <= 0:
        return None
    steps = c["after"]["steps"] - c["before"]["steps"]
    if steps <= 0:
        return None
    lo, hi = run["cfg"].held
    return (scored["sum"] / steps, attended["sum"] / steps,
            (hi - lo) * hit["sum"] / slots["sum"],
            rows["sum"] / steps / run["cfg"].n_layers)


def read(run: dict, program: str, count: str, kernels: list | None = None):
    got, counted = steps_and_seconds(run, program), per_step(run)
    if got is None or counted is None:
        return None
    steps, seconds = got
    module = load_module("roofline", count)
    slots = run["engine"]["max_batch"]
    if kernels is None:
        least = module.least_seconds(run["cfg"], run["peaks"], slots, *counted)
    else:  # the attention's two kernels alone: their own time, their own count
        seconds = sum(s for name, s in run["trace"]["ops"]
                      if "pallas:" in name and any(k in name for k in kernels))
        if not seconds:
            return None
        least = module.least_seconds(run["cfg"], run["peaks"], slots,
                                     *counted[:2])
    return 100.0 * steps * least / seconds

"""``readers/kda_moe_decode_roofline.py`` for the compressed-latent convolved
attention family's decode program: the least time the chip could take for the
decode steps of the traced span (``roofline/<count>.py``) as a share of the
device time they took. Everything the count needs the program counts itself
between the span's two snapshots: rows updated
(``rt_llm_cca_row_updates_total``), live positions of the kv kind
(``rt_llm_decode_kv_tokens_live_total``), held experts touched, rows routed
to them. A program without those counters (the tree before it had them)
reads as nothing."""
from benchmarks.lib.configs import load_module
from benchmarks.readers.decode_step_ms_dispatched import steps_and_seconds
from benchmarks.readers.stage_mean_ms import stage_delta


def per_step(run: dict):
    """(rows updated, live positions, held experts touched a layer, rows
    routed to held experts a layer) a decode step, or nothing."""
    c = run.get("counters") or {}
    updates = stage_delta(run, "rt_llm_cca_row_updates_total")
    reach = stage_delta(run, "rt_llm_decode_kv_tokens_live_total")
    hit = stage_delta(run, "rt_llm_moe_experts_touched_total")
    slots = stage_delta(run, "rt_llm_moe_expert_slots_total")
    rows = stage_delta(run, "rt_llm_moe_assignments_total")
    if None in (updates, reach, hit, slots, rows) or slots["sum"] <= 0:
        return None
    steps = c["after"]["steps"] - c["before"]["steps"]
    if steps <= 0:
        return None
    lo, hi = run["cfg"].held
    layers = slots["sum"] / steps / (hi - lo)
    return (updates["sum"] / steps, reach["sum"] / steps,
            (hi - lo) * hit["sum"] / slots["sum"], rows["sum"] / steps / layers)


def read(run: dict, program: str, count: str):
    got, counted = steps_and_seconds(run, program), per_step(run)
    if got is None or counted is None:
        return None
    steps, seconds = got
    least = load_module("roofline", count).least_seconds(
        run["cfg"], run["peaks"], run["engine"]["max_batch"], *counted)
    return 100.0 * steps * least / seconds

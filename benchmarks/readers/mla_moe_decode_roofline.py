"""``readers/decode_roofline.py`` for the MLA + sparse-expert decode program:
the least time the chip could take for the decode steps of the traced span
(``roofline/mla_moe_decode_multi.py``) as a share of the device time they
took. The count needs what the existing reader cannot pass: the routed
experts the steps TOUCHED, which the program counts itself
(``rt_llm_moe_experts_touched_total`` over ``rt_llm_moe_expert_slots_total``
between the span's two snapshots). A program without those counters reads as
nothing."""
from benchmarks.lib import stats
from benchmarks.lib.configs import load_module
from benchmarks.readers.decode_steps import steps_and_seconds
from benchmarks.readers.stage_mean_ms import stage_delta


def experts_touched(run: dict):
    """Mean distinct routed experts a decode step a layer, or nothing."""
    hit = stage_delta(run, "rt_llm_moe_experts_touched_total")
    slots = stage_delta(run, "rt_llm_moe_expert_slots_total")
    if hit is None or slots is None or slots["sum"] <= 0:
        return None
    lo, hi = run["cfg"].held
    return (hi - lo) * hit["sum"] / slots["sum"]


def read(run: dict, program: str, count: str):
    got = steps_and_seconds(run, program)
    touched = experts_touched(run)
    if got is None or touched is None or not run.get("trace_window"):
        return None
    steps, seconds = got
    live = stats.live_kv_tokens(run["recs_all"], *run["trace_window"])
    least = load_module("roofline", count).least_seconds(
        run["cfg"], run["peaks"], run["engine"]["max_batch"], live, touched)
    return 100.0 * steps * least / seconds

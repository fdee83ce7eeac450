"""``readers/ssm_moe_decode_roofline.py`` for the delta-rule + latent-attention
family's decode program: the least time the chip could take for the decode
steps of the traced span (``roofline/<count>.py``) as a share of the device
time they took — the whole program's, or, with ``parts``, the state rows'
bytes alone against the device time of those parts of the decode program
(``lib/xplane_parts.py``'s seconds by part, ``readers/part_share.py``'s
table): the state update's share of ITS roofline, whichever form runs.
Everything the count needs the program counts itself between the span's two
snapshots: state rows updated (``rt_llm_delta_state_updates_total``), live
positions of the latent kind (``rt_llm_decode_kv_tokens_live_total``), held
experts touched, rows routed to them. A program without those counters (the
tree before it had them), or a trace without the part table, reads as
nothing."""
from benchmarks.lib.configs import load_module
from benchmarks.readers.decode_step_ms_dispatched import steps_and_seconds
from benchmarks.readers.stage_mean_ms import stage_delta


def per_step(run: dict):
    """(state rows updated, live latent positions, held experts touched an
    expert layer, rows routed to held experts an expert layer) a decode
    step, or nothing."""
    c = run.get("counters") or {}
    updates = stage_delta(run, "rt_llm_delta_state_updates_total")
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
    expert_layers = slots["sum"] / steps / (hi - lo)
    return (updates["sum"] / steps, reach["sum"] / steps,
            (hi - lo) * hit["sum"] / slots["sum"],
            rows["sum"] / steps / expert_layers)


def read(run: dict, program: str, count: str, parts: list | None = None):
    got, counted = steps_and_seconds(run, program), per_step(run)
    if got is None or counted is None:
        return None
    steps, seconds = got
    module = load_module("roofline", count)
    if parts is None:
        least = module.least_seconds(
            run["cfg"], run["peaks"], run["engine"]["max_batch"], *counted)
        return 100.0 * steps * least / seconds
    from benchmarks.readers.part_share import _table

    table = _table(run)
    if not table or any(program in p for p in table["stale"]):
        return None
    took = sum(s for (prog, part), s in table["seconds"].items()
               if program in prog and part in parts)
    if not took:
        return None
    least = (module.state_bytes(run["cfg"], counted[0])
             / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * steps * least / took

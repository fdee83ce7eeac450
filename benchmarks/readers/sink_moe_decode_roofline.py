"""``readers/swa_moe_decode_roofline.py`` for a cache whose two kinds of pages
differ in their row's bytes (a copy of it: that reader takes the positions
within reach as ONE mean over layers, which says nothing of a model whose
full layers hold 4 KV heads and whose window layers 8): the least time the
chip could take for the decode steps of the traced span
(``roofline/<count>.py``) as a share of the device time they took.
Everything the count needs the program counts itself between the span's two
snapshots: the positions within a layer's reach of each kind
(``rt_llm_decode_kv_tokens_live_total{full}`` / ``{window}``), the held
experts touched and the rows routed to them, summed over the expert layers.
A program without those counters reads as nothing."""
from benchmarks.lib.configs import load_module
from benchmarks.readers.decode_step_ms_dispatched import steps_and_seconds
from benchmarks.readers.stage_mean_ms import stage_delta

LIVE = "rt_llm_decode_kv_tokens_live_total"


def per_step(run: dict):
    """(positions within a full layer's reach, within a window layer's,
    held experts touched over all expert layers, rows routed to held experts
    over all expert layers) a decode step, or nothing."""
    c = run.get("counters") or {}
    full, window = (stage_delta(run, LIVE, kind) for kind in ("full", "window"))
    hit = stage_delta(run, "rt_llm_moe_experts_touched_total")
    rows = stage_delta(run, "rt_llm_moe_assignments_total")
    if None in (full, window, hit, rows):
        return None
    steps = c["after"]["steps"] - c["before"]["steps"]
    if steps <= 0:
        return None
    return (full["sum"] / steps, window["sum"] / steps, hit["sum"] / steps,
            rows["sum"] / steps)


def read(run: dict, program: str, count: str):
    got, counted = steps_and_seconds(run, program), per_step(run)
    if got is None or counted is None:
        return None
    steps, seconds = got
    engine = run["engine"]
    slots = engine["max_batch"]
    # both tables once a step: a full table of every page a slot can reach,
    # and the ring's
    entries = slots * (-(-engine["max_seq_len"] // engine["page_size"])
                       + run["cfg"].sliding_window // engine["page_size"] + 1)
    least = load_module("roofline", count).least_seconds(
        run["cfg"], run["peaks"], slots, *counted, table_entries=entries)
    return 100.0 * steps * least / seconds

"""``readers/ssm_moe_decode_roofline.py`` for the windowed exact + pooled-pair
attention family's decode program: the least time the chip could take for the
decode steps of the traced span (``roofline/<count>.py``) as a share of the
device time they took — the whole program's, or, with ``kernels``, the two
walks' bytes alone (attended rows, q and o) against the device time of the
Pallas calls whose names hold one of ``kernels``: the walk's share of ITS
roofline. Everything the count needs the program counts itself between the
span's two snapshots: rows attended of each kind
(``rt_llm_decode_kv_tokens_live_total{kind}``) and pairs written
(``rt_llm_eva_pairs_written_total``). A program without those counters, or a
trace without the kernels, reads as nothing."""
from benchmarks.lib.configs import load_module
from benchmarks.readers.decode_step_ms_dispatched import steps_and_seconds
from benchmarks.readers.stage_mean_ms import stage_delta

LIVE = "rt_llm_decode_kv_tokens_live_total"


def per_step(run: dict):
    """(window rows attended, pairs attended, pairs written) a decode step —
    the rows one layer's worth, the pairs written over all layers — or
    nothing."""
    c = run.get("counters") or {}
    window, summary = (stage_delta(run, LIVE, k) for k in ("window", "summary"))
    pairs = stage_delta(run, "rt_llm_eva_pairs_written_total")
    if None in (window, summary, pairs):
        return None
    steps = c["after"]["steps"] - c["before"]["steps"]
    if steps <= 0:
        return None
    return window["sum"] / steps, summary["sum"] / steps, pairs["sum"] / steps


def read(run: dict, program: str, count: str, kernels: list | None = None):
    got, counted = steps_and_seconds(run, program), per_step(run)
    if got is None or counted is None:
        return None
    steps, seconds = got
    module = load_module("roofline", count)
    if kernels is None:
        least = module.least_seconds(run["cfg"], run["peaks"], *counted)
        return 100.0 * steps * least / seconds
    took = sum(s for name, s in run["trace"]["ops"]
               if "pallas:" in name and any(k in name for k in kernels))
    if not took:
        return None
    least = (module.attention_bytes(run["cfg"], *counted)
             / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * steps * least / took

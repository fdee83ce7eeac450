"""``readers/cca_moe_decode_roofline.py`` for the looped family's decode
program: the least time the chip could take for the decode steps of the
traced span (``roofline/<count>.py``: every layer's weights once a pass and
the live keys and values of every plane) as a share of the device time they
took. The live positions a step are the program's own count between the
span's two snapshots (``rt_llm_decode_kv_tokens_live_total``, one plane's) —
not ``readers/decode_roofline.py``'s, which reckons them from the clients'
first and last token stamps and finds none on a unary reply (it read this
cell's K and V as 0 bytes: a quarter of the step's least bytes left out). A
program without the counter reads as nothing."""
from benchmarks.lib.configs import load_module
from benchmarks.readers.decode_step_ms_dispatched import steps_and_seconds
from benchmarks.readers.stage_mean_ms import stage_delta


def read(run: dict, program: str, count: str):
    got = steps_and_seconds(run, program)
    reach = stage_delta(run, "rt_llm_decode_kv_tokens_live_total")
    if got is None or reach is None:
        return None
    c = run["counters"]
    counted = c["after"]["steps"] - c["before"]["steps"]
    if counted <= 0:
        return None
    least = load_module("roofline", count).least_seconds(
        run["cfg"], run["peaks"], run["engine"]["max_batch"],
        reach["sum"] / counted)
    return 100.0 * got[0] * least / got[1]

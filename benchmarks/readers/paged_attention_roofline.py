"""The paged decode kernel's share of its roofline over the traced span, one
kind of page at a time: the least time the chip could take to read the keys
and values WITHIN REACH of that kind's layers (``roofline/<count>.py``) over
the device time of the operations whose names hold every one of
``patterns``. The positions within reach a step are the program's own count
for the kind (``rt_llm_decode_kv_tokens_live_total{kind}``, one layer's,
between the span's two snapshots over the steps counted between them); the
steps the trace holds are ``readers/decode_step_ms_dispatched.py``'s. A
program without the tagged counter or the kernel reads as nothing."""
from benchmarks.lib.configs import load_module
from benchmarks.readers.decode_step_ms_dispatched import steps_and_seconds
from benchmarks.readers.stage_mean_ms import stage_delta


def read(run: dict, program: str, patterns: list, count: str, kind: str):
    got = steps_and_seconds(run, program)
    reach = stage_delta(run, "rt_llm_decode_kv_tokens_live_total", kind)
    if got is None or reach is None:
        return None
    c = run["counters"]
    counted = c["after"]["steps"] - c["before"]["steps"]
    took = sum(s for name, s in run["trace"]["ops"]
               if all(p in name for p in patterns))
    if counted <= 0 or not took:
        return None
    least = load_module("roofline", count).least_seconds(
        run["cfg"], run["peaks"], run["engine"]["max_batch"], kind,
        reach["sum"] / counted)
    return 100.0 * got[0] * least / took

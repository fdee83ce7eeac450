"""The least time the chip could take for the decode steps of the traced
span (``roofline/paged_decode_multi.py``: weights once and the live keys and
values, over the memory bandwidth) as a share of the device time they took."""
from benchmarks.lib import stats
from benchmarks.lib.configs import load_module
from benchmarks.readers.decode_steps import steps_and_seconds


def read(run: dict, program: str, count: str):
    got = steps_and_seconds(run, program)
    if got is None or not run.get("trace_window"):
        return None
    steps, seconds = got
    live = stats.live_kv_tokens(run["recs_all"], *run["trace_window"])
    least = load_module("roofline", count).least_seconds(
        run["cfg"], run["peaks"], run["engine"]["max_batch"], live)
    return 100.0 * steps * least / seconds

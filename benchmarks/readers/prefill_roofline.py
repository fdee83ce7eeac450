"""The operations of the prefills of the traced span at their true, unpadded
lengths (``roofline/paged_prefill_batch.py``) over the chip's peak, as a
share of the device time of the prefill programs. A prefill belongs to the
span when its request's first token reached the client inside it."""
from benchmarks.lib.configs import load_module


def span_prompt_lens(run: dict) -> list[int]:
    """Prompt lengths of the requests whose prefill fell into the traced
    span, or nothing without a trace."""
    window = run.get("trace_window")
    if not window:
        return []
    a, b = window
    lens = [r["prompt_len"] for r in run["recs_all"]
            if "first" in r and a <= r["first"] < b]
    # closed-loop unary requests carry no first-token stamp: place the
    # prefill at the reply less the decode steps' share of the request
    lens += [r["prompt_len"] for r in run["recs_all"]
             if "first" not in r and "done" in r and "sent" in r
             and a <= r["sent"] + 0.5 * (r["done"] - r["sent"]) < b]
    return lens


def read(run: dict, program: str, count: str):
    trace, lens = run.get("trace"), span_prompt_lens(run)
    if not trace or not lens or program not in trace["programs"]:
        return None
    least = load_module("roofline", count).least_seconds(
        run["cfg"], run["peaks"], lens)
    return 100.0 * least / trace["programs"][program]["seconds"]

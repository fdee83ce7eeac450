"""1 - the union of the device's operation intervals over the traced span."""


def read(run: dict):
    trace = run.get("trace")
    if not trace or not trace["busy_s"] or not run.get("trace_span_s"):
        return None
    span = max(run["trace_span_s"], trace["window_s"])
    return 100.0 * (1.0 - trace["busy_s"] / span)

"""Decode steps in the traced span: a ``paged_decode_multi`` program runs one
of the engine's ``block_buckets`` of fused steps (or 1) of one fixed shape, so
each event's step count is the bucket that brings its duration closest to
the step time — first guessed from the engine's ``steps`` counter."""


def steps_and_seconds(run: dict, program: str):
    trace = run.get("trace")
    if not trace or program not in trace["programs"]:
        return None
    durations = trace["programs"][program]["durations"]
    c = run["counters"]
    buckets = c["after"]["block_buckets"]  # the engine's own
    counted = c["after"]["steps"] - c["before"]["steps"]
    if counted <= 0 or not durations:
        return None
    buckets = sorted({1, *buckets})
    # the counter moves in whole blocks, so its step time is only a first
    # guess (tens of percent off over a few seconds): settle it on the trace
    guess = sum(durations) / counted
    for _ in range(4):
        steps = sum(min(buckets, key=lambda b: abs(d / b - guess))
                    for d in durations)
        guess = sum(durations) / steps
    return steps, sum(durations)

"""Mean of one stage the program times itself, in ms: growth of the
stage's ``sum`` over growth of its ``count`` between the two counter
snapshots around the traced span.

The channel: ``LLMEngineServer.engine_stats()["stages"]`` (the program's own
method; ``bench_stats()`` passes every key through), so each snapshot under
``run["counters"]`` holds ``stages[family][tag] = {"sum", "count"}`` —
cumulative since the replica's process started, ``tag`` the value of the
family's one tag (``phase`` or ``leg``) or ``""``. A program without the
key (the parent of the PR that added it) reads as nothing."""


def stage_delta(run: dict, family: str, tag: str = ""):
    """Growth of ``sum`` and ``count`` (0 for a bare counter) of one stage
    between ``counters.before`` and ``counters.after``, or nothing."""
    c = run.get("counters") or {}
    before, after = c.get("before") or {}, c.get("after") or {}
    if "stages" not in before or "stages" not in after:
        return None
    a = after["stages"].get(family, {}).get(tag)
    if a is None:
        return None  # never observed: nothing to read
    b = before["stages"].get(family, {}).get(tag, {})
    return {k: a.get(k, 0) - b.get(k, 0) for k in ("sum", "count")}


def read(run: dict, family: str, tag: str = ""):
    d = stage_delta(run, family, tag)
    if d is None or d["count"] <= 0:
        return None
    return 1e3 * d["sum"] / d["count"]

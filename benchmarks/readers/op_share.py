"""Device time of the operations whose names hold one of ``patterns`` (a
Pallas kernel appears under its kernel function's name) as a share of the
device's busy time."""


def read(run: dict, patterns: list):
    trace = run.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    hit = sum(s for name, s in trace["ops"] if any(p in name for p in patterns))
    return 100.0 * hit / trace["busy_s"] if hit else None

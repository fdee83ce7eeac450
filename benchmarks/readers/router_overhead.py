"""Median over the sampled requests of the client's time to first token
(from when it was sent) less the replica's own time from ``_submit`` to the
first token: what handle, router and replica lane add, in ms."""
from benchmarks.lib import stats


def read(run: dict):
    over = [(r["first"] - r["sent"]) - r["replica_ttft_s"]
            for r in run.get("recs") or []
            if "first" in r and r.get("replica_ttft_s") is not None]
    p = stats.percentile(over, 50)
    return None if p is None else 1e3 * p

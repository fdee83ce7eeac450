"""A percentile over the sampled requests of a client-side time, in ms:
``ttft`` (first token less the instant the request was due), ``tpot`` ((last
less first token) / (tokens - 1)) or ``send_lag`` (sent less due)."""
from benchmarks.lib import stats


def read(run: dict, field: str, q: float):
    recs = run.get("recs")
    if not recs:
        return None
    values = {"ttft": stats.ttft_s, "tpot": stats.tpot_s,
              "send_lag": stats.send_lag_s}[field](recs)
    p = stats.percentile(values, q)
    return None if p is None else 1e3 * p

"""Arithmetic on a run's client records, shared by the readers."""
from __future__ import annotations

import numpy as np


def ms(values) -> list[float]:
    return [1e3 * v for v in values]


def ttft_s(recs) -> list[float]:
    """First token at the client less the instant the request was due."""
    return [r["first"] - r.get("due", r["sent"]) for r in recs if "first" in r]


def tpot_s(recs) -> list[float]:
    return [(r["last"] - r["first"]) / (r["tokens"] - 1)
            for r in recs if "first" in r and r.get("tokens", 0) > 1]


def send_lag_s(recs) -> list[float]:
    return [r["sent"] - r["due"] for r in recs if "sent" in r and "due" in r]


def percentile(values, q: float):
    return float(np.percentile(np.asarray(values, float), q)) if len(values) else None


def live_kv_tokens(recs, a: float, b: float) -> float:
    """Mean, over the span [a, b], of the context tokens held by running
    requests: a request holds prompt_len at its first token and grows by one
    a token to its last."""
    total = 0.0
    for r in recs:
        if ("first" not in r or "last" not in r or r["last"] <= r["first"]
                or "tokens" not in r):
            continue
        lo, hi = max(a, r["first"]), min(b, r["last"])
        if hi <= lo:
            continue
        rate = (r["tokens"] - 1) / (r["last"] - r["first"])
        mid = (lo + hi) / 2 - r["first"]
        total += (r["prompt_len"] + 1 + rate * mid) * (hi - lo)
    return total / (b - a)

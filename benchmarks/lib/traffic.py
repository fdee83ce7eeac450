"""The one general traffic generator: a traffic file's parameters and a seed
in, a schedule of requests out. No jax, no program code.

The rule that keeps a cell steady: the *multiset* of (prompt, output) lengths
and the number of requests due in the window are fixed by the file alone
(lengths at evenly spaced quantiles of the file's distributions); ``--seed``
chooses only the pairing, the order, the arrival instants and the token ids.
Two seeds offer the same requests, prompt tokens and output tokens."""
from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due_s: float        # relative to the window's start; negative in the lead-in
    prompt_len: int
    max_tokens: int
    sampled: bool       # due inside the window


def quantile_lengths(spec: dict, n: int) -> list[int]:
    """``n`` lengths at the evenly spaced quantiles (i + 1/2) / n of the
    distribution ``spec``, clipped to [min, max] or, where the file lists the
    ``lengths`` allowed, snapped to the nearest of them."""
    qs = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(q)) for q in qs])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = spec["min"] + qs * (spec["max"] - spec["min"])
    elif spec["dist"] == "fixed":
        x = np.full(n, spec["value"], float)
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    if "lengths" in spec:  # the only lengths allowed: snap to the nearest
        allowed = np.asarray(sorted(spec["lengths"]), float)
        return [int(allowed[np.argmin(np.abs(allowed - v))]) for v in x]
    return [int(v) for v in np.clip(np.rint(x), spec.get("min", 1),
                                    spec.get("max", 1 << 30))]


def length_pairs(traffic: dict, n: int, rng: np.random.Generator
                 ) -> list[tuple[int, int]]:
    """The fixed multiset of n prompt and n output lengths, paired by the
    seed. Every pairing fits ``max_total`` because the file's two maxima do."""
    prompts = quantile_lengths(traffic["prompt"], n)
    outputs = quantile_lengths(traffic["output"], n)
    if max(prompts) + max(outputs) > traffic["max_total"]:
        raise ValueError("the traffic file's maxima exceed its max_total")
    order_p, order_o = rng.permutation(n), rng.permutation(n)
    return [(prompts[i], outputs[j]) for i, j in zip(order_p, order_o)]


def _rng(seed: int, stream: int) -> np.random.Generator:
    # any whole number, also above 2**31
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, stream])


def open_schedule(traffic: dict, seed: int, seconds: float) -> list[Request]:
    """Open loop. Arrivals of independent users (exponential gaps)
    conditioned on a fixed count: N = round(rate * seconds) requests due in
    the window at N uniform order statistics over it. The same process runs
    through a lead-in before the window and a tail after it (the client
    stops the tail once every sampled request has finished); those requests
    load the engine and are not sampled. Lead-in and tail have fixed
    multisets of their own, so the engine meets the window's first request
    under the same amount of work whatever the seed."""
    rate = traffic["rate_rps"]
    out: list[Request] = []
    spans = [(-float(traffic["lead_in_s"]), 0.0, False),
             (0.0, float(seconds), True),
             (float(seconds), float(seconds) + float(traffic["tail_s"]), False)]
    for stream, (t0, t1, sampled) in enumerate(spans):
        rng = _rng(seed, stream)
        n = max(1, round(rate * (t1 - t0)))
        pairs = length_pairs(traffic, n, rng)  # each span its own fixed multiset
        dues = np.sort(rng.uniform(t0, t1, n))
        out += [Request(0, float(d), p, o, sampled)
                for d, (p, o) in zip(dues, pairs)]
    return [dataclasses.replace(r, index=i) for i, r in enumerate(out)]


def closed_list(traffic: dict, seed: int) -> list[tuple[int, int]]:
    """Closed loop: the callers draw from one seeded permutation of a fixed
    list of ``list_size`` (prompt, output) pairs, cycled."""
    return length_pairs(traffic, int(traffic["list_size"]), _rng(seed, 0))


def prompt_tokens(seed: int, index: int, length: int, vocab: int,
                  reserved: int = 3) -> list[int]:
    """Token ids of request ``index``: seeded, never a reserved id."""
    rng = _rng(seed, 1000 + index)
    return rng.integers(reserved, vocab, length).tolist()


def offered(requests: list[Request]) -> dict:
    s = [r for r in requests if r.sampled]
    return {"requests": len(s), "prompt_tokens": sum(r.prompt_len for r in s),
            "output_tokens": sum(r.max_tokens for r in s)}

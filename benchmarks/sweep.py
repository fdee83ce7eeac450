#!/usr/bin/env python3
"""One set-up, many windows, in one process: the rate sweep that finds a
serving cell's knee, and the noise split (six windows of one seed against six
windows of six seeds) that says how much of a metric's spread is the host's
and how much the traffic's. Prints a table; never the driver's result line.

    python3 benchmarks/sweep.py --workload mistral7b_chat_open --seconds 40 \
        --rates 1.2,1.6,2.0,2.4,2.8
    python3 benchmarks/sweep.py --workload mistral7b_chat_open --seconds 51 \
        --seeds 101,101,101,101,101,101,201,202,203,204,205,206
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def spread(values: list[float]) -> float:
    """Distance between the first and third quartile over the median."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="", help="comma list of requests/s")
    ap.add_argument("--seeds", default="1", help="comma list, one window each")
    ap.add_argument("--seed", type=int, default=1, help="weights' seed")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()
    args.trace, args.trace_seconds = 0, 0.0

    from benchmarks.lib import stats
    from benchmarks.lib.cluster import Runtime
    from benchmarks.lib.configs import load_cell, load_module
    from benchmarks.drivers import serve
    from benchmarks.run import Clock

    cell = load_cell(args.workload)
    rates = [float(r) for r in args.rates.split(",") if r]
    seeds = [int(s) for s in args.seeds.split(",") if s]
    plan = [(r, seeds[0]) for r in rates] or [(None, s) for s in seeds]
    rows = []
    with Runtime(cell["chips"], args.allow_cpu, deadline_s=3500):
        ctx = serve.setup(cell, args, Clock())
        print(f"[sweep] set up in {time.monotonic() - T_START:.1f}s", flush=True)
        for rate, seed in plan:
            if rate is not None:
                ctx["traffic"] = {**ctx["traffic"], "rate_rps": rate}
            raw = serve.window(ctx, seed, args.seconds, poll_s=1.0)
            recs, c = raw["recs"], raw["counters"]
            ttft, tpot = stats.ms(stats.ttft_s(recs)), stats.ms(stats.tpot_s(recs))
            row = {
                "rate": ctx["traffic"].get("rate_rps"), "seed": seed,
                "n": len(recs), "failed": raw["failed"],
                "ttft_p50_ms": stats.percentile(ttft, 50),
                "ttft_p85_ms": stats.percentile(ttft, 85),
                "tpot_p50_ms": stats.percentile(tpot, 50),
                "tpot_mean_ms": load_module("readers", "tpot_mean").read(raw),
                "tokens_per_s": load_module("readers", "serve_tokens_per_s").read(raw),
                "send_lag_p95_ms": stats.percentile(
                    stats.ms(stats.send_lag_s(recs)), 95),
                # the engine's queue and busy slots, polled once a second:
                # means over the window's first and second half
                **{f"{name}_{half}": statistics.fmean(
                    p[col] for p in c["polls"]
                    if (p[0] < raw["seconds"] / 2) == (half == "h1"))
                   for col, name in ((1, "waiting"), (2, "live"))
                   for half in ("h1", "h2")},
                "compiles": raw["compiles_in_window"],
                "peak_bytes": raw["device"]["peak_bytes_in_use"][0],
                "platform": raw["device"]["platform"],
            }
            rows.append(row)
            print("[sweep] " + json.dumps(row), flush=True)
    cols = list(rows[0])
    print(" | ".join(cols))
    for row in rows:
        print(" | ".join(f"{row[c]:.4g}" if isinstance(row[c], float) else str(row[c])
                         for c in cols))
    if not rates:  # the noise split
        groups: dict[int, list[dict]] = {}
        for row in rows:
            groups.setdefault(row["seed"], []).append(row)
        same = max(groups.values(), key=len)
        across = [g[0] for s, g in groups.items() if g is not same] or same
        for name in ("ttft_p50_ms", "tpot_p50_ms", "tpot_mean_ms", "tokens_per_s"):
            line = f"{name}:"
            for label, part in (("one seed", same), ("across seeds", across)):
                if len(part) >= 3:
                    vals = [r[name] for r in part]
                    line += (f" {label} x{len(vals)} median "
                             f"{statistics.median(vals):.5g} spread "
                             f"{100 * spread(vals):.2f}%;")
            print(line)
    out = os.environ.get("BENCH_DEBUG_DIR")
    if out:
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, f"sweep.{args.workload}.{int(time.time())}.json"),
                  "w") as f:
            json.dump(rows, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())

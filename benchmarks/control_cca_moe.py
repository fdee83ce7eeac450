#!/usr/bin/env python3
"""``control.py`` for the cells of ``drivers/serve_cca_moe.py`` (a copy of
``control_kda_moe.py`` with this family's controls): the two readings that
set the limits of ``correct``, at the cell's own size on the chip, in one
set-up — the program against the float32 reference on some seeds, and the
controls on fewer: the reference with every matmul input rounded in the
program's place (``fp8``: the precision below the configuration's;
``bfloat16``: the configuration's own, which reads as the program does and is
reported, not a control), and the float32 reference with other mathematics —
no convolution (``noconv``), the depthwise one alone (``depthwise``), the
second depthwise too (``depthwise2``), no mean term (``nomean``), the mean
without the group average (``meanfirst``), no value shift (``noshift``), the
shift on KV head 0 (``shifthead0``), q and k not normalised (``noqknorm``),
tau = 1 (``tauone``), the whole head rotated (``ropewhole``), theta 1e4
(``theta1e4``), k cached before tau (``kbeforetau``), the prompt's pad
positions advancing the row (``padrun``: only a prompt that does not fill its
last page differs), no carry (``nocarry``), the router on x (``routerx``),
the router in bf16 (``routerbf16``), the bias in the weight
(``biasweights``), top-1's weight 1 (``weightone``), residual gains 1
(``gainsone``).

    python3 benchmarks/control_cca_moe.py --workload zaya1_cot_closed \
        --seeds 1,2,3 --control-seeds 1 --modes fp8,bfloat16,noconv,...
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


VARIANTS = {"noconv": {"conv": "none"}, "depthwise": {"conv": "depthwise"},
            "depthwise2": {"conv": "depthwise2"}, "nomean": {"mean": "none"},
            "meanfirst": {"mean": "first"}, "noshift": {"vshift": "none"},
            "shifthead0": {"vshift": "head0"}, "noqknorm": {"qknorm": False},
            "tauone": {"temp": "one"}, "ropewhole": {"rope": "whole"},
            "theta1e4": {"theta": 1e4}, "kbeforetau": {"temp": "after"},
            "padrun": {"pad": True}, "nocarry": {"carry": False},
            "routerx": {"router_in": "x"}, "routerbf16": {"router": "bfloat16"},
            "biasweights": {"bias": "weights"}, "weightone": {"weight": "one"},
            "gainsone": {"gains": "one"}}
MODES = ("fp8", "bfloat16", *VARIANTS)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--modes", default=",".join(MODES))
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()
    args.trace, args.trace_seconds, args.seconds = 0, 0.0, 0.0

    import ray_tpu
    from benchmarks.drivers import serve_cca_moe as driver
    from benchmarks.lib.cluster import Runtime
    from benchmarks.lib.configs import load_cell
    from benchmarks.run import Clock

    cell = load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    plan = [(s, None) for s in seeds] + [
        (int(s), m) for m in args.modes.split(",")
        for s in args.control_seeds.split(",")]
    rows = []
    with Runtime(cell["chips"], args.allow_cpu, deadline_s=3500):
        args.seed = seeds[0]
        ctx = driver.setup(cell, args, Clock())
        handle, cfg = ctx["handle"], ctx["cfg"]
        checks = ctx["traffic"]["reference_check"]
        variants = VARIANTS
        for seed, mode in plan:
            if mode is None:  # the control never reads the program's weights
                ray_tpu.get(handle.reseed.remote(seed, cfg), timeout=600)
            row = {}
            for i, (prefix, rc) in enumerate(zip(driver.CHECKS, checks)):
                one = ray_tpu.get(handle.reference_check.remote(
                    seed, cfg, rc["prompt_len"], rc["max_tokens"],
                    mode if mode in ("fp8", "bfloat16") else "float32",
                    variants.get(mode), i), timeout=900)
                row.update({prefix + k: v for k, v in one.items()})
            rows.append({"seed": seed, **row, "mode": mode or "program"})
            print("[control] " + json.dumps(rows[-1]), flush=True)
        device = ray_tpu.get(handle.bench_stats.remote(), timeout=60)["device"]
    print(f"[control] device {device['platform']} {device['kind']!r}")
    for name in [k for k, v in rows[0].items() if isinstance(v, float)]:
        prog = [r[name] for r in rows if r["mode"] == "program"]
        line = f"{name}: program largest {max(prog):.6g} over {len(prog)} seeds"
        for mode in args.modes.split(","):
            low = [r[name] for r in rows if r["mode"] == mode]
            if low:
                line += f"; {mode} smallest {min(low):.6g} over {len(low)}"
        print(line)
    out = os.environ.get("BENCH_DEBUG_DIR")
    if out:
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, f"control.{args.workload}.json"), "w") as f:
            json.dump(rows, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())

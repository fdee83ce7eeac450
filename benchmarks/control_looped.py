#!/usr/bin/env python3
"""``control.py`` for the cells of ``drivers/serve_looped.py`` (a copy of
``control_sink_moe.py``: README_looped.md): the two readings that set the
limits of ``correct``, at the cell's own size on the chip, in one set-up — the
program against the float32 reference on some seeds, and the controls on
some: the float32 reference with OTHER MATHEMATICS in the program's place
(``CONTROLS``: three passes or five; every pass reading pass 4's, pass 1's or
the pass before's planes; one plane a layer written by all passes in turn; no
norm between passes; pre-norm alone; post-norm alone; positions that advance
with the pass; a threshold of 0.5; the head on the mean of the four states;
base 1e4; half a head rotated), and the reference with every matmul input
rounded to fp8 (``fp8``). Every reading is the worse of the traffic file's
checked requests, as a run's is.

    python3 benchmarks/control_looped.py --workload ouro26b_solve_closed \
        --seeds 1,2 --control-seeds 1 --modes fp8,passes_3,read_last
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# name -> the variant of ``reference/looped.py`` (fp8 is a mode, not one);
# ``read_from: "prompt"`` is filled in by the replica with the prompt's length
CONTROLS = {
    "passes_3": {"n_passes": 3},
    "passes_5": {"n_passes": 5},
    "read_last": {"read": "last"},
    "read_first": {"read": "first"},
    "read_previous": {"read": "previous"},
    "one_plane": {"read": "last", "read_from": "prompt"},
    "carry_raw": {"carry": "raw"},
    "pre_norm": {"norms": "pre"},
    "post_norm": {"norms": "post"},
    "positions_advance": {"position_step": 1},
    "threshold_half": {"exit_threshold": 0.5},
    "head_on_mean": {"head_on": "mean"},
    "base_1e4": {"rope_theta": 1e4},
    "half_rotated": {"rotary_share": 0.5},
}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--modes", default="fp8," + ",".join(CONTROLS))
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()
    args.trace, args.trace_seconds, args.seconds = 0, 0.0, 0.0

    import ray_tpu
    from benchmarks.drivers import serve_looped as driver
    from benchmarks.lib.cluster import Runtime
    from benchmarks.lib.configs import load_cell
    from benchmarks.run import Clock

    cell = load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    modes = [m for m in args.modes.split(",") if m]
    plan = [(s, None) for s in seeds] + [
        (int(s), m) for m in modes for s in args.control_seeds.split(",")]
    rows = []
    with Runtime(cell["chips"], args.allow_cpu, deadline_s=3500):
        args.seed = seeds[0]
        ctx = driver.setup(cell, args, Clock())
        handle, cfg = ctx["handle"], ctx["cfg"]
        for seed, mode in plan:
            if mode is None:  # the control never reads the program's weights
                ray_tpu.get(handle.reseed.remote(seed, cfg), timeout=600)
            row = driver.worst_of([ray_tpu.get(handle.reference_check.remote(
                seed, cfg, rc["prompt_len"], rc["max_tokens"],
                "fp8" if mode == "fp8" else "float32", CONTROLS.get(mode), i),
                timeout=900)
                for i, rc in enumerate(ctx["traffic"]["reference_check"])])
            rows.append({"seed": seed, **row, "mode": mode or "program"})
            print("[control] " + json.dumps(rows[-1]), flush=True)
        device = ray_tpu.get(handle.bench_stats.remote(), timeout=60)["device"]
    print(f"[control] device {device['platform']} {device['kind']!r}")
    limits = cell["config_file"]["correct_limits"][ctx["traffic"]["driver"]]
    for name in [k for k, v in rows[0].items() if isinstance(v, float)]:
        prog = [r[name] for r in rows if r["mode"] == "program"]
        line = f"{name}: program largest {max(prog):.6g} over {len(prog)} seeds"
        for mode in modes:
            low = [r[name] for r in rows if r["mode"] == mode]
            if low:
                line += f"; {mode} smallest {min(low):.6g}"
        print(line)
    for mode in modes:
        caught = sorted({n for r in rows if r["mode"] == mode
                         for n, limit in limits.items() if r[n] > limit})
        print(f"[control] {mode}: fails {caught or 'NO LIMIT'}")
    out = os.environ.get("BENCH_DEBUG_DIR")
    if out:
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, f"control.{args.workload}.json"), "w") as f:
            json.dump(rows, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())

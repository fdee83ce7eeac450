#!/usr/bin/env python3
"""``control.py`` for the cells of ``drivers/serve_sink_moe.py`` (a copy of
``control_cohere2_moe.py``: README_sink_moe.md): the two readings that set the
limits of ``correct``, at the cell's own size on the chip, in one set-up — the
program against the float32 reference on some seeds, and the controls on
some: the float32 reference with OTHER MATHEMATICS in the program's place
(``CONTROLS``: no sink, the sink on full layers too, the sink's value counted,
the window off by one either way or gone, values unscaled, another share of
the head rotated, the full layers' base or grouping in the window layers,
weights not renormalised, the bias in the weight, layer 0 routed), and the
reference with every matmul input rounded to fp8 (``fp8``). Every reading is
the worse of the traffic file's checked requests, as a run's is.

    python3 benchmarks/control_sink_moe.py --workload mimov2flash_agent_closed \
        --seeds 1,2 --control-seeds 1 --modes fp8,no_sink,window_127
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# name -> the variant of ``reference/sink_moe.py`` (fp8 is a mode, not one)
CONTROLS = {
    "no_sink": {"sink_window": False},
    "sink_full_too": {"sink_full": True},
    "sink_value": {"sink_value": True},
    "window_127": {"sliding_window": 127},
    "window_129": {"sliding_window": 129},
    "no_window": {"sliding_window": 10**9},
    "unscaled": {"value_scale": 1.0},
    "whole_head": {"partial_rotary_factor": 1.0},
    "lanes_96": {"partial_rotary_factor": 0.5},
    "base_5e6": {"swa_rope_theta": 5000000.0},
    "group_16": {"window_group": 16},
    "no_renorm": {"norm_topk_prob": False},
    "bias_weighs": {"bias_in_weight": True},
    "layer0_routed": {"layer_moe": "all"},
}


def variant_of(name: str, cfg) -> dict:
    v = dict(CONTROLS[name])
    if v.get("layer_moe") == "all":
        v["layer_moe"] = (True,) * cfg.n_layers
    return v


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
    from benchmarks.drivers import serve_sink_moe as driver
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
        for seed, mode in plan:
            if mode is None:  # the control never reads the program's weights
                ray_tpu.get(handle.reseed.remote(seed, cfg), timeout=600)
            row = driver.worst_of([ray_tpu.get(handle.reference_check.remote(
                seed, cfg, rc["prompt_len"], rc["max_tokens"],
                "fp8" if mode == "fp8" else "float32",
                variant_of(mode, cfg) if mode in CONTROLS else None),
                timeout=900) for rc in ctx["traffic"]["reference_check"]])
            rows.append({"seed": seed, **row, "mode": mode or "program"})
            print("[control] " + json.dumps(rows[-1]), flush=True)
        device = ray_tpu.get(handle.bench_stats.remote(), timeout=60)["device"]
    print(f"[control] device {device['platform']} {device['kind']!r}")
    limits = cell["config_file"]["correct_limits"][ctx["traffic"]["driver"]]
    for name in [k for k, v in rows[0].items() if isinstance(v, float)]:
        prog = [r[name] for r in rows if r["mode"] == "program"]
        line = f"{name}: program largest {max(prog):.6g} over {len(prog)} seeds"
        for mode in args.modes.split(","):
            low = [r[name] for r in rows if r["mode"] == mode]
            if low:
                line += f"; {mode} smallest {min(low):.6g}"
        print(line)
    for mode in args.modes.split(","):
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

#!/usr/bin/env python3
"""``control.py`` for the cells of ``drivers/serve_kda_moe.py`` (a copy of
``control_ssm_moe.py`` with this family's controls): the two readings that
set the limits of ``correct``, at the cell's own size on the chip, in one
set-up — the program against the float32 reference on some seeds, and the
controls on fewer: the reference with every matmul input rounded in the
program's place (``fp8``: the precision below the configuration's;
``bfloat16``: the configuration's own, which reads as the program does and is
reported, not a control), and the float32 reference with other mathematics —
no decay (``nodecay``), one decay a head (``headdecay``), no delta term
(``nodelta``), ``beta`` = 1 (``betaone``), q and k not normalised
(``noqknorm``), no silu after the convolution (``nosilu``), the gate before
the norm (``gatebefore``), the state rounded to bf16 after every position
(``bf16state``), the prompt's pad positions advancing state and convolution
(``padrun``: only a prompt that does not fill its last page differs), top-8
of all 512 with no group chosen (``nogroups``), a group's score its largest
alone (``groupmax``), the bias in the weights (``biasweights``), no rotation
in the MLA layers (``norope``).

    python3 benchmarks/control_kda_moe.py --workload ling3flashvl_think_closed \
        --seeds 1,2,3 --control-seeds 1 --modes fp8,bfloat16,nodecay,...
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


VARIANTS = {"nodecay": {"decay": "none"}, "headdecay": {"decay": "head"},
            "nodelta": {"delta": False}, "betaone": {"beta": "one"},
            "noqknorm": {"qknorm": False}, "nosilu": {"silu": False},
            "gatebefore": {"gate": "before"}, "bf16state": {"state": "bfloat16"},
            "padrun": {"pad": True}, "nogroups": {"groups": "none"},
            "groupmax": {"groups": "max"}, "biasweights": {"bias": "weights"},
            "norope": {"rope": False}}
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
    from benchmarks.drivers import serve_kda_moe as driver
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

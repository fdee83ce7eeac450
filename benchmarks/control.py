#!/usr/bin/env python3
"""The two readings that set the limits of ``correct``, at the cell's own
size on the chip, in one set-up: the program against the float32 reference on
a dozen seeds, and the control — the reference at the nearest precision below
the configuration's, in the program's place — on three or more. Prints a
table; the benchmark's own runs never run the control.

    python3 benchmarks/control.py --workload mistral7b_chat_open \
        --seeds 1,2,...,12 --control-seeds 1,2,3 --modes fp8,bfloat16
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def train_control_loop(config: dict) -> None:
    from benchmarks.drivers.train import reference_numbers
    from benchmarks.lib import weights
    from ray_tpu import train
    from ray_tpu.utils.device import device_report

    cfg, rows = config["cfg"], []
    for seed, mode in config["plan"]:
        params = None if mode else weights.make_params(weights.seed_key(seed), cfg)
        row = reference_numbers(seed, cfg, params, config["rc"],
                                config["reference"], mode)
        rows.append({"seed": seed, **row})
        del params
    train.report({"rows": rows, "device": device_report()})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--modes", default="fp8")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()
    args.trace, args.trace_seconds, args.seconds = 0, 0.0, 0.0

    import ray_tpu
    from benchmarks.lib.cluster import Runtime
    from benchmarks.lib.configs import llama_config, load_cell
    from benchmarks.run import Clock

    cell = load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    plan = [(s, None) for s in seeds] + [
        (int(s), m) for m in args.modes.split(",")
        for s in args.control_seeds.split(",")]
    cf, traffic = cell["config_file"], cell["traffic_file"]
    if args.allow_cpu:
        cf, traffic = {**cf, **cf["tiny"]}, {**traffic, **traffic["tiny"]}
    rc = traffic["reference_check"]
    with Runtime(cell["chips"], args.allow_cpu, deadline_s=3500):
        if traffic["driver"] == "train":
            from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

            result = JaxTrainer(
                train_control_loop,
                train_loop_config={"cfg": llama_config(cf), "plan": plan, "rc": rc,
                                   "reference": cf["reference"]},
                scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
                run_config=RunConfig(name="bench_control", storage_path=os.path.join(
                    tempfile.gettempdir(), "bench_control"))).fit()
            if result.error is not None:
                raise result.error
            rows, device = result.metrics["rows"], result.metrics["device"]
        else:
            from benchmarks.drivers import serve

            args.seed = seeds[0]
            ctx = serve.setup(cell, args, Clock())
            handle, cfg, eos = ctx["handle"], ctx["cfg"], ctx["engine"].get("eos_id")
            rows = []
            for seed, mode in plan:
                ray_tpu.get(handle.reseed.remote(seed, cfg, eos), timeout=600)
                row = ray_tpu.get(handle.reference_check.remote(
                    seed, cfg, rc["prompt_len"], rc["max_tokens"], eos,
                    mode or "float32"), timeout=600)
                rows.append({"seed": seed, **row, "mode": mode or "program"})
            device = ray_tpu.get(handle.bench_stats.remote(), timeout=60)["device"]
    print(f"[control] device {device['platform']} {device['kind']!r}")
    names = [k for k, v in rows[0].items()
             if isinstance(v, float) and k not in ("loss", "loss_reference")]
    for row in rows:
        print("[control] " + json.dumps(row), flush=True)
    for name in names:
        prog = [r[name] for r in rows if r["mode"] == "program"]
        line = f"{name}: program largest {max(prog):.6g} over {len(prog)} seeds"
        for mode in args.modes.split(","):
            low = [r[name] for r in rows if r["mode"] == mode]
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

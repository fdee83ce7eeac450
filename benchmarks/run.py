#!/usr/bin/env python3
"""One cell of the benchmark, once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix, metrics, readers, operation counts,
peaks and reference are files found by the names in ``BENCHMARK.json``; this
file knows no cell by name. The process stays off jax: the chip belongs to
the worker the runtime leases it to. No chip, a CPU device in that worker, or
a device that ``peaks.json`` does not know, ends the run with no result.
``--allow-cpu`` rehearses the same code at the files' ``tiny`` sizes: its
line says ``"platform": "cpu"``, ``"correct": false`` and holds no metric."""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

os.environ["JAX_PLATFORMS"] = "cpu"  # before anything can import jax
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class Clock:
    def __init__(self):
        self.marks = {"start": T_START}

    def mark(self, name: str) -> None:
        self.marks[name] = time.monotonic()


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-seconds", type=float, default=10.0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse at the files' tiny sizes on the CPU")
    return ap.parse_args(argv)


def read_metrics(cell: dict, group: str, run: dict) -> dict:
    """Each of the cell's metrics through the reader its file names; a
    reader that finds nothing to read returns nothing and is left out."""
    from benchmarks.lib.configs import cell_metrics, load_json, load_module

    folder = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}[group]
    out = {}
    for m in cell_metrics(cell, group):
        spec = load_json(folder, m["name"] + ".json")
        value = load_module("readers", spec["reader"]).read(run, **spec["args"])
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def judge(cell: dict, raw: dict, on_chip: bool) -> bool:
    """``correct``: every number compared is printed beside its limit."""
    limits = cell["config_file"]["correct_limits"][cell["traffic_file"]["driver"]]
    ok = True
    for name, limit in limits.items():
        value = raw["reference"][name]
        good = value <= limit
        say(f"correct: {name} {value:.6g} <= {limit:g}: {good}")
        ok &= good
    for name, value in raw["reference"].items():
        if name not in limits and "_err" in name:
            say(f"not judged (does not separate the control): {name} {value:.6g}")
    checks = {"failed requests or steps": raw["failed"],
              "programs first used in the window": raw["compiles_in_window"]}
    if "repeats" in raw["reference"]:
        checks["greedy repeat differs"] = int(not raw["reference"]["repeats"])
    if "train" in raw and on_chip:
        checks["train step without the Pallas kernels"] = int(
            not raw["train"]["has_kernel"])
        checks["gradient comparison without the Pallas kernels"] = int(
            not raw["reference"]["kernel_in_check"])
    for name, value in checks.items():
        say(f"correct: {name} {value} <= 0: {value == 0}")
        ok &= value == 0
    if raw["attempted"] <= 0:
        say("correct: nothing was attempted")
        ok = False
    return bool(ok)


def main(argv=None) -> int:
    args = parse_args(argv)
    from benchmarks.lib.configs import load_cell, load_json, load_module

    cell = load_cell(args.workload)
    from benchmarks.lib.cluster import Runtime, chip_files, worker_log_tails

    try:
        found = chip_files()
    except ImportError as e:
        say(f"FAILED: the system under test is not here: {e}")
        return 2
    if not args.allow_cpu and len(found) < cell["chips"]:
        say(f"FAILED: the cell needs {cell['chips']} chip(s), found {found}: no "
            f"accelerator here (--allow-cpu rehearses on the CPU)")
        return 2
    say(f"{cell['name']}: config {cell['config']}, traffic {cell['traffic']}, "
        f"seed {args.seed}, {args.seconds:g}s, trace {args.trace}; chips {found}; "
        f"JAX_COMPILATION_CACHE_DIR={os.environ.get('JAX_COMPILATION_CACHE_DIR')}")

    clock = Clock()
    driver = load_module("drivers", cell["traffic_file"]["driver"])
    started = time.time()
    try:
        with Runtime(cell["chips"], args.allow_cpu, deadline_s=1150):
            raw = driver.run(cell, args, clock)
    except BaseException:
        print(worker_log_tails(started), file=sys.stderr, flush=True)
        raise

    device = raw["device"]
    on_chip = device["platform"] == "tpu"
    if not on_chip and not args.allow_cpu:
        say(f"FAILED: ran on {device['platform']!r}")
        return 2
    if device["count"] != cell["chips"]:
        say(f"FAILED: ran on {device['count']} device(s), the cell asks for "
            f"{cell['chips']}")
        return 2
    peaks = load_json("peaks.json").get(device["kind"])
    if peaks is None and on_chip:
        say(f"FAILED: device kind {device['kind']!r} is not in peaks.json")
        return 2

    marks = clock.marks
    run = {**raw, "peaks": peaks, "setup_s": marks["window"] - T_START}
    steps = " ".join(f"{b}+{marks[b] - marks[a]:.1f}s" for a, b in
                     zip(list(marks), list(marks)[1:]))
    say(f"phases: {steps}")
    correct = judge(cell, raw, on_chip)
    group = "per_layer" if args.trace else "end_to_end"
    metrics = read_metrics(cell, group, run)
    tag = "" if on_chip else "cpu-rehearsal."  # never a device metric's name
    for name, m in metrics.items():
        say(f"{tag}{name} = {m['value']} {m['unit']}")

    line = {"correct": correct and on_chip, "attempted": raw["attempted"],
            "failed": raw["failed"],
            "metrics": metrics if on_chip else {},
            "device": {"platform": device["platform"], "kind": device["kind"],
                       "count": device["count"],
                       "memory_peak_bytes": max(
                           (b or 0) for b in device["peak_bytes_in_use"])}}
    trace = raw.get("trace")
    if args.trace and trace and on_chip:
        line["device"]["busy_s"] = trace["busy_s"]
        line["device"]["window_s"] = max(raw["trace_span_s"], trace["window_s"])
        line["breakdown"] = {"device_ops": trace["ops"][:10],
                             "idle_gaps": trace["idle_gaps"][:10]}
    if not on_chip:
        line["rehearsal"] = {tag + name: m["value"] for name, m in metrics.items()}
    out_dir = os.environ.get("BENCH_DEBUG_DIR")
    if out_dir:  # the builder's own look at a run; the driver sets nothing
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, f"{cell['name']}.{args.seed}."
                               f"{args.trace}.json"), "w") as f:
            json.dump({"line": line, "marks": marks, "reference": raw["reference"],
                       "trace": trace, "recs": raw.get("recs_all"),
                       "train": raw.get("train"),
                       "counters": raw.get("counters")}, f, default=str)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Finding the benchmark's data files by name, and turning a configuration
file into the program's ``LlamaConfig``.

Nothing here imports jax at module level: the parent process of a run stays
off the chip."""
from __future__ import annotations

import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_manifest() -> dict:
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(workload: str) -> dict:
    """The cell's entry of BENCHMARK.json with its configuration's and its
    traffic mix's files, found by name."""
    manifest = load_manifest()
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = dict(cells[workload])
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    with open(os.path.join(REPO_ROOT, entry["file"])) as f:
        cell["config_file"] = json.load(f)
    cell["traffic_file"] = load_json("traffic", cell["traffic"] + ".json")
    cell["manifest"] = manifest
    return cell


def cell_metrics(cell: dict, group: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that this cell reports: a
    metric with no ``workloads`` key belongs to every cell that reports the
    end-to-end metric it moves (``setup_s`` to all)."""
    manifest, name = cell["manifest"], cell["name"]
    e2e = [m for m in manifest["end_to_end"]
           if name in m.get("workloads", [name])]
    if group == "end_to_end":
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]


def llama_config(config_file: dict, **overrides):
    """The published keys of a configuration file as the program's config.
    ``tiny`` overrides (the CPU rehearsal) replace whole keys."""
    from ray_tpu.models.llama import LlamaConfig

    c = {**config_file, **overrides}
    heads = c["num_attention_heads"]
    if c.get("head_dim", c["hidden_size"] // heads) != c["hidden_size"] // heads:
        raise ValueError("models/llama.py derives head_dim from hidden_size")
    return LlamaConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=heads,
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), dtype=c["torch_dtype"],
        remat=c.get("remat", True))


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` (a driver, a reader, an operation
    count, a reference), found by the name a data file gives."""
    return importlib.import_module(f"benchmarks.{kind}.{name}")

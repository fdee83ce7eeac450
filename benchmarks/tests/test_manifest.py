"""``BENCHMARK.json`` against the files it names, read as files alone (no
jax): an entry without its file, a file without its entry, two entries that
are one reading, a cell missing from a list every serve cell stands in. And
the one helper that finds a program by its pattern."""
import json
import os

import pytest

from benchmarks.lib.configs import BENCH_DIR, REPO_ROOT, load_json, load_manifest
from benchmarks.readers import (decode_step_ms, decode_step_ms_dispatched,
                                program_share)
from benchmarks.readers.program_named import resolve

MANIFEST = load_manifest()
GROUPS = (("per_layer", "layer_metrics"), ("end_to_end", "end_to_end"))
CELLS = [w["name"] for w in MANIFEST["workloads"]]
EVERY_SERVE_CELL = ("engine.decode_step_ms.batch", "engine.prefill_share.batch",
                    "device.idle_share.batch")


def exists(*parts: str) -> bool:
    return os.path.exists(os.path.join(BENCH_DIR, *parts))


@pytest.mark.parametrize("group,folder", GROUPS)
def test_every_entry_has_its_file_and_its_reader(group, folder):
    for m in MANIFEST[group]:
        spec = load_json(folder, m["name"] + ".json")
        assert set(spec) == {"name", "reader", "args"}, m["name"]
        assert spec["name"] == m["name"]
        assert exists("readers", spec["reader"] + ".py"), m["name"]


@pytest.mark.parametrize("group,folder", GROUPS)
def test_every_file_has_its_entry(group, folder):
    files = {f[:-len(".json")] for f in os.listdir(os.path.join(BENCH_DIR, folder))}
    assert files == {m["name"] for m in MANIFEST[group]}


def test_no_two_entries_are_one_reading():
    seen = {}
    for m in MANIFEST["per_layer"]:
        spec = load_json("layer_metrics", m["name"] + ".json")
        key = (spec["reader"], json.dumps(spec["args"], sort_keys=True), m["moves"])
        assert key not in seen, f"{m['name']} reads what {seen[key]} reads"
        seen[key] = m["name"]


def test_the_manifest_is_inside_its_caps():
    assert len(MANIFEST["per_layer"]) <= 128
    assert len(MANIFEST["workloads"]) <= 24


@pytest.mark.parametrize("group", ["per_layer", "end_to_end"])
def test_every_listed_workload_is_a_cell(group):
    for m in MANIFEST[group]:
        assert set(m.get("workloads", ())) <= set(CELLS), m["name"]


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_has_its_files(cell):
    w = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
    entry = next(c for c in MANIFEST["configs"] if c["name"] == w["config"])
    assert os.path.exists(os.path.join(REPO_ROOT, entry["file"]))
    assert exists("traffic", w["traffic"] + ".json")
    assert exists("drivers", load_json("traffic", w["traffic"] + ".json")["driver"] + ".py")


@pytest.mark.parametrize("metric", EVERY_SERVE_CELL)
def test_every_serve_cell_stands_in_the_lists_of_all(metric):
    serve = next(m for m in MANIFEST["end_to_end"]
                 if m["name"] == "serve_tokens_per_s")["workloads"]
    listed = next(m for m in MANIFEST["per_layer"] if m["name"] == metric)
    assert listed["moves"] == "serve_tokens_per_s"
    assert set(serve) <= set(listed["workloads"])


def run_with(*programs: str) -> dict:
    return {"trace": {"busy_s": 2.0, "programs": {
                p: {"count": 2, "seconds": 0.5, "durations": [0.32, 0.16]}
                for p in programs}},
            "dispatched_steps": [8, 4],
            "counters": {"before": {"steps": 0},
                         "after": {"steps": 12, "block_buckets": [4, 8]}}}


@pytest.mark.parametrize("programs,found", [
    (("jit_kda_moe_decode_multi", "jit_kda_moe_prefill_batch", "jit__unstack",
      "jit_paged_decode_verify"), "jit_kda_moe_decode_multi"),       # one
    (("jit_kda_moe_prefill_batch", "jit__unstack"), None),            # none
    (("jit_kda_moe_decode_multi", "jit_paged_decode_multi"), None),   # two
], ids=["one", "none", "two"])
def test_a_program_named_by_its_pattern(programs, found):
    run = run_with(*programs)
    assert resolve(run, "jit_*_decode_multi") == found
    # two matches are a reader's nothing, never a sum
    got = [reader.read(run, "jit_*_decode_multi") for reader in
           (program_share, decode_step_ms, decode_step_ms_dispatched)]
    assert got == ([25.0, pytest.approx(40.0), pytest.approx(40.0)] if found
                   else [None] * 3)
    # a literal name is looked up as it stands, and a run without a trace
    # names nothing
    assert resolve(run, programs[0]) == programs[0]
    assert resolve(run, "jit_absent") is None
    assert resolve({"trace": None}, "jit_*_decode_multi") is None

"""The harness is driven by data: a new cell, configuration, traffic mix,
per-layer metric and reader arrive as new files and new entries of
``BENCHMARK.json``, and no file that was there is edited. ``BENCHMARK.json``
alone says which cells report a metric, its layer and what it moves; a
metric's own file names its reader and the reader's arguments, nothing else,
so a cell joins a metric that is there by an entry and no edit."""
import json
import os
import shutil
import subprocess
import sys

from benchmarks.lib import configs

ROOT = configs.REPO_ROOT


def test_every_metric_has_its_file_and_reader():
    manifest = configs.load_manifest()
    for group, folder in (("per_layer", "layer_metrics"), ("end_to_end", "end_to_end")):
        for m in manifest[group]:
            spec = configs.load_json(folder, m["name"] + ".json")
            assert set(spec) == {"name", "reader", "args"}, m["name"]
            assert spec["name"] == m["name"]
            assert os.path.exists(os.path.join(
                configs.BENCH_DIR, "readers", spec["reader"] + ".py"))
    for w in manifest["workloads"]:
        cell = configs.load_cell(w["name"])
        assert os.path.exists(os.path.join(
            configs.BENCH_DIR, "drivers", cell["traffic_file"]["driver"] + ".py"))
        e2e = {m["name"] for m in configs.cell_metrics(cell, "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = configs.cell_metrics(cell, "per_layer")
        assert layer and all(m["moves"] in e2e for m in layer)
    for c in manifest["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cf = json.load(f)
        assert cf["source"] == c["source"] and cf["reduced"] == c["reduced"]
        assert set(cf["published"]) == set(c["reduced"])


def test_a_new_cell_is_files_alone(tmp_path):
    shutil.copytree(configs.BENCH_DIR, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "recorded_trace"))
    manifest = configs.load_manifest()
    before = {p: os.path.getmtime(os.path.join(dp, p))
              for dp, _, fs in os.walk(tmp_path / "benchmarks") for p in fs}
    bench = tmp_path / "benchmarks"
    cf = configs.load_json("configs", "yi-6b.json")
    (bench / "configs" / "dummy.json").write_text(json.dumps(
        {**cf, "name": "dummy", "source": "https://example.org/dummy"}))
    (bench / "traffic" / "dummy_mix.json").write_text(json.dumps(
        configs.load_json("traffic", "train_4k.json")))
    (bench / "readers" / "dummy_reader.py").write_text(
        "def read(run, scale):\n    return scale * run['x']\n")
    (bench / "layer_metrics" / "dummy.metric.json").write_text(json.dumps(
        {"name": "dummy.metric", "reader": "dummy_reader", "args": {"scale": 2}}))
    manifest["configs"].append({"name": "dummy", "source": "https://example.org/dummy",
                                "file": "benchmarks/configs/dummy.json",
                                "reduced": ["num_hidden_layers"], "why": "test"})
    manifest["workloads"].append({"name": "dummy_cell", "config": "dummy",
                                  "traffic": "dummy_mix", "chips": 1, "why": "test"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] in ("train_tokens_per_s", "train.mfu"):
            m["workloads"].append("dummy_cell")  # joins metrics that are there
    manifest["per_layer"].append(
        {"name": "dummy.metric", "unit": "things", "better": "higher",
         "source": "program_counter", "layer": "kernels",
         "moves": "train_tokens_per_s", "workloads": ["dummy_cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    code = (
        "import sys, json; sys.path.insert(0, '.'); sys.argv=['x']\n"
        "from benchmarks.lib import configs\n"
        "from benchmarks import run\n"
        "cell = configs.load_cell('dummy_cell')\n"
        "assert cell['config_file']['name'] == 'dummy'\n"
        "assert configs.load_module('drivers', cell['traffic_file']['driver'])\n"
        "names = [m['name'] for m in configs.cell_metrics(cell, 'per_layer')]\n"
        "assert names == ['train.mfu', 'dummy.metric'], names\n"
        "got = run.read_metrics(cell, 'per_layer', {'x': 21, 'train': None})\n"
        "e2e = run.read_metrics(cell, 'end_to_end', {'setup_s': 3.0, 'train': "
        "{'steps': 2, 'tokens_per_step': 10, 'span_s': 4.0}})\n"
        "print(json.dumps([got, e2e]))\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, check=True,
                         capture_output=True, text=True,
                         env={**os.environ, "PYTHONPATH": ROOT})
    got, e2e = json.loads(out.stdout.strip().splitlines()[-1])
    assert got == {"dummy.metric": {"value": 42, "unit": "things"}}
    assert e2e["train_tokens_per_s"]["value"] == 5.0 and e2e["setup_s"]["value"] == 3.0
    after = {p: os.path.getmtime(os.path.join(dp, p))
             for dp, _, fs in os.walk(bench) for p in fs if "__pycache__" not in dp}
    assert all(after[p] == t for p, t in before.items()), "a file that was there changed"

"""What PR 57 added to the benchmark, on the CPU: the looped reference and
its controls at the configuration's tiny size, every new roofline count
against a hand count, the readers the new metrics name on a hand-made run
(the untagged ``kind`` among them), the new cell found by name as files
alone, its traffic's multiset whatever the seed, and the ``--allow-cpu``
rehearsal of the whole cell."""
import json
import os
import subprocess
import sys
from collections import Counter
from types import SimpleNamespace as NS

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers.serve_looped import looped_config
from benchmarks.lib import configs
from benchmarks.lib import weights_looped as W
from benchmarks.reference import looped as R
from benchmarks.roofline import looped_decode_attention as attn_count
from benchmarks.roofline import looped_decode_multi as count
from benchmarks.roofline import looped_prefill_batch as prefill_count

CELL = "ouro26b_solve_closed"
CONFIG = "ouro-2.6b.json"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
MINE = {"kernel.looped_decode_roofline", "kernel.looped_prefill_roofline",
        "kernel.paged_decode_attention_roofline.solve",
        "engine.slots_live_share.solve"}
JOINED = {"engine.decode_step_ms.batch", "engine.prefill_share.batch",
          "kernel.decode_kv_read_amplification.batch",
          "kernel.unnamed_share.batch", "engine.compiles_in_window.batch",
          "engine.loop_blocked_share.batch",
          "engine.prompts_per_prefill_counted.batch",
          "engine.prefill_pad_waste.batch",
          "engine.admit_undrained_share.batch", "device.idle_share.batch",
          "device.idle_in_sync_emit.batch", "device.idle_in_admit.batch",
          "device.idle_in_dispatch.batch", "device.idle_unattributed.batch",
          "setup.programs_from_cache_share", "setup.program_trace_lower_s",
          "setup.backend_start_s", "setup.weights_s",
          "setup.program_cache_read_s", "setup.program_compile_s",
          "setup.parts_table_s", "setup.unaccounted_s"}
# the seven part_share entries that read null since PR 56 (no ``programs``
# in their files): the cell joins none of them
NULL_SINCE_56 = {"kernel.router_share", "kernel.ssm_share.reason",
                 "kernel.summary_share.bytes", "kernel.delta_share.think",
                 "kernel.cca_mix_share.cot", "kernel.head_share.cot",
                 "kernel.indexer_select_share.longctx"}


def published():
    return looped_config(configs.load_json("configs", CONFIG))


def tiny():
    cf = configs.load_json("configs", CONFIG)
    return looped_config({**cf, **cf["tiny"]})


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_program_forward_agrees_and_the_controls_do_not(seed):
    from benchmarks.control_looped import CONTROLS
    from ray_tpu.models.looped import looped_forward

    cfg = tiny()
    assert (cfg.n_layers, cfg.n_passes, cfg.planes, cfg.n_heads,
            cfg.n_kv_heads, cfg.head_dim, cfg.d_ff) == (3, 4, 12, 4, 4, 16, 176)
    tokens = np.random.default_rng(seed % 1000).integers(3, cfg.vocab_size, 40)
    params = W.make_params(W.seed_key(seed), cfg, 1)
    want = R.forward(seed, cfg, tokens, zero_col=1)
    got, depth = looped_forward(params, jnp.asarray(tokens)[None], cfg)
    assert rel(got[0], want["logits"]) < 1e-5
    assert (np.asarray(depth[0]) == want["depth"]).all() and (want["depth"] == 4).all()
    assert not np.asarray(want["logits"])[:, 1].any()   # the eos id's column
    assert sorted(want["k"]) == list(range(12))
    assert want["k"][0].shape == want["v"][11].shape == (40, 64)
    assert want["h"].shape == (4, 40, 64) and want["lam"].shape == (4, 40)
    assert np.allclose(want["pdf"].sum(0), 1.0, atol=1e-6)
    # the passes do not converge, and the gate decides nothing at 1 and
    # something below it
    h = np.asarray(want["h"])
    assert 0.1 < rel(h[2], h[3]) < 1.0
    lam = want["lam"]
    assert 0.1 < np.median(lam) < 0.9 and lam.max() < 1.0
    half = R.forward(seed, cfg, tokens, variant={"exit_threshold": 0.5},
                     planes=())
    assert len(set(half["depth"].tolist())) >= 3
    # a lower precision stands apart everywhere, the next one below further,
    # and a later pass's rows stand on more of it
    errs = {m: [rel(low["k"][p], want["k"][p]) for p in (0, 11)]
            for m in ("bfloat16", "fp8")
            for low in [R.forward(seed, cfg, tokens, mode=m)]}
    assert errs["fp8"][0] > 2.5 * errs["bfloat16"][0] > 1e-4, errs
    assert errs["bfloat16"][1] > errs["bfloat16"][0]
    # what changes a layer's mathematics moves the planes from pass 1 on;
    # what changes how the passes are chained leaves pass 1 alone; what
    # changes the exit leaves every plane alone and moves the logits
    chained = {"read_first", "read_previous", "carry_raw", "positions_advance"}
    exits = {"threshold_half", "head_on_mean", "passes_5"}
    for name, variant in CONTROLS.items():
        if variant.get("read_from") == "prompt":
            variant = {**variant, "read_from": 25}
        other = R.forward(seed, cfg, tokens, variant=variant)
        first = max(rel(other[n][2], want[n][2]) for n in "kv")    # pass 1
        last = max(rel(other[n][11], want[n][11]) for n in "kv")   # pass 4
        if name in exits:
            assert first == last == 0.0, name
            assert rel(other["logits"], want["logits"]) > 0.05, name
        elif name in chained:
            assert first == 0.0 and last > 0.01, name
        elif name == "passes_3":
            assert first == 0.0 and last == 1.0, name   # no fourth pass: zeros
        elif name == "one_plane":   # sound over the prompt, then not
            assert rel(other["k"][11][:25], want["k"][11][:25]) < 1e-5
            assert rel(other["k"][11][25:], want["k"][11][25:]) > 0.01
        else:
            assert first > 1e-3 and last > 0.01, name


def test_the_published_configuration_is_what_the_program_gets():
    cf = configs.load_json("configs", CONFIG)
    cfg = looped_config(cf)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff,
            cfg.rope_theta) == (2048, 16, 16, 128, 5632, 1e6)
    assert (cfg.n_layers, cfg.n_passes, cfg.planes, cfg.exit_threshold) == (
        48, 4, 192, 1.0)
    assert (cfg.vocab_size, cfg.max_seq_len, cfg.rms_norm_eps, cfg.dtype) == (
        49152, 640, 1e-6, "bfloat16")
    assert cf["published"] == {"max_position_embeddings": 65536}
    assert cf["reduced"] == ["max_position_embeddings"]
    # every key of the catalog's config under its own name, unchanged but
    # for the one in ``reduced``
    rows = [json.loads(line) for line in open(CATALOG)] if os.path.exists(
        CATALOG) else []
    for row in rows:
        if row["name"] == "Ouro-2.6B":
            assert cf["source"] == row["source_url"]
            assert {k: cf[k] for k in row["config"] if k not in cf["reduced"]} == {
                k: v for k, v in row["config"].items() if k not in cf["reduced"]}
    assert len(cf["layer_types"]) == 48 == cf["max_window_layers"]
    assert cf["sliding_window"] is None and cf["use_sliding_window"] is False
    assert [a[:3] for a in cf["assumed"]] == [
        "(a)", "(b)", "(c)", "(d)", "(e)", "(f)", "(g)"]
    assert "one v5e chip holds the model whole" in cf["deployment"]
    assert cf["engine"] == {"max_batch": 24, "page_size": 16, "n_pages": 352,
                            "max_seq_len": 640, "eos_id": 1}
    for key, bad in (("sliding_window", 4096), ("tie_word_embeddings", True),
                     ("rope_scaling", {"type": "yarn"}), ("model_type", "llama")):
        with pytest.raises(ValueError, match=key):
            looped_config({**cf, key: bad})
    with pytest.raises(ValueError, match="full-attention layers alone"):
        looped_config({**cf, "layer_types": ["sliding_attention"] * 48})


def test_decode_count_against_a_hand_count():
    cfg = published()
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert layer == 51_380_224                                # 16.78 + 34.60 M
    assert count.layer_bytes(cfg) == (layer + 4 * 2048) * 2 == 102_776_832
    assert count.kv_position_bytes(cfg) == 2 * 16 * 128 * 2 == 8192
    assert cfg.planes * 8192 == 1_572_864                     # 1.5 MiB a position
    # every layer once A PASS: 4 x 4.93 GB; head, final norm and gate once;
    # 4,400 live positions in 192 planes
    weights = 4 * 48 * 102_776_832
    assert weights == 19_733_151_744
    once = (2048 * 49152 + 2048) * 2 + (2048 + 1) * 4
    kv = 4400 * 1_572_864
    got = count.bytes_per_step(cfg, 4400)
    assert got == weights + once + kv == 26_855_092_228
    assert abs(count.least_seconds(cfg, PEAKS, 24, 4400) - got / 819e9) < 1e-12
    assert 0.0327 < got / 819e9 < 0.0329                      # 33 ms a step
    assert 0.73 < weights / got < 0.74 and 0.25 < kv / got < 0.26
    # an unlooped model of these widths: one pass, a quarter of the planes
    assert (weights + kv) / 4 + once < 6.9e9
    assert count.flops_per_step(cfg, 24, 4400) == (
        2 * 24 * (192 * layer + 2048 * 49152) + 4 * 4400 * 16 * 128 * 192)
    # the attention kernel's own: K and V within reach, q in and o out, a
    # call a plane
    call = 4400 * 8192 + 2 * 24 * 16 * 128 * 2
    assert attn_count.bytes_per_call(cfg, 24, 4400) == call
    assert attn_count.least_seconds(cfg, PEAKS, 24, "", 4400) == (
        192 * call / 819e9)
    with pytest.raises(ValueError, match="one kind of page"):
        attn_count.least_seconds(cfg, PEAKS, 24, "kv", 1)


def test_prefill_count_against_a_hand_count():
    cfg = published()
    layer = 51_380_224
    pairs = 300 * 301 / 2
    assert prefill_count.attention_flops(cfg, [300]) == 4 * 16 * 128 * 192 * pairs
    want = 2 * 300 * 192 * layer + 4 * 16 * 128 * 192 * pairs + 2 * 2048 * 49152
    assert prefill_count.flops(cfg, [300.0]) == want
    assert 19.7e9 < 2 * 192 * layer < 19.8e9     # 19.7 GFLOP a token: 4 passes
    assert prefill_count.flops(cfg, [128.0] * 2) == 2 * prefill_count.flops(
        cfg, [128.0])
    assert prefill_count.least_seconds(cfg, PEAKS, [300.0]) == want / 197e12


def _run(steps=12):
    cfg = published()

    def snap(scale):
        def s(v):
            return {"sum": v * steps * scale}
        return {"steps": steps * scale, "block_buckets": [4, 8, 16, 32, 64],
                "program_parts": {}, "stages": {
            "rt_llm_looped_live_slots_total": {"": s(15.0)},
            "rt_llm_looped_exit_depth_total": {"": s(60.0)},
            "rt_llm_decode_kv_tokens_live_total": {"": s(4400.0)},
            "rt_llm_decode_kv_tokens_read_total": {"": s(4500.0)}}}

    # unary replies: a record has no first-token stamp
    recs = [{"sent": -10.0, "last": 11.0, "done": 11.0, "tokens": 211,
             "prompt_len": 193} for _ in range(15)]
    return {"cfg": cfg, "engine": {"max_batch": 24}, "peaks": PEAKS,
            "counters": {"before": snap(1), "after": snap(2)},
            "recs_all": recs,
            "trace": {"busy_s": 2.0, "window_s": 2.0, "programs": {
                "jit_looped_decode_multi": {
                    "durations": [0.4] + [0.2] * 3, "seconds": 1.0},
                "jit_looped_prefill_batch": {"durations": [0.1],
                                             "seconds": 0.1},
                "jit_merge_carry": {"durations": [0.001], "seconds": 0.001}},
                "ops": [["pallas:_paged_decode_attention:bf16_24_16_128", 0.4],
                        ["pallas:gqa_prefill_attention:bf16_4_384_2048", 0.02]]},
            "trace_window": (0.0, 1.0),
            "dispatched_steps": [8, 4, 4, 4],
            "admitted_lens": [212.0] * 4,
            "part_seconds": {"stale": set(), "unnamed_ops": [], "seconds": {
                ("jit_looped_decode_multi", "attention"): 0.5,
                ("jit_looped_prefill_batch", "attention"): 0.1,
                ("jit_looped_decode_multi", "kv_write"): 0.04,
                ("jit_merge_carry", "unnamed"): 0.001,
                ("jit_looped_decode_multi", "ffn"): 0.6}}}


def test_new_readers_on_a_hand_made_run():
    from benchmarks import run as bench_run
    from benchmarks.lib import stats

    cell = configs.load_cell(CELL)
    run = _run()
    got = {k: v["value"] for k, v in
           bench_run.read_metrics(cell, "per_layer", run).items()}
    assert got["engine.slots_live_share.solve"] == pytest.approx(62.5)
    assert got["engine.decode_step_ms.batch"] == pytest.approx(50.0)
    assert got["engine.prefill_share.batch"] == pytest.approx(5.0)
    assert got["kernel.decode_kv_read_amplification.batch"] == pytest.approx(
        4500 / 4400)
    # 20 steps in the trace (an 8-step and three 4-step blocks)
    # the live positions are the program's own count, 4,400 a step: the
    # clients' stamps of a unary reply say nothing of them
    assert stats.live_kv_tokens(run["recs_all"], 0.0, 1.0) == 0.0
    assert got["kernel.looped_decode_roofline"] == pytest.approx(
        100 * 20 * count.least_seconds(run["cfg"], PEAKS, 24, 4400.0) / 1.0)
    # the untagged sample of a one-kind family: ``kind`` ""
    assert got["kernel.paged_decode_attention_roofline.solve"] == pytest.approx(
        100 * 20 * attn_count.least_seconds(run["cfg"], PEAKS, 24, "",
                                            4400.0) / 0.4)
    assert got["kernel.looped_prefill_roofline"] == pytest.approx(
        100 * prefill_count.flops(run["cfg"], [212.0] * 4) / 197e12 / 0.1)
    for name in ("kernel.looped_decode_roofline",
                 "kernel.paged_decode_attention_roofline.solve",
                 "kernel.looped_prefill_roofline"):
        assert 0 < got[name] < 100, name
    # a program without the counters or the part table reads as nothing, and
    # nothing raises
    bare = _run()
    for snap in bare["counters"].values():
        snap["stages"] = {}
    bare["trace"]["ops"] = []
    bare["trace"]["programs"] = {}
    bare["dispatched_steps"] = []
    bare["admitted_lens"] = []
    bare["part_seconds"] = None
    assert not set(bench_run.read_metrics(cell, "per_layer", bare)) & MINE


def test_the_new_cell_is_found_by_name_as_files_alone():
    manifest = configs.load_manifest()
    cell = configs.load_cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "solve_closed"
    traffic, cf = cell["traffic_file"], cell["config_file"]
    assert configs.load_module("drivers", traffic["driver"]).run
    assert traffic["driver"] in cf["correct_limits"]
    assert configs.load_module("reference", cf["reference"]).forward
    assert (cf["engine"]["max_batch"], traffic["callers"], traffic["list_size"],
            traffic["stream"]) == (24, 32, 256, False)
    assert (traffic["caller_stagger_s"], traffic["lead_in_s"]) == (0.25, 20)
    assert traffic["prompt"] == {"dist": "lognormal", "median": 192,
                                 "sigma": 0.5, "lengths": [128, 256, 384]}
    assert traffic["output"] == {"dist": "uniform", "min": 64, "max": 256}
    assert traffic["max_total"] == 640 == cf["engine"]["max_seq_len"]
    assert traffic["warm_waves"] == [1, 2, 4] and traffic["trace_seconds"] == 0.2
    assert traffic["reference_check"] == [
        {"prompt_len": 384, "max_tokens": 24},
        {"prompt_len": 100, "max_tokens": 64}]
    e2e = {m["name"] for m in configs.cell_metrics(cell, "end_to_end")}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    layer = configs.cell_metrics(cell, "per_layer")
    assert {m["moves"] for m in layer} == {"serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in layer}
    # this PR's, and those the cell joined by name: a later PR may add more
    assert MINE | JOINED <= names and not names & NULL_SINCE_56
    for m in layer:
        spec = configs.load_json("layer_metrics", m["name"] + ".json")
        assert set(spec) == {"name", "reader", "args"}
        assert configs.load_module("readers", spec["reader"]).read
    # benchmarks/tests/test_sink_moe_cell.py holds the list to 112
    assert len(manifest["per_layer"]) <= 112
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == cf["reduced"] and set(cf["published"]) == set(cf["reduced"])
    assert entry["source"] == cf["source"]
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    # every limit is judged on a name the replica reports
    assert set(cf["correct_limits"][traffic["driver"]]) <= {
        n + w for w in (".prefill", ".decode")
        for n in ("kv_rel_err", *(f"pass{u}_kv_rel_err" for u in (1, 2, 3, 4)))
    } | {"token_logit_gap_p50", "token_logit_gap"}
    from ray_tpu.llm.looped import WAVE_LIMIT
    assert WAVE_LIMIT == (4, 1536) and "wave_limit" not in traffic


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_the_traffics_multiset_whatever_the_seed(seed, monkeypatch):
    """256 quantiles of lognormal(192, 0.5) snapped to the three lengths
    (128 / 89 / 39, mean 212), outputs uniform 64-256 (mean 160): the
    multiset ``lib/traffic.py`` makes, which this driver cycles in ONE order
    whatever the seed; a request reserves 12-40 pages, 24 on average."""
    from benchmarks.drivers import serve_looped as D
    from benchmarks.lib import traffic as T

    traffic = configs.load_cell(CELL)["traffic_file"]
    a, b = D.even_list(traffic), T.closed_list(traffic, seed)
    mix = Counter(p for p, _ in a)
    assert mix == {128: 128, 256: 89, 384: 39} == Counter(p for p, _ in b)
    assert sum(p for p, _ in a) / 256 == 211.5
    assert sum(o for _, o in a) / 256 == 160
    assert sorted(o for _, o in a) == sorted(o for _, o in b)
    assert max(p + o for p, o in a) <= traffic["max_total"]
    pages = [-(-(p + o) // 16) for p, o in a]
    assert (min(pages), max(pages)) == (12, 40) and 23 < np.mean(pages) < 24.5
    # the prefill programs a wave limit of (4, 1536) lets these lengths form
    assert D.pads_of(T.quantile_lengths(traffic["prompt"], 4096), 16) == [
        128, 256, 384]
    assert D.pads_of((rc["prompt_len"] for rc in traffic["reference_check"]),
                     16) == [112, 384]
    assert all(w * p <= 1536 for p in (128, 256, 384) for w in (1, 2, 4))
    monkeypatch.setattr(D, "setup", lambda cell, args, clock: {"traffic": traffic})
    monkeypatch.setattr(D, "window", lambda ctx, s, *rest: T.closed_list(
        ctx["traffic"], s))
    got = D.run({}, NS(seed=seed, seconds=1, trace=0, trace_seconds=1), None)
    assert got == a and T.closed_list(traffic, 1) != T.closed_list(traffic, 2)


def test_the_cell_rehearses_on_the_cpu_at_tiny_sizes(tmp_path):
    """The whole cell through ``run.py --allow-cpu``: deploy, warm-up, both
    checked requests against the reference in the planes of all four passes
    (the second fills neither a page nor a pad), the closed loop, the
    readers."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_DEBUG_DIR": str(tmp_path)}
    done = subprocess.run(
        [sys.executable, os.path.join(configs.BENCH_DIR, "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 11), "--seconds", "4",
         "--trace", "1", "--allow-cpu"], env=env, cwd=configs.REPO_ROOT,
        capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu" and not line["correct"]
    assert line["failed"] == 0 and line["attempted"] > 0
    kept = json.load(open(tmp_path / f"{CELL}.{2**31 + 11}.1.json"))
    ref = kept["reference"]
    for span in ("prefill", "decode"):
        assert ref[f"kv_rel_err.{span}"] < 1e-5
        for u in (1, 2, 3, 4):
            assert ref[f"pass{u}_kv_rel_err.{span}"] < 1e-5, (u, span)
    assert ref["token_logit_gap"] == 0.0 and ref["repeats"]
    assert 0.1 < ref["h43"] < 1.0 and ref["exit_depth_mean"] == 4.0
    assert ref["planes_compared"] == [0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11]
    rehearsed = line["rehearsal"]
    assert rehearsed["cpu-rehearsal.engine.compiles_in_window.batch"] == 0
    assert 0 < rehearsed["cpu-rehearsal.engine.slots_live_share.solve"] <= 100

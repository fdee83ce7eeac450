"""What PR 40 added to the benchmark, on the CPU: the windowed exact +
pooled-pair attention reference and its controls at the configuration's tiny
size, every new roofline count against a hand count, the new readers on a
hand-made run, the new cell found by name as files alone, its traffic's
multiset whatever the seed, and the ``--allow-cpu`` rehearsal of the whole
cell."""
import json
import os
import subprocess
import sys
from collections import Counter
from types import SimpleNamespace as NS

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers.serve_eva import eva_config
from benchmarks.lib import configs
from benchmarks.lib import weights_eva as W
from benchmarks.reference import eva as R
from benchmarks.roofline import eva_decode_multi as count
from benchmarks.roofline import eva_prefill_attention as attention_count
from benchmarks.roofline import eva_prefill_batch as prefill_count

CELL = "evabyte_bytes_closed"
CONFIG = "evabyte-6.5b.json"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
# this PR's per-layer metrics; the cell reports these AND whatever later PRs
# let it join (PERF.md section 7: a test that pins the whole set breaks then)
MINE = {"engine.decode_step_ms.batch", "engine.prefill_share.batch",
        "kernel.eva_decode_roofline", "kernel.eva_decode_attention_roofline",
        "kernel.eva_prefill_roofline", "kernel.eva_prefill_attention_roofline",
        "eva.pair_rows_share.bytes", "cache.eva_rows_held_share.bytes",
        "kernel.summary_share.bytes", "kernel.decode_kv_read_amplification.batch"}


def published():
    return eva_config(configs.load_json("configs", CONFIG))


def tiny():
    cf = configs.load_json("configs", CONFIG)
    return eva_config({**cf, **cf["tiny"]})


def rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_program_forward_agrees_and_the_controls_do_not(seed):
    from ray_tpu.models.eva import eva_forward

    cfg = tiny()
    assert (cfg.window_size, cfg.chunk_size, cfg.n_pred_heads) == (32, 4, 8)
    tokens = np.random.default_rng(seed % 1000).integers(3, cfg.vocab_size, 90)
    params = W.make_params(W.seed_key(seed), cfg, 2)
    want = R.forward(seed, cfg, tokens, q_block=32, zero_col=2)
    got = eva_forward(params, jnp.asarray(tokens)[None], cfg)[0]
    assert got.shape == (90, 8, cfg.vocab_size)
    assert rel(got, want["logits"]) < 1e-5
    assert want["k"][0].shape == (90, 64) and want["kh"][1].shape == (22, 64)
    # a lower precision stands apart everywhere, the next one below further
    errs = {m: rel(R.forward(seed, cfg, tokens, mode=m, q_block=32,
                             zero_col=2)["kh"][0], want["kh"][0])
            for m in ("bfloat16", "fp8")}
    assert errs["fp8"] > 2.5 * errs["bfloat16"] > 1e-4, errs
    # layer 0's rows precede every attention: the controls of the attention
    # leave them alone and move what lies behind them; the controls of the
    # pooling move layer 0's pairs
    for variant in ({"own_pairs": True}, {"sliding": True}, {"two_softmax": True}):
        other = R.forward(seed, cfg, tokens, variant=variant, q_block=32, zero_col=2)
        assert rel(other["k"][0], want["k"][0]) < 1e-6, variant
        assert rel(other["kh"][0], want["kh"][0]) < 1e-6, variant
        assert rel(other["k"][1], want["k"][1]) > 1e-3, variant
    for variant in ({"pool": "mean"}, {"no_mu": True}, {"unrotated_pairs": True},
                    {"no_pairs_from": 41}):
        other = R.forward(seed, cfg, tokens, variant=variant, q_block=32, zero_col=2)
        assert rel(other["k"][0], want["k"][0]) < 1e-6, variant
        assert rel(other["kh"][0], want["kh"][0]) > 0.02, variant
    low = R.forward(seed, cfg, tokens, variant={"residual": "bfloat16"},
                    q_block=32, zero_col=2)
    assert rel(low["k"][1], want["k"][1]) > 1e-4
    # pad positions pooled: only the chunk the prompt ended in differs
    short = tokens[:30]
    padded = R.forward(seed, cfg, short, q_block=32, zero_col=2,
                       variant={"pad": 24, "pad_from": 21})
    plain = R.forward(seed, cfg, short, q_block=32, zero_col=2)
    assert rel(padded["kh"][0][:5], plain["kh"][0][:5]) < 1e-6
    assert rel(padded["kh"][0][5], plain["kh"][0][5]) > 0.02
    with pytest.raises(ValueError, match="sees the padded pairs"):
        R.forward(seed, cfg, tokens, variant={"pad": 24, "pad_from": 21})


def test_the_published_configuration_is_what_the_program_gets():
    cf = configs.load_json("configs", CONFIG)
    cfg = eva_config(cf)
    assert (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff) == (4096, 32, 128, 11008)
    assert (cfg.window_size, cfg.chunk_size, cfg.chunks_per_window) == (2048, 16, 128)
    assert (cfg.vocab_size, cfg.n_pred_heads, cfg.n_layers) == (320, 8, 8)
    assert (cfg.rms_norm_eps, cfg.rope_theta, cfg.max_seq_len, cfg.dtype) == (
        1e-5, 1e5, 32768, "bfloat16")
    assert cf["published"] == {"num_hidden_layers": 32}
    assert cf["reduced"] == ["num_hidden_layers"]
    # every key of the catalog's config under its own name, unchanged but
    # for the one in ``reduced``
    catalog = {"attention_bias": False, "attention_class": "eva",
               "chunk_size": 16, "fp32_ln": False, "fp32_logits": True,
               "fp32_skip_add": True, "hidden_act": "silu", "hidden_size": 4096,
               "init_cutoff_factor": None, "init_fn": "v2", "init_std": 0.01275,
               "intermediate_size": 11008, "lazy_init": True,
               "max_position_embeddings": 32768, "max_seq_length": 32768,
               "mixedp_attn": True, "model_type": "evabyte",
               "norm_add_unit_offset": True, "num_attention_heads": 32,
               "num_chunks": None, "num_key_value_heads": 32,
               "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None,
               "rope_theta": 100000, "tie_word_embeddings": False,
               "vocab_size": 320, "window_size": 2048}
    assert {k: cf[k] for k in catalog} == catalog
    assert [a[:3] for a in cf["assumed"][:4]] == ["(a)", "(b)", "(c)", "(d)"]
    assert "four pipeline stages of 8 layers" in cf["deployment"]
    assert cf["engine"] == {"max_batch": 24, "page_size": 16, "max_seq_len": 32768,
                            "n_pages": {"window": 3073, "summary": 1300},
                            "eos_id": 2}
    assert cf["engine"]["n_pages"]["window"] == 1 + 24 * 2048 // 16
    for key, other in (("fp32_skip_add", False), ("attention_class", "full"),
                       ("norm_add_unit_offset", False), ("fp32_logits", False)):
        with pytest.raises(ValueError, match=key):
            eva_config({**cf, key: other})
    with pytest.raises(ValueError, match="KV head"):
        eva_config({**cf, "num_key_value_heads": 8})


def test_decode_count_against_a_hand_count():
    cfg = published()
    layer = 4 * 4096 * 4096 + 3 * 4096 * 11008            # 202,375,168
    assert count.layer_params(cfg) == layer == 202_375_168
    assert count.row_bytes(cfg) == 32 * 128 * 2 * 2 == 16_384
    # 24 slots at 1,000 window rows and 560 pairs each, 12 pairs written
    rows_w, rows_s, pairs = 24_000.0, 13_440.0, 12.0
    weights = (8 * layer + 4096 * 320) * 2                # head 0's columns
    attended = 8 * (24_000 + 13_440) * 16_384
    written = 12 * (1 + 16) * 16_384
    assert count.attended_bytes(cfg, rows_w, rows_s) == attended == 4_907_335_680
    assert count.bytes_per_step(cfg, rows_w, rows_s, pairs) == (
        weights + attended + written) == 8_151_302_144
    assert count.least_seconds(cfg, PEAKS, rows_w, rows_s, pairs) == pytest.approx(
        8_151_302_144 / 819e9)
    # the walks alone: the attended rows, q and o of 12 x 16 / 8 = 24 slots
    assert count.attention_bytes(cfg, rows_w, rows_s, pairs) == (
        attended + 2 * 24 * 8 * 4096 * 2)


def test_prefill_count_against_a_hand_count():
    cfg = published()
    # a prompt of 5,000: two whole windows and 904 positions of a third
    exact = 2 * (2048 * 2049 // 2) + 904 * 905 // 2
    pooled = 128 * (2048 * 1 + 904 * 2)                   # window 1 sees 128,
    assert attention_count.rows_attended(cfg, 5000) == exact + pooled  # window 2 256
    assert attention_count.rows_attended(cfg, 5000) == sum(
        t % 2048 + 1 + 128 * (t // 2048) for t in range(5000))
    assert attention_count.rows_attended(cfg, 1) == 1
    assert attention_count.rows_attended(cfg, 2048) == 2048 * 2049 // 2
    att = 4 * 32 * 128 * 8 * (exact + pooled)
    assert attention_count.flops(cfg, [5000]) == att
    want = (2 * 5000 * 8 * 202_375_168 + att + 4 * 5000 * 8 * 4096
            + 2 * 4096 * 320)
    assert prefill_count.flops(cfg, [5000]) == want
    assert prefill_count.flops(cfg, [5000, 5000]) == 2 * want
    assert prefill_count.least_seconds(cfg, PEAKS, [5000]) == pytest.approx(
        want / 197e12)


def _run(steps=12):
    cfg = published()

    def snap(scale):
        def s(v):
            return {"sum": v * steps * scale}
        return {"steps": steps * scale, "block_buckets": [4, 8, 16, 32, 64],
                "program_parts": {}, "stages": {
            "rt_llm_eva_pairs_written_total": {"": s(12.0)},
            "rt_llm_decode_kv_tokens_live_total": {
                "": s(18_720.0), "window": s(24_000.0), "summary": s(13_440.0)},
            "rt_llm_decode_kv_tokens_read_total": {
                "": s(18_900.0), "window": s(24_192.0), "summary": s(13_608.0)},
            "rt_llm_pages_drawn_total": {"window": s(128.0), "summary": s(40.0)}}}

    return {"cfg": cfg, "engine": {"max_batch": 24}, "peaks": PEAKS,
            "counters": {"before": snap(1), "after": snap(2)},
            "trace": {"busy_s": 2.0, "window_s": 2.0, "programs": {
                "jit_eva_decode_multi": {
                    "durations": [0.2] * 3 + [0.1] * 4, "seconds": 1.0},
                "jit_eva_prefill_batch": {"durations": [0.8], "seconds": 0.8}},
                "ops": [["pallas:paged_window_part:f32_24_32_128", 0.3],
                        ["pallas:paged_attention_part:f32_24_32_128", 0.2],
                        ["pallas:eva_prefill_attention:bf16_1_15360_4096", 0.25],
                        ["fusion.12:bf16_24_4096", 0.1]]},
            "trace_window": (0.0, 1.0),
            "dispatched_steps": [64, 8, 8, 8, 4, 4, 4, 4],
            "admitted_lens": [7680.0] * 2,
            "part_seconds": {"stale": set(), "unnamed_ops": [], "seconds": {
                ("jit_eva_decode_multi", "attention"): 0.5,
                ("jit_eva_decode_multi", "summary"): 0.05,
                ("jit_eva_prefill_batch", "summary"): 0.03,
                ("jit_eva_prefill_batch", "attention"): 0.25}}}


def test_new_readers_on_a_hand_made_run():
    from benchmarks import run as bench_run

    cell = configs.load_cell(CELL)
    run = _run()
    got = {k: v["value"] for k, v in
           bench_run.read_metrics(cell, "per_layer", run).items()}
    assert MINE <= set(got)
    assert got["engine.decode_step_ms.batch"] == pytest.approx(25.0)
    assert got["engine.prefill_share.batch"] == pytest.approx(40.0)
    assert got["kernel.decode_kv_read_amplification.batch"] == pytest.approx(
        18_900 / 18_720)
    assert got["eva.pair_rows_share.bytes"] == pytest.approx(
        100 * 13_440 / (24_000 + 13_440))
    # 168 pages of 16 rows for the 40 x 256 positions the pairs' pages stand for
    assert got["cache.eva_rows_held_share.bytes"] == pytest.approx(
        100 * 168 * 16 / (40 * 256))
    assert got["kernel.summary_share.bytes"] == pytest.approx(100 * 0.08 / 2.0)
    # 40 steps in the trace (three 8-step and four 4-step blocks)
    assert got["kernel.eva_decode_roofline"] == pytest.approx(
        100 * 40 * (8_151_302_144 / 819e9) / 1.0)
    assert got["kernel.eva_decode_attention_roofline"] == pytest.approx(
        100 * 40 * count.attention_bytes(run["cfg"], 24_000.0, 13_440.0, 12.0)
        / 819e9 / 0.5)
    assert got["kernel.eva_prefill_roofline"] == pytest.approx(
        100 * prefill_count.flops(run["cfg"], [7680.0] * 2) / 197e12 / 0.8)
    assert got["kernel.eva_prefill_attention_roofline"] == pytest.approx(
        100 * attention_count.flops(run["cfg"], [7680.0] * 2) / 197e12 / 0.25)
    for name in ("kernel.eva_decode_roofline", "kernel.eva_prefill_roofline",
                 "kernel.eva_decode_attention_roofline",
                 "kernel.eva_prefill_attention_roofline"):
        assert 0 < got[name] < 100, name
    # a program without the counters, the kernels or the part table (the
    # parent) reads as nothing, and nothing raises
    bare = _run()
    for snap in bare["counters"].values():
        snap["stages"] = {}
    bare["trace"]["ops"] = []
    bare["trace"]["programs"] = {}
    bare["dispatched_steps"] = []
    bare["admitted_lens"] = []
    bare["part_seconds"] = None
    assert not MINE & set(bench_run.read_metrics(cell, "per_layer", bare))


def test_the_new_cell_is_found_by_name_as_files_alone():
    manifest = configs.load_manifest()
    cell = configs.load_cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "bytes_closed"
    traffic, cf = cell["traffic_file"], cell["config_file"]
    assert configs.load_module("drivers", traffic["driver"]).run
    assert traffic["driver"] in cf["correct_limits"]
    assert configs.load_module("reference", cf["reference"]).forward
    assert (cf["engine"]["max_batch"], traffic["callers"], traffic["list_size"],
            traffic["stream"]) == (24, 32, 256, False)
    assert (traffic["caller_stagger_s"], traffic["lead_in_s"]) == (0.25, 20)
    assert traffic["prompt"] == {"dist": "lognormal", "median": 8192, "sigma": 0.5,
                                 "lengths": [4608, 7680, 12288, 15360]}
    assert traffic["output"] == {"dist": "uniform", "min": 512, "max": 1536}
    assert traffic["max_total"] == 16896 <= cf["engine"]["max_seq_len"]
    assert traffic["warm_waves"] == [1, 2]
    assert traffic["reference_check"] == [
        {"prompt_len": 12280, "max_tokens": 24},
        {"prompt_len": 200, "max_tokens": 24}]
    e2e = {m["name"] for m in configs.cell_metrics(cell, "end_to_end")}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    layer = configs.cell_metrics(cell, "per_layer")
    assert {m["moves"] for m in layer} >= {"serve_tokens_per_s"}
    names = {m["name"] for m in layer}
    assert names >= MINE | {
        "kernel.unnamed_share.batch", "engine.compiles_in_window.batch",
        "engine.loop_blocked_share.batch",
        "engine.prompts_per_prefill_counted.batch",
        "engine.prefill_pad_waste.batch", "device.idle_share.batch",
        "device.idle_in_sync_emit.batch", "device.idle_in_admit.batch",
        "device.idle_in_dispatch.batch", "device.idle_unattributed.batch"}
    for m in layer:
        spec = configs.load_json("layer_metrics", m["name"] + ".json")
        assert set(spec) == {"name", "reader", "args"}
        assert configs.load_module("readers", spec["reader"]).read
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == cf["reduced"] and set(cf["published"]) == set(cf["reduced"])
    assert entry["source"] == cf["source"]
    # every limit is judged on a name the replica reports
    assert set(cf["correct_limits"][traffic["driver"]]) <= {
        p + n + w for p in ("", "short.") for w in (".prefill", ".decode")
        for n in ("kv_rel_err", "pair_rel_err", "deep_row_err_p50",
                  "deep_pair_err_p50")}
    from ray_tpu.llm.eva import WAVE_LIMIT
    assert WAVE_LIMIT == (8, 16384) and "wave_limit" not in traffic


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_the_traffics_multiset_whatever_the_seed(seed, monkeypatch):
    """256 quantiles of lognormal(8192, 0.5) snapped to the four lengths are
    72 / 95 / 51 / 38 (mean 8,874), outputs uniform 512-1,536 (mean 1,024):
    the multiset ``lib/traffic.py`` makes, which this driver cycles in ONE
    order whatever the seed."""
    from benchmarks.drivers import serve_eva as D
    from benchmarks.lib import traffic as T

    traffic = configs.load_cell(CELL)["traffic_file"]
    a, b = D.even_list(traffic), T.closed_list(traffic, seed)
    assert Counter(p for p, _ in a) == {4608: 72, 7680: 95, 12288: 51, 15360: 38}
    assert sum(p for p, _ in a) / 256 == 8874 and sum(o for _, o in a) / 256 == 1024
    assert sorted(p for p, _ in a) == sorted(p for p, _ in b)
    assert sorted(o for _, o in a) == sorted(o for _, o in b)
    assert max(p + o for p, o in a) <= traffic["max_total"]
    # every length ends a quarter, three quarters, none or a half of the way
    # through a window
    assert sorted({p % 2048 / 2048 for p, _ in a}) == [0.0, 0.25, 0.5, 0.75]
    # the prefill programs a wave limit of (8, 16384) lets these lengths form
    assert D.reachable_pads(traffic, 16) == [4608, 7680, 12288, 15360]
    waves = {(p, w) for p in (4608, 7680, 12288, 15360) for w in (1, 2)
             if w * p <= 16384}
    assert len(waves) == 6
    monkeypatch.setattr(D, "setup", lambda cell, args, clock: {"traffic": traffic})
    monkeypatch.setattr(D, "window", lambda ctx, s, *rest: T.closed_list(
        ctx["traffic"], s))
    got = D.run({}, NS(seed=seed, seconds=1, trace=0, trace_seconds=1), None)
    assert got == a and T.closed_list(traffic, 1) != T.closed_list(traffic, 2)


def test_the_cell_rehearses_on_the_cpu_at_tiny_sizes(tmp_path):
    """The whole cell through ``run.py --allow-cpu``: deploy, warm-up, both
    checked requests against the reference (the first crosses a window's end
    while it decodes, the second fills neither a page nor a chunk), the
    closed loop, the readers."""
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
    for prefix in ("", "short."):
        for name in ("kv_rel_err.prefill", "kv_rel_err.decode",
                     "pair_rel_err.prefill", "pair_rel_err.decode",
                     "deep_row_err_p50.decode", "deep_pair_err_p50.decode"):
            assert ref[prefix + name] < 1e-5, prefix + name
        assert ref[prefix + "token_logit_gap"] == 0.0
    assert ref["repeats"]
    rehearsed = line["rehearsal"]
    assert rehearsed["cpu-rehearsal.engine.compiles_in_window.batch"] == 0
    assert 0 < rehearsed["cpu-rehearsal.eva.pair_rows_share.bytes"] < 100
    assert 0 < rehearsed["cpu-rehearsal.cache.eva_rows_held_share.bytes"] < 100

"""What PR 48 added to the benchmark, on the CPU: the compressed-latent
convolved attention + top-1 expert reference and its controls at the
configuration's tiny size, every new roofline count against a hand count, the
new reader on a hand-made run, the new cell found by name as files alone, its
traffic's multiset whatever the seed, and the ``--allow-cpu`` rehearsal of the
whole cell."""
import json
import os
import subprocess
import sys
from collections import Counter
from types import SimpleNamespace as NS

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers.serve_cca_moe import cca_moe_config
from benchmarks.lib import configs
from benchmarks.lib import weights_cca_moe as W
from benchmarks.reference import cca_moe as R
from benchmarks.roofline import cca_decode_attention as attn_count
from benchmarks.roofline import cca_moe_decode_multi as count
from benchmarks.roofline import cca_moe_prefill_batch as prefill_count

CELL = "zaya1_cot_closed"
CONFIG = "zaya1-8b.json"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
MINE = {"engine.decode_step_ms.batch", "engine.prefill_share.batch",
        "kernel.cca_moe_decode_roofline", "kernel.cca_moe_prefill_roofline",
        "kernel.paged_decode_attention_roofline.cot", "kernel.cca_mix_share.cot",
        "kernel.head_share.cot", "kernel.grouped_matmul_share",
        "moe.experts_touched_share", "moe.load_imbalance",
        "kernel.decode_kv_read_amplification.batch"}
JOINED = {"moe.expert_passes_per_touched", "kernel.router_share",
          "kernel.unnamed_share.batch", "engine.compiles_in_window.batch",
          "engine.loop_blocked_share.batch",
          "engine.prompts_per_prefill_counted.batch",
          "engine.prefill_pad_waste.batch", "device.idle_share.batch",
          "device.idle_in_sync_emit.batch", "device.idle_in_admit.batch",
          "device.idle_in_dispatch.batch", "device.idle_unattributed.batch"}


def published():
    return cca_moe_config(configs.load_json("configs", CONFIG))


def tiny():
    cf = configs.load_json("configs", CONFIG)
    return cca_moe_config({**cf, **cf["tiny"]})


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_program_forward_agrees_and_the_controls_do_not(seed):
    from benchmarks.control_cca_moe import VARIANTS
    from ray_tpu.models.cca_moe import cca_moe_forward

    cfg = tiny()
    assert (cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.n_experts,
            cfg.rotary_dim, cfg.router_hidden) == (3, 4, 2, 4, 8, 16)
    tokens = np.random.default_rng(seed % 1000).integers(3, cfg.vocab_size, 60)
    params = W.make_params(W.seed_key(seed), cfg, 1)
    kw = dict(state_at=(21, 60), zero_row=1)
    want = R.forward(seed, cfg, tokens, **kw)
    got = cca_moe_forward(params, jnp.asarray(tokens)[None], cfg)[0]
    assert rel(got, want["logits"]) < 1e-5
    assert want["k"].shape == want["v"].shape == (3, 60, 32)
    assert want["row"].shape == (3, 2, 2 * cfg.conv_width + cfg.v_half)
    # a lower precision stands apart everywhere, the next one below further
    errs = {m: rel(R.forward(seed, cfg, tokens, mode=m, **kw)["k"][0],
                   want["k"][0]) for m in ("bfloat16", "fp8")}
    assert errs["fp8"] > 2.5 * errs["bfloat16"] > 1e-4, errs
    # what changes the mixing moves layer 0's cache; what stands behind it
    # (the router, the gains) leaves layer 0 alone and moves the last layer's
    behind = {"nocarry", "routerx", "routerbf16", "biasweights", "weightone",
              "gainsone"}
    for name, variant in VARIANTS.items():
        if name == "padrun":
            continue
        other = R.forward(seed, cfg, tokens, variant=variant, **kw)
        first = max(rel(other[n][0], want[n][0]) for n in ("k", "v"))
        deep = max(rel(other[n][-1], want[n][-1]) for n in ("k", "v"))
        if name in behind:
            assert first == 0.0, name
            if name != "routerbf16":   # rounding flips a choice or it does not
                assert deep > 0.01, name
        else:
            assert first > 1e-3, name
    padded = R.forward(seed, cfg, tokens, state_at=(21, 60), zero_row=1,
                       variant={"pad": 24, "pad_from": 21})
    assert rel(padded["row"][0, 0], want["row"][0, 0]) > 0.05


def test_the_published_configuration_is_what_the_program_gets():
    cf = configs.load_json("configs", CONFIG)
    cfg = cca_moe_config(cf)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.rotary_dim, cfg.rope_theta) == (2048, 8, 2, 128, 64, 5e6)
    assert (cfg.n_experts, cfg.d_expert, cfg.router_hidden, cfg.held) == (
        16, 2048, 256, (0, 16))
    assert (cfg.vocab_size, cfg.n_layers, cfg.max_seq_len, cfg.rms_norm_eps,
            cfg.dtype) == (262272, 20, 3072, 1e-5, "bfloat16")
    assert (cfg.conv_width, cfg.v_half, cfg.in_width) == (1280, 128, 1536)
    assert cf["published"] == {"num_hidden_layers": 40,
                               "max_position_embeddings": 131072}
    assert cf["reduced"] == ["num_hidden_layers", "max_position_embeddings"]
    # every key of the catalog's config under its own name, unchanged but
    # for those in ``reduced`` (``layer_types`` whole)
    rows = [json.loads(line) for line in open(CATALOG)] if os.path.exists(
        CATALOG) else []
    for row in rows:
        if row["name"] == "ZAYA1-8B":
            assert cf["source"] == row["source_url"]
            assert {k: cf[k] for k in row["config"] if k not in cf["reduced"]} == {
                k: v for k, v in row["config"].items() if k not in cf["reduced"]}
    assert len(cf["layer_types"]) == 40 and set(cf["layer_types"]) == {"hybrid"}
    assert [a[:3] for a in cf["assumed"][:7]] == [
        "(a)", "(b)", "(c)", "(d)", "(e)", "(f)", "(g)"]
    assert "two pipeline stages of 20 layers" in cf["deployment"]
    assert cf["engine"] == {"max_batch": 80, "page_size": 16,
                            "n_pages": {"kv": 9400, "row": 81},
                            "max_seq_len": 3072, "eos_id": 1}
    for key, bad in (("num_experts_per_tok", 2), ("cca_time1", 4),
                     ("tie_word_embeddings", False), ("sliding_window", 4096)):
        with pytest.raises(ValueError, match=key):
            cca_moe_config({**cf, key: bad})
    with pytest.raises(ValueError, match="hybrid layers alone"):
        cca_moe_config({**cf, "layer_types": ["hybrid_sliding"] * 40})


def test_decode_count_against_a_hand_count():
    cfg = published()
    mixer = 2048 * 1536 + 2 * 1280 + 2 * 10 * 128 * 128 + 1024 * 2048
    assert count.mixer_params(cfg) == mixer == 5_573_120        # 5.24 + 0.33 M
    assert count.router_params(cfg) == 2048 * 256 + 2 * 256 * 256 + 256 * 16 == 659_456
    assert count.expert_params(cfg) == 3 * 2048 * 2048 == 12_582_912
    router_bytes = 2048 * 256 * 2 + 4 * (2 * 256 * 256 + 256 * 16)
    fixed = 20 * (mixer * 2 + router_bytes) + 2048 * 262272 * 2
    assert count.fixed_bytes(cfg) == fixed == 1_328_975_872
    assert count.kv_row_bytes(cfg) == 1024 and count.row_bytes(cfg) == 5376
    # 80 live slots x 20 layers updated, 92,800 live positions (1,160 a
    # slot), every one of 16 experts touched a layer: 1.33 GB outside the
    # experts (1.07 of it the table), 8.05 GB of experts, 1.90 GB of K and
    # V, 17 MB of rows
    updates = 80 * 20
    got = count.bytes_per_step(cfg, updates, 92_800, 16.0)
    experts = 20 * 16 * 12_582_912 * 2
    assert experts == 8_053_063_680
    assert got == fixed + experts + 2 * updates * 5376 + 92_800 * 20 * 1024
    assert got == 11_299_786_752
    assert abs(count.least_seconds(cfg, PEAKS, 80, updates, 92_800, 16.0, 80.0)
               - got / 819e9) < 1e-12                       # bound by bytes
    assert 0.87 < (experts + 92_800 * 20 * 1024) / got < 0.89  # nine tenths
    assert count.flops_per_step(cfg, 80, 92_800, 80.0) == (
        2 * 80 * (20 * (mixer + 659_456) + 2048 * 262272)
        + 2 * 20 * 80 * 12_582_912 + 4 * 92_800 * 8 * 128 * 20)
    # the attention kernel's own: K and V within reach, q in and o out
    call = 92_800 * 1024 + 2 * 80 * 8 * 128 * 2
    assert attn_count.bytes_per_call(cfg, 80, 92_800) == call
    assert attn_count.least_seconds(cfg, PEAKS, 80, "kv", 92_800) == (
        20 * call / 819e9)
    with pytest.raises(ValueError, match="'kv'"):
        attn_count.least_seconds(cfg, PEAKS, 80, "row", 1)


def test_prefill_count_against_a_hand_count():
    cfg = published()
    # a token meets 20 mixers and routers and ONE expert a layer
    per_token = 20 * (5_573_120 + 659_456 + 12_582_912)
    assert prefill_count.token_params(cfg) == per_token == 376_309_760
    pairs = 1000 * 1001 / 2
    assert prefill_count.attention_flops(cfg, [1000]) == (
        2 * 8 * 256 * 20 * pairs)
    want = 2 * 1000 * per_token + 2 * 8 * 256 * 20 * pairs + 2 * 2048 * 262272
    assert prefill_count.flops(cfg, [1000.0]) == want
    assert 0.79e12 < want < 0.80e12    # 0.75 GFLOP a token, and its pairs
    assert prefill_count.flops(cfg, [512.0] * 2) == 2 * prefill_count.flops(
        cfg, [512.0])
    assert prefill_count.least_seconds(cfg, PEAKS, [1000.0]) == want / 197e12


def _run(steps=12):
    cfg = published()

    def snap(scale):
        def s(v):
            return {"sum": v * steps * scale}
        return {"steps": steps * scale, "block_buckets": [4, 8, 16, 32, 64],
                "program_parts": {}, "stages": {
            "rt_llm_moe_experts_touched_total": {"": s(310.0)},
            "rt_llm_moe_expert_slots_total": {"": s(320.0)},
            "rt_llm_moe_max_load_total": {"": s(200.0)},
            "rt_llm_moe_assignments_total": {"": s(1600.0)},
            "rt_llm_moe_expert_passes_total": {"": s(310.0)},
            "rt_llm_cca_row_updates_total": {"": s(1600.0)},
            "rt_llm_decode_kv_tokens_live_total": {
                "": s(92_800.0), "kv": s(92_800.0)},
            "rt_llm_decode_kv_tokens_read_total": {"": s(93_000.0)}}}

    return {"cfg": cfg, "engine": {"max_batch": 80}, "peaks": PEAKS,
            "counters": {"before": snap(1), "after": snap(2)},
            "trace": {"busy_s": 2.0, "window_s": 2.0, "programs": {
                "jit_cca_moe_decode_multi": {
                    "durations": [0.2] * 3 + [0.1] * 4, "seconds": 1.0},
                "jit_cca_moe_prefill_batch": {"durations": [0.1],
                                              "seconds": 0.1}},
                "ops": [["pallas:ragged-dot-swiglu:bf16_80_2048", 0.6],
                        ["pallas:_paged_decode_attention:bf16_80_8_128", 0.2]]},
            "trace_window": (0.0, 1.0),
            "dispatched_steps": [64, 8, 8, 8, 4, 4, 4, 4],
            "admitted_lens": [512.0] * 8,
            "part_seconds": {"stale": set(), "unnamed_ops": [], "seconds": {
                ("jit_cca_moe_decode_multi", "mix"): 0.08,
                ("jit_cca_moe_prefill_batch", "mix"): 0.02,
                ("jit_cca_moe_decode_multi", "head"): 0.15,
                ("jit_cca_moe_decode_multi", "sample"): 0.05,
                ("jit_cca_moe_decode_multi", "experts"): 0.6}}}


def test_new_readers_on_a_hand_made_run():
    from benchmarks import run as bench_run

    cell = configs.load_cell(CELL)
    run = _run()
    got = {k: v["value"] for k, v in
           bench_run.read_metrics(cell, "per_layer", run).items()}
    assert got["moe.experts_touched_share"] == pytest.approx(100 * 310 / 320)
    assert got["moe.expert_passes_per_touched"] == pytest.approx(1.0)
    assert got["engine.decode_step_ms.batch"] == pytest.approx(25.0)
    assert got["engine.prefill_share.batch"] == pytest.approx(5.0)
    assert got["kernel.decode_kv_read_amplification.batch"] == pytest.approx(
        93_000 / 92_800)
    assert got["kernel.grouped_matmul_share"] == pytest.approx(30.0)
    assert got["kernel.cca_mix_share.cot"] == pytest.approx(5.0)
    assert got["kernel.head_share.cot"] == pytest.approx(10.0)
    # 40 steps in the trace (three 8-step and four 4-step blocks); 20 layers:
    # 15.5 of 16 experts touched, 80 rows routed a layer
    least = count.least_seconds(run["cfg"], PEAKS, 80, 1600.0, 92_800.0,
                                15.5, 80.0)
    assert got["kernel.cca_moe_decode_roofline"] == pytest.approx(
        100 * 40 * least / 1.0)
    assert got["kernel.paged_decode_attention_roofline.cot"] == pytest.approx(
        100 * 40 * attn_count.least_seconds(run["cfg"], PEAKS, 80, "kv",
                                            92_800.0) / 0.2)
    assert got["kernel.cca_moe_prefill_roofline"] == pytest.approx(
        100 * prefill_count.flops(run["cfg"], [512.0] * 8) / 197e12 / 0.1)
    for name in ("kernel.cca_moe_decode_roofline",
                 "kernel.paged_decode_attention_roofline.cot",
                 "kernel.cca_moe_prefill_roofline"):
        assert 0 < got[name] < 100, name
    # a program without the counters or the part table (the parent) reads
    # as nothing, and nothing raises
    bare = _run()
    for snap in bare["counters"].values():
        snap["stages"] = {}
    bare["trace"]["ops"] = []
    bare["dispatched_steps"] = []
    bare["admitted_lens"] = []
    bare["part_seconds"] = None
    left = bench_run.read_metrics(cell, "per_layer", bare)
    assert not (set(left) & MINE) - {"engine.prefill_share.batch"}


def test_the_new_cell_is_found_by_name_as_files_alone():
    manifest = configs.load_manifest()
    cell = configs.load_cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "cot_closed"
    traffic, cf = cell["traffic_file"], cell["config_file"]
    assert configs.load_module("drivers", traffic["driver"]).run
    assert traffic["driver"] in cf["correct_limits"]
    assert configs.load_module("reference", cf["reference"]).forward
    slots = cf["engine"]["max_batch"]
    assert (slots, traffic["callers"], traffic["list_size"], traffic["stream"]) == (
        80, 88, 256, False)
    assert (traffic["caller_stagger_s"], traffic["lead_in_s"]) == (0.1, 20)
    assert traffic["prompt"] == {"dist": "lognormal", "median": 448,
                                 "sigma": 0.6, "lengths": [256, 512, 1024]}
    assert traffic["output"] == {"dist": "uniform", "min": 512, "max": 2048}
    assert traffic["max_total"] == 3072 == cf["engine"]["max_seq_len"]
    assert traffic["reference_check"] == [
        {"prompt_len": 1024, "max_tokens": 24},
        {"prompt_len": 200, "max_tokens": 24}]
    e2e = {m["name"] for m in configs.cell_metrics(cell, "end_to_end")}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    layer = configs.cell_metrics(cell, "per_layer")
    assert {m["moves"] for m in layer} >= {"serve_tokens_per_s"}
    names = {m["name"] for m in layer}
    # this PR's, and those the cell joined by name: a later PR may add more
    assert MINE | JOINED <= names
    for m in layer:
        spec = configs.load_json("layer_metrics", m["name"] + ".json")
        assert set(spec) == {"name", "reader", "args"}
        assert configs.load_module("readers", spec["reader"]).read
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == cf["reduced"] and set(cf["published"]) == set(cf["reduced"])
    assert entry["source"] == cf["source"]
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    # every limit is judged on a name the replica reports
    assert set(cf["correct_limits"][traffic["driver"]]) <= {
        p + n + w for p in ("", "short.") for w in (".prefill", ".decode")
        for n in ("kv_rel_err", "row_rel_err", "kv_row_err_p50",
                  "deep_kv_row_err_p50", "deep_row_err")}
    from ray_tpu.llm.cca_moe import WAVE_LIMIT
    assert WAVE_LIMIT == (8, 8192) and "wave_limit" not in traffic


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_the_traffics_multiset_whatever_the_seed(seed, monkeypatch):
    """256 quantiles of lognormal(448, 0.6) snapped to the three lengths,
    outputs uniform 512-2048 (mean 1,280): the multiset ``lib/traffic.py``
    makes, which this driver cycles in ONE order whatever the seed."""
    from benchmarks.drivers import serve_cca_moe as D
    from benchmarks.lib import traffic as T

    traffic = configs.load_cell(CELL)["traffic_file"]
    a, b = D.even_list(traffic), T.closed_list(traffic, seed)
    mix = Counter(p for p, _ in a)
    assert set(mix) == {256, 512, 1024} and sum(mix.values()) == 256
    assert mix == Counter(p for p, _ in b)
    assert sum(o for _, o in a) / 256 == 1280
    assert sorted(o for _, o in a) == sorted(o for _, o in b)
    assert max(p + o for p, o in a) <= traffic["max_total"]
    # the prefill programs a wave limit of (8, 8192) lets these lengths form
    assert D.reachable_pads(traffic, 16) == [256, 512, 1024]
    waves = {(p, w) for p in (256, 512, 1024) for w in (1, 2, 4, 8)
             if w * p <= 8192}
    assert len(waves) == 12
    monkeypatch.setattr(D, "setup", lambda cell, args, clock: {"traffic": traffic})
    monkeypatch.setattr(D, "window", lambda ctx, s, *rest: T.closed_list(
        ctx["traffic"], s))
    got = D.run({}, NS(seed=seed, seconds=1, trace=0, trace_seconds=1), None)
    assert got == a and T.closed_list(traffic, 1) != T.closed_list(traffic, 2)


def test_the_cell_rehearses_on_the_cpu_at_tiny_sizes(tmp_path):
    """The whole cell through ``run.py --allow-cpu``: deploy, warm-up, both
    checked requests against the reference (the second fills neither a page
    nor a pad), the closed loop, the readers."""
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
                     "row_rel_err.prefill", "row_rel_err.decode",
                     "kv_row_err_p50.decode", "deep_kv_row_err_p50.prefill",
                     "deep_kv_row_err_p50.decode", "deep_row_err.prefill"):
            assert ref[prefix + name] < 1e-5, prefix + name
        assert ref[prefix + "token_logit_gap"] == 0.0
    assert ref["repeats"]
    rehearsed = line["rehearsal"]
    assert rehearsed["cpu-rehearsal.engine.compiles_in_window.batch"] == 0
    assert 0 < rehearsed["cpu-rehearsal.moe.experts_touched_share"] <= 100

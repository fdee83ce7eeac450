"""What PR 44 added to the benchmark, on the CPU: the delta-rule +
latent-attention + group-routed expert reference and its controls at the
configuration's tiny size, every new roofline count against a hand count, the
new readers on a hand-made run, the new cell found by name as files alone,
its traffic's multiset whatever the seed, and the ``--allow-cpu`` rehearsal of
the whole cell."""
import json
import os
import subprocess
import sys
from collections import Counter
from types import SimpleNamespace as NS

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers.serve_kda_moe import kda_moe_config
from benchmarks.lib import configs
from benchmarks.lib import weights_kda_moe as W
from benchmarks.reference import kda_moe as R
from benchmarks.roofline import kda_moe_decode_multi as count
from benchmarks.roofline import kda_moe_prefill_batch as prefill_count

CELL = "ling3flashvl_think_closed"
CONFIG = "ling-3.0-flash-vl.json"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
MINE = {"engine.decode_step_ms.batch", "engine.prefill_share.batch",
        "kernel.kda_moe_decode_roofline", "kernel.delta_state_update_roofline",
        "kernel.kda_moe_prefill_roofline", "kernel.delta_prefill_roofline",
        "kernel.delta_share.think", "kernel.delta_pool_step_share.think",
        "moe.tokens_here_share.think", "kernel.grouped_matmul_share",
        "moe.experts_touched_share", "moe.load_imbalance",
        "kernel.decode_kv_read_amplification.batch"}


def published():
    return kda_moe_config(configs.load_json("configs", CONFIG))


def tiny():
    cf = configs.load_json("configs", CONFIG)
    return kda_moe_config({**cf, **cf["tiny"]})


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_program_forward_agrees_and_the_controls_do_not(seed):
    from ray_tpu.models.kda_moe import kda_moe_forward

    cfg = tiny()
    assert cfg.held == (8, 12) and cfg.n_experts == 32 and cfg.n_layers == 6
    assert (cfg.n_group, cfg.topk_group, cfg.layer_group_size) == (8, 4, 3)
    tokens = np.random.default_rng(seed % 1000).integers(3, cfg.vocab_size, 60)
    params = W.make_params(W.seed_key(seed), cfg)
    want = R.forward(seed, cfg, tokens, q_block=32, state_at=(21, 60))
    got = kda_moe_forward(params, jnp.asarray(tokens)[None], cfg)[0]
    assert rel(got, want["logits"]) < 1e-5
    assert want["state"].shape == (4, 2, 4, 16, 16)
    assert want["conv"].shape == (4, 2, 3, cfg.conv_width)
    assert want["rows"].shape == (2, 60, cfg.latent_width)
    # a lower precision stands apart everywhere, the next one below further
    errs = {m: rel(R.forward(seed, cfg, tokens, mode=m, q_block=32,
                             state_at=(21, 60))["state"][0], want["state"][0])
            for m in ("bfloat16", "fp8")}
    assert errs["fp8"] > 2.5 * errs["bfloat16"] > 1e-4, errs
    # what changes the recurrence moves layer 0's state ...
    for variant in ({"decay": "none"}, {"decay": "head"}, {"delta": False},
                    {"beta": "one"}, {"qknorm": False}, {"silu": False},
                    {"state": "bfloat16"}):
        other = R.forward(seed, cfg, tokens, variant=variant, q_block=32,
                          state_at=(21, 60))
        assert rel(other["state"][0], want["state"][0]) > 1e-3, variant
    # ... what lies behind it leaves that state alone and moves the rows of
    # the MLA layers: the first's for the mixers' own parts, the last's
    # (behind expert layers) for the routing
    for variant, layer in (({"gate": "before"}, 0), ({"rope": False}, 0),
                           ({"groups": "none"}, 1), ({"groups": "max"}, 1),
                           ({"bias": "weights"}, 1)):
        other = R.forward(seed, cfg, tokens, variant=variant, q_block=32,
                          state_at=(21, 60))
        assert rel(other["state"][0], want["state"][0]) < 1e-6, variant
        assert rel(other["rows"][layer], want["rows"][layer]) > 0.01, variant
    padded = R.forward(seed, cfg, tokens, q_block=32, state_at=(21, 60),
                       variant={"pad": 24, "pad_from": 21})
    assert rel(padded["state"][0, 0], want["state"][0, 0]) > 0.05


def test_the_published_configuration_is_what_the_program_gets():
    cf = configs.load_json("configs", CONFIG)
    cfg = kda_moe_config(cf)
    assert (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.conv_kernel,
            cfg.kda_lower_bound) == (2560, 32, 128, 4, -5.0)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.rope_theta) == (512, 128, 64, 128, 6e6)
    assert (cfg.n_experts, cfg.n_experts_per_tok, cfg.n_group, cfg.topk_group,
            cfg.d_expert, cfg.d_shared, cfg.routed_scaling_factor) == (
        512, 8, 8, 4, 768, 768, 2.5)
    assert (cfg.d_ff, cfg.first_dense_layers, cfg.layer_group_size) == (6144, 2, 6)
    assert cfg.held == (0, 64) and cfg.vocab_size == 19648 and cfg.n_layers == 12
    assert cfg.layers_of("mla") == (5, 11) and len(cfg.layers_of("kda")) == 10
    assert cfg.rms_norm_eps == 1e-6 and cfg.max_seq_len == 6144
    assert (cfg.chunk_size, cfg.sub_chunk) == (64, 16)
    assert cf["published"] == {"num_hidden_layers": 42, "num_experts": 512,
                               "vocab_size": 157184,
                               "max_position_embeddings": 131072}
    # every key of the catalog's config under its own name, unchanged but
    # for those in ``reduced`` (the two limit lists whole)
    rows = [json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")] if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else []
    for row in rows:
        if row["name"] == "Ling-3.0-flash-VL":
            assert cf["source"] == row["source_url"]
            assert {k: cf[k] for k in row["config"] if k not in cf["reduced"]} == {
                k: v for k, v in row["config"].items() if k not in cf["reduced"]}
    assert len(cf["expert_swiglu_limit_list"]) == 42
    assert [a[:3] for a in cf["assumed"][:8]] == [
        "(a)", "(b)", "(c)", "(d)", "(e)", "(f)", "(g)", "(h)"]
    assert "8 chips share each layer" in cf["deployment"]
    assert not cfg.vocab_held[0] <= cf["engine"]["eos_id"] < cfg.vocab_held[1]
    assert cf["engine"]["n_pages"] == {"latent": 24600, "state": 97}
    with pytest.raises(ValueError, match="score_function"):
        kda_moe_config({**cf, "score_function": "softmax"})
    with pytest.raises(ValueError, match="experts held"):
        kda_moe_config({**cf, "num_experts": 512})
    with pytest.raises(ValueError, match="clamped SwiGLU"):
        kda_moe_config({**cf, "num_hidden_layers": 36})


def test_decode_count_against_a_hand_count():
    cfg = published()
    kda = 2560 * (12288 + 4096 + 64) + 4 * 12288 + 4096 * 2560        # 52.6 M
    assert count.kda_params(cfg) == kda == 52_641_792
    mla = (2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 2560 * 32
           + 4096 * 2560)                                              # 32.0 M
    assert count.mla_params(cfg) == mla == 31_965_184
    assert count.expert_params(cfg) == 3 * 2560 * 768 == 5_898_240
    fixed_e = 2560 * 512 + 3 * 2560 * 768                              # 7.2 M
    assert count.expert_layer_fixed(cfg) == fixed_e == 7_208_960
    fixed = (10 * kda + 2 * mla + 2 * 3 * 2560 * 6144 + 10 * fixed_e
             + 2560 * 19648)
    assert count.fixed_params(cfg) == fixed == 807_108_608
    assert count.state_row_bytes(cfg) == 2_097_152 + 73_728 == 2_170_880
    assert count.latent_row_bytes(cfg) == 1152
    # 96 live slots x 10 KDA layers updated, 259,200 live positions (2,700 a
    # slot), 50 of 64 held experts touched a layer: 1.61 GB of weights
    # outside the experts, 5.90 GB of touched experts, 4.17 GB of state
    # rows, 0.60 GB of latent rows
    updates = 96 * 10
    got = count.bytes_per_step(cfg, updates, 259_200, 50.0)
    weights = (fixed + 10 * 50 * 5_898_240) * 2
    state = 2 * updates * 2_170_880
    assert state == 4_168_089_600 == count.state_bytes(cfg, updates)
    assert got == weights + state + 259_200 * 2 * 1152 == 12_277_743_616
    assert abs(count.least_seconds(cfg, PEAKS, 96, updates, 259_200, 50.0, 96.0)
               - got / 819e9) < 1e-12                       # bound by bytes
    assert 0.33 < state / got < 0.35                        # a third of them state
    assert 0.8 < (state + 10 * 50 * 5_898_240 * 2) / got < 0.85   # four fifths
    flops = count.flops_per_step(cfg, 96, updates, 259_200, 96.0)
    assert flops == (2 * 96 * fixed + 2 * 10 * 96 * 5_898_240
                     + 7 * updates * 32 * 128 * 128
                     + 2 * 259_200 * 32 * (576 + 512) * 2)


def test_prefill_count_against_a_hand_count():
    cfg = published()
    # a token meets 10 KDA and 2 MLA mixers, 2 dense layers and 10 expert
    # layers outside their routed experts, and of its 8 choices the 64 / 512
    # held here: ONE expert a layer
    per_token = (10 * 52_641_792 + 2 * 31_965_184 + 2 * 47_185_920
                 + 10 * (7_208_960 + 1.0 * 5_898_240))
    assert prefill_count.token_params(cfg) == per_token == 815_792_128
    assert prefill_count.scan_flops(cfg, 1000) == 7 * 1000 * 10 * 32 * 128 * 128
    pairs = 1000 * 1001 / 2
    assert prefill_count.attention_flops(cfg, [1000]) == (
        2 * 32 * (192 + 128) * 2 * pairs)
    want = (2 * 1000 * per_token + 7 * 1000 * 10 * 32 * 128 * 128
            + 2 * 32 * 320 * 2 * pairs + 2 * 2560 * 19648)
    assert prefill_count.flops(cfg, [1000.0]) == want
    assert 1.68e12 < want < 1.70e12    # 1.7 GFLOP a token
    assert prefill_count.scan_least_seconds(cfg, PEAKS, [1000.0]) == (
        7 * 1000 * 10 * 32 * 128 * 128 / 197e12)
    assert prefill_count.flops(cfg, [512.0] * 2) == 2 * prefill_count.flops(
        cfg, [512.0])


def _run(steps=12):
    cfg = published()

    def snap(scale):
        def s(v):
            return {"sum": v * steps * scale}
        return {"steps": steps * scale, "block_buckets": [4, 8, 16, 32, 64],
                "program_parts": {}, "stages": {
            "rt_llm_moe_experts_touched_total": {"": s(500.0)},
            "rt_llm_moe_expert_slots_total": {"": s(640.0)},
            "rt_llm_moe_max_load_total": {"": s(60.0)},
            "rt_llm_moe_assignments_total": {"": s(960.0)},
            "rt_llm_moe_expert_passes_total": {"": s(500.0)},
            "rt_llm_delta_state_updates_total": {"": s(960.0)},
            "rt_llm_moe_tokens_here_total": {"": s(480.0)},
            "rt_llm_decode_kv_tokens_live_total": {"": s(259_200.0)},
            "rt_llm_decode_kv_tokens_read_total": {"": s(260_000.0)}}}

    return {"cfg": cfg, "engine": {"max_batch": 96}, "peaks": PEAKS,
            "counters": {"before": snap(1), "after": snap(2)},
            "trace": {"busy_s": 2.0, "window_s": 2.0, "programs": {
                "jit_kda_moe_decode_multi": {
                    "durations": [0.2] * 3 + [0.1] * 4, "seconds": 1.0},
                "jit_kda_moe_prefill_batch": {"durations": [0.8],
                                              "seconds": 0.8}},
                "ops": [["pallas:ragged-dot-swiglu:bf16_832_2560", 0.1],
                        ["pallas:kda_pool_step:f32_97_32_128", 0.3]]},
            "trace_window": (0.0, 1.0),
            "dispatched_steps": [64, 8, 8, 8, 4, 4, 4, 4],
            "admitted_lens": [2048.0] * 8,
            # what ``readers/part_share.py`` makes of a trace and the
            # program's table: seconds by (program, part)
            "part_seconds": {"stale": set(), "unnamed_ops": [], "seconds": {
                ("jit_kda_moe_decode_multi", "delta"): 0.35,
                ("jit_kda_moe_decode_multi", "conv"): 0.05,
                ("jit_kda_moe_decode_multi", "experts"): 0.3,
                ("jit_kda_moe_prefill_batch", "delta"): 0.3,
                ("jit_kda_moe_prefill_batch", "conv"): 0.1}}}


def test_new_readers_on_a_hand_made_run():
    from benchmarks import run as bench_run

    cell = configs.load_cell(CELL)
    run = _run()
    got = {k: v["value"] for k, v in
           bench_run.read_metrics(cell, "per_layer", run).items()}
    assert got["moe.experts_touched_share"] == pytest.approx(100 * 500 / 640)
    assert got["moe.tokens_here_share.think"] == pytest.approx(50.0)
    assert got["moe.expert_passes_per_touched"] == pytest.approx(1.0)
    assert got["engine.decode_step_ms.batch"] == pytest.approx(25.0)
    assert got["engine.prefill_share.batch"] == pytest.approx(40.0)
    assert got["kernel.decode_kv_read_amplification.batch"] == pytest.approx(
        260_000 / 259_200)
    assert got["kernel.grouped_matmul_share"] == pytest.approx(5.0)
    assert got["kernel.delta_pool_step_share.think"] == pytest.approx(15.0)
    assert got["kernel.delta_share.think"] == pytest.approx(100 * 0.8 / 2.0)
    # 40 steps in the trace (three 8-step and four 4-step blocks); 10 expert
    # layers: 50 of 64 held experts touched, 96 rows routed to them a layer
    least = count.least_seconds(run["cfg"], PEAKS, 96, 960.0, 259_200.0,
                                50.0, 96.0)
    assert got["kernel.kda_moe_decode_roofline"] == pytest.approx(
        100 * 40 * least / 1.0)
    assert got["kernel.delta_state_update_roofline"] == pytest.approx(
        100 * 40 * (4_168_089_600 / 819e9) / 0.4)
    assert got["kernel.kda_moe_prefill_roofline"] == pytest.approx(
        100 * prefill_count.flops(run["cfg"], [2048.0] * 8) / 197e12 / 0.8)
    assert got["kernel.delta_prefill_roofline"] == pytest.approx(
        100 * prefill_count.scan_flops(run["cfg"], 8 * 2048.0) / 197e12 / 0.4)
    for name in ("kernel.kda_moe_decode_roofline",
                 "kernel.delta_state_update_roofline",
                 "kernel.kda_moe_prefill_roofline",
                 "kernel.delta_prefill_roofline"):
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
    assert cell["chips"] == 1 and cell["traffic"] == "think_closed"
    traffic, cf = cell["traffic_file"], cell["config_file"]
    assert configs.load_module("drivers", traffic["driver"]).run
    assert traffic["driver"] in cf["correct_limits"]
    assert configs.load_module("reference", cf["reference"]).forward
    slots = cf["engine"]["max_batch"]
    assert (slots, traffic["callers"], traffic["list_size"], traffic["stream"]) == (
        96, 104, 256, False)
    assert (traffic["caller_stagger_s"], traffic["lead_in_s"]) == (0.1, 20)
    assert traffic["prompt"] == {"dist": "lognormal", "median": 1536,
                                 "sigma": 0.6, "lengths": [1024, 2048, 4096]}
    assert traffic["output"] == {"dist": "uniform", "min": 512, "max": 2048}
    assert traffic["max_total"] == 6144 == cf["engine"]["max_seq_len"]
    assert traffic["reference_check"] == [
        {"prompt_len": 4096, "max_tokens": 24},
        {"prompt_len": 200, "max_tokens": 24}]
    e2e = {m["name"] for m in configs.cell_metrics(cell, "end_to_end")}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    layer = configs.cell_metrics(cell, "per_layer")
    assert {m["moves"] for m in layer} >= {"serve_tokens_per_s"}
    names = {m["name"] for m in layer}
    # this PR's, and those the cell joined by name: a later PR may add more
    assert MINE | {
        "moe.expert_passes_per_touched", "kernel.router_share",
        "kernel.unnamed_share.batch", "engine.compiles_in_window.batch",
        "engine.loop_blocked_share.batch",
        "engine.prompts_per_prefill_counted.batch",
        "engine.prefill_pad_waste.batch", "device.idle_share.batch",
        "device.idle_in_sync_emit.batch", "device.idle_in_admit.batch",
        "device.idle_in_dispatch.batch", "device.idle_unattributed.batch"
    } <= names
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
        for n in ("state_rel_err", "latent_row_err_p50", "deep_state_err_p50",
                  "deep_latent_row_err_p50")}
    from ray_tpu.llm.kda_moe import WAVE_LIMIT
    assert WAVE_LIMIT == (8, 16384) and "wave_limit" not in traffic


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_the_traffics_multiset_whatever_the_seed(seed, monkeypatch):
    """256 quantiles of lognormal(1536, 0.6) snapped to the three lengths are
    128 / 96 / 32 (mean 1,792), outputs uniform 512-2048 (mean 1,280): the
    multiset ``lib/traffic.py`` makes, which this driver cycles in ONE order
    whatever the seed, every aligned run of 8 holding the file's own mix."""
    from benchmarks.drivers import serve_kda_moe as D
    from benchmarks.lib import traffic as T

    traffic = configs.load_cell(CELL)["traffic_file"]
    a, b = D.even_list(traffic), T.closed_list(traffic, seed)
    assert Counter(p for p, _ in a) == {1024: 128, 2048: 96, 4096: 32}
    assert sum(p for p, _ in a) / 256 == 1792 and sum(o for _, o in a) / 256 == 1280
    assert sorted(p for p, _ in a) == sorted(p for p, _ in b)
    assert sorted(o for _, o in a) == sorted(o for _, o in b)
    assert {sum(p == 4096 for p, _ in a[k:k + 8]) for k in range(0, 256, 8)} == {1}
    assert max(p + o for p, o in a) <= traffic["max_total"]
    # the prefill programs a wave limit of (8, 16384) lets these lengths form
    assert D.reachable_pads(traffic, 16) == [1024, 2048, 4096]
    waves = {(p, w) for p in (1024, 2048, 4096) for w in (1, 2, 4, 8)
             if w * p <= 16384}
    assert len(waves) == 11
    monkeypatch.setattr(D, "setup", lambda cell, args, clock: {"traffic": traffic})
    monkeypatch.setattr(D, "window", lambda ctx, s, *rest: T.closed_list(
        ctx["traffic"], s))
    got = D.run({}, NS(seed=seed, seconds=1, trace=0, trace_seconds=1), None)
    assert got == a and T.closed_list(traffic, 1) != T.closed_list(traffic, 2)


def test_the_cell_rehearses_on_the_cpu_at_tiny_sizes(tmp_path):
    """The whole cell through ``run.py --allow-cpu``: deploy, warm-up, both
    checked requests against the reference (the second fills neither a page
    nor a chunk), the closed loop, the readers."""
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
        for name in ("state_rel_err.prefill", "state_rel_err.decode",
                     "latent_row_err_p50.decode", "deep_state_err_p50.prefill",
                     "deep_latent_row_err_p50.decode"):
            assert ref[prefix + name] < 1e-5, prefix + name
        assert ref[prefix + "token_logit_gap"] == 0.0
    assert ref["repeats"]
    rehearsed = line["rehearsal"]
    assert rehearsed["cpu-rehearsal.engine.compiles_in_window.batch"] == 0
    assert 0 < rehearsed["cpu-rehearsal.moe.experts_touched_share"] <= 100
    assert 0 < rehearsed["cpu-rehearsal.moe.tokens_here_share.think"] <= 100

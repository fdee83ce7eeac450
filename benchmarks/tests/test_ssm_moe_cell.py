"""What PR 38 added to the benchmark, on the CPU: the state-space + attention +
ungated-expert reference and its controls at the configuration's tiny size,
every new roofline count against a hand count, the new readers on a hand-made
run, the new cell found by name as files alone, its traffic's multiset
whatever the seed, and the ``--allow-cpu`` rehearsal of the whole cell."""
import json
import os
import subprocess
import sys
from collections import Counter
from types import SimpleNamespace as NS

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers.serve_ssm_moe import ssm_moe_config
from benchmarks.lib import configs
from benchmarks.lib import weights_ssm_moe as W
from benchmarks.reference import ssm_moe as R
from benchmarks.roofline import ssm_moe_decode_multi as count
from benchmarks.roofline import ssm_moe_prefill_batch as prefill_count

CELL = "nemotron3nano_reason_closed"
CONFIG = "nvidia-nemotron-3-nano-30b-a3b-bf16.json"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def published():
    return ssm_moe_config(configs.load_json("configs", CONFIG))


def tiny():
    cf = configs.load_json("configs", CONFIG)
    return ssm_moe_config({**cf, **cf["tiny"]})


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_program_forward_agrees_and_the_controls_do_not(seed):
    from ray_tpu.models.ssm_moe import ssm_moe_forward

    cfg = tiny()
    assert cfg.held == (4, 12) and cfg.n_experts == 16 and cfg.pattern == "MEM*EME"
    tokens = np.random.default_rng(seed % 1000).integers(3, cfg.vocab_size, 60)
    params = W.make_params(W.seed_key(seed), cfg)
    want = R.forward(seed, cfg, tokens, q_block=32, state_at=(21, 60))
    got = ssm_moe_forward(params, jnp.asarray(tokens)[None], cfg)[0]
    assert rel(got, want["logits"]) < 1e-5
    assert want["state"].shape == (3, 2, 16, 8, 16)
    assert want["conv"].shape == (3, 2, 3, cfg.conv_width)
    # a lower precision stands apart everywhere, the next one below further
    errs = {m: rel(R.forward(seed, cfg, tokens, mode=m, q_block=32,
                             state_at=(21, 60))["state"][0], want["state"][0])
            for m in ("bfloat16", "fp8")}
    assert errs["fp8"] > 2.5 * errs["bfloat16"] > 1e-4, errs
    # block 0 precedes every gate, skip, rotation and expert: those controls
    # leave its state alone and move what lies behind it
    for variant in ({"gate": "after"}, {"skip": False}, {"rope": True},
                    {"act": "relu"}, {"act": "swiglu"}):
        other = R.forward(seed, cfg, tokens, variant=variant, q_block=32,
                          state_at=(21, 60))
        assert rel(other["state"][0], want["state"][0]) < 1e-6, variant
        behind = "state" if "rope" in variant else "k"
        assert rel(other[behind][-1], want[behind][-1]) > 0.05, variant
    low = R.forward(seed, cfg, tokens, variant={"state": "bfloat16"},
                    q_block=32, state_at=(21, 60))
    assert rel(low["state"][0], want["state"][0]) > 1e-3
    padded = R.forward(seed, cfg, tokens, q_block=32, state_at=(21, 60),
                       variant={"pad": 24, "pad_from": 21})
    assert rel(padded["state"][0, 0], want["state"][0, 0]) > 0.05


def test_the_published_configuration_is_what_the_program_gets():
    cf = configs.load_json("configs", CONFIG)
    cfg = ssm_moe_config(cf)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (2688, 32, 2, 128)
    assert (cfg.mamba_heads, cfg.mamba_head_dim, cfg.n_groups, cfg.ssm_state,
            cfg.conv_kernel, cfg.chunk_size) == (64, 64, 8, 128, 4, 128)
    assert (cfg.n_experts, cfg.n_experts_per_tok, cfg.d_expert, cfg.d_shared,
            cfg.n_shared_experts, cfg.routed_scaling_factor) == (
        128, 6, 1856, 3712, 1, 2.5)
    assert cfg.held == (0, 16) and cfg.vocab_size == 16384 and cfg.n_layers == 18
    assert cfg.pattern == PATTERN[:18] == "MEMEM*EMEMEM*EMEME"
    assert [len(cfg.blocks_of(c)) for c in "ME*"] == [8, 8, 2]
    assert cfg.rms_norm_eps == 1e-5 and cfg.max_seq_len == 4096
    assert cf["published"] == {"num_hidden_layers": 52,
                               "hybrid_override_pattern": PATTERN,
                               "n_routed_experts": 128, "vocab_size": 131072,
                               "max_position_embeddings": 262144}
    # every number of the catalog's config under its own key, unchanged but
    # for those in ``reduced``
    catalog = {"chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
               "hidden_size": 2688, "intermediate_size": 1856,
               "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
               "mamba_num_heads": 64, "moe_intermediate_size": 1856,
               "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
               "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05,
               "num_attention_heads": 32, "num_experts_per_tok": 6,
               "num_key_value_heads": 2, "num_logits_to_keep": 1,
               "partial_rotary_factor": 1, "rope_theta": 10000,
               "routed_scaling_factor": 2.5, "ssm_state_size": 128,
               "time_step_floor": 0.0001, "time_step_max": 0.1,
               "time_step_min": 0.001, "topk_group": 1}
    assert {k: cf[k] for k in catalog} == catalog
    assert [a[:3] for a in cf["assumed"][:6]] == [
        "(a)", "(b)", "(c)", "(d)", "(e)", "(f)"]
    assert "8 chips share each layer" in cf["deployment"]
    assert not cfg.vocab_held[0] <= cf["engine"]["eos_id"] < cfg.vocab_held[1]
    assert cf["engine"]["n_pages"] == {"kv": 20000, "state": 129}
    with pytest.raises(ValueError, match="mlp_hidden_act"):
        ssm_moe_config({**cf, "mlp_hidden_act": "silu"})
    with pytest.raises(ValueError, match="experts held"):
        ssm_moe_config({**cf, "n_routed_experts": 128})
    with pytest.raises(ValueError, match="num_hidden_layers long"):
        ssm_moe_config({**cf, "num_hidden_layers": 26})


def test_decode_count_against_a_hand_count():
    cfg = published()
    mamba = 2688 * (4096 + 6144 + 64) + 4 * 6144 + 4096 * 2688       # 38.7 M
    assert count.mamba_params(cfg) == mamba == 38_731_776
    attn = 2 * 2688 * 4096 + 2 * 2688 * 256                          # 23.4 M
    assert count.attn_params(cfg) == attn == 23_396_352
    assert count.expert_params(cfg) == 2 * 2688 * 1856 == 9_977_856
    fixed_e = 2688 * 128 + 2 * 2688 * 3712                           # 20.3 M
    assert count.expert_block_fixed(cfg) == fixed_e == 20_299_776
    fixed = 8 * mamba + 2 * attn + 8 * fixed_e + 2688 * 16384
    assert count.fixed_params(cfg) == fixed == 563_085_312
    assert count.state_row_bytes(cfg) == 4 * 64 * 64 * 128 + 3 * 6144 * 2 == 2_134_016
    assert count.kv_row_bytes(cfg) == 1024
    # 128 live slots x 8 Mamba-2 blocks updated, 256,000 live positions, 14
    # of 16 held experts touched: 1.13 GB of weights outside the experts,
    # 2.23 GB of touched experts, 4.37 GB of state rows, 0.52 GB of K and V
    updates = 128 * 8
    got = count.bytes_per_step(cfg, updates, 256_000, 14.0)
    weights = (fixed + 8 * 14 * 9_977_856) * 2
    state = 2 * updates * 2_134_016
    assert state == 4_370_464_768 == count.state_bytes(cfg, updates)
    assert got == weights + state + 256_000 * 2 * 1024 == 8_255_963_136
    assert abs(count.least_seconds(cfg, PEAKS, 128, updates, 256_000, 14.0, 96.0)
               - got / 819e9) < 1e-12                       # bound by bytes
    assert 0.5 < state / got < 0.55                         # half of them state
    flops = count.flops_per_step(cfg, 128, updates, 256_000, 96.0)
    assert flops == (2 * 128 * fixed + 2 * 8 * 96 * 9_977_856
                     + 5 * updates * 64 * 64 * 128 + 4 * 256_000 * 4096 * 2)


def test_prefill_count_against_a_hand_count():
    cfg = published()
    # a token meets 8 Mamba-2 blocks, 2 attention blocks and 8 expert blocks
    # outside their routed experts, and of its 6 choices the 16 / 128 held
    # here: three quarters of an expert a block
    per_token = (8 * 38_731_776 + 2 * 23_396_352
                 + 8 * (20_299_776 + 0.75 * 9_977_856))
    assert prefill_count.token_params(cfg) == per_token == 578_912_256
    assert prefill_count.scan_flops(cfg, 1000) == 5 * 1000 * 8 * 64 * 64 * 128
    pairs = 1000 * 1001 / 2
    assert prefill_count.attention_flops(cfg, [1000]) == 4 * 32 * 128 * 2 * pairs
    want = (2 * 1000 * per_token + 5 * 1000 * 8 * 64 * 64 * 128
            + 4 * 32 * 128 * 2 * pairs + 2 * 2688 * 16384)
    assert prefill_count.flops(cfg, [1000.0]) == want
    assert 1.19e12 < want < 1.20e12    # 1.2 GFLOP a token: 6 ms of the peak
    assert prefill_count.flops(cfg, [512.0] * 2) == 2 * prefill_count.flops(
        cfg, [512.0])


def _run(steps=12):
    cfg = published()

    def snap(scale):
        def s(v):
            return {"sum": v * steps * scale}
        return {"steps": steps * scale, "block_buckets": [4, 8, 16, 32, 64],
                "program_parts": {}, "stages": {
            "rt_llm_moe_experts_touched_total": {"": s(112.0)},
            "rt_llm_moe_expert_slots_total": {"": s(128.0)},
            "rt_llm_moe_max_load_total": {"": s(120.0)},
            "rt_llm_moe_assignments_total": {"": s(768.0)},
            "rt_llm_moe_expert_passes_total": {"": s(112.0)},
            "rt_llm_ssm_state_updates_total": {"": s(1024.0)},
            "rt_llm_decode_kv_tokens_live_total": {"": s(256_000.0)},
            "rt_llm_decode_kv_tokens_read_total": {"": s(257_024.0)}}}

    return {"cfg": cfg, "engine": {"max_batch": 128}, "peaks": PEAKS,
            "counters": {"before": snap(1), "after": snap(2)},
            "trace": {"busy_s": 2.0, "window_s": 2.0, "programs": {
                "jit_ssm_moe_decode_multi": {
                    "durations": [0.2] * 3 + [0.1] * 4, "seconds": 1.0},
                "jit_ssm_moe_prefill_batch": {"durations": [0.8],
                                              "seconds": 0.8}},
                "ops": [["pallas:ragged-dot-none:bf16_768_1856", 0.1]]},
            "trace_window": (0.0, 1.0),
            "dispatched_steps": [64, 8, 8, 8, 4, 4, 4, 4],
            "admitted_lens": [1024.0] * 8,
            # what ``readers/part_share.py`` makes of a trace and the
            # program's table: seconds by (program, part)
            "part_seconds": {"stale": set(), "unnamed_ops": [], "seconds": {
                ("jit_ssm_moe_decode_multi", "ssm"): 0.45,
                ("jit_ssm_moe_decode_multi", "conv"): 0.05,
                ("jit_ssm_moe_decode_multi", "experts"): 0.2,
                ("jit_ssm_moe_prefill_batch", "ssm"): 0.3}}}


def test_new_readers_on_a_hand_made_run():
    from benchmarks import run as bench_run

    cell = configs.load_cell(CELL)
    run = _run()
    got = {k: v["value"] for k, v in
           bench_run.read_metrics(cell, "per_layer", run).items()}
    assert got["moe.experts_touched_share"] == pytest.approx(87.5)
    assert got["moe.expert_passes_per_touched"] == pytest.approx(1.0)
    assert got["moe.load_imbalance"] == pytest.approx(16 * 120 / 768)
    assert got["engine.decode_step_ms.batch"] == pytest.approx(25.0)
    assert got["engine.prefill_share.batch"] == pytest.approx(40.0)
    assert got["kernel.decode_kv_read_amplification.batch"] == pytest.approx(
        257_024 / 256_000)
    assert got["kernel.grouped_matmul_share"] == pytest.approx(5.0)
    assert got["kernel.ssm_share.reason"] == pytest.approx(100 * 0.8 / 2.0)
    # 40 steps in the trace (three 8-step and four 4-step blocks); 8 expert
    # blocks: 14 of 16 held experts touched, 96 rows routed to them a block
    least = count.least_seconds(run["cfg"], PEAKS, 128, 1024.0, 256_000.0,
                                14.0, 96.0)
    assert got["kernel.ssm_moe_decode_roofline"] == pytest.approx(
        100 * 40 * least / 1.0)
    assert got["kernel.ssm_state_update_roofline"] == pytest.approx(
        100 * 40 * (4_370_464_768 / 819e9) / 0.5)
    assert got["kernel.ssm_moe_prefill_roofline"] == pytest.approx(
        100 * prefill_count.flops(run["cfg"], [1024.0] * 8) / 197e12 / 0.8)
    for name in ("kernel.ssm_moe_decode_roofline",
                 "kernel.ssm_state_update_roofline",
                 "kernel.ssm_moe_prefill_roofline"):
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
    joined = {"engine.decode_step_ms.batch", "moe.experts_touched_share",
              "moe.load_imbalance", "kernel.grouped_matmul_share",
              "kernel.decode_kv_read_amplification.batch"}
    assert not {m for m in left if "ssm" in m or "reason" in m or m in joined}


def test_the_new_cell_is_found_by_name_as_files_alone():
    manifest = configs.load_manifest()
    cell = configs.load_cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "reason_closed"
    traffic, cf = cell["traffic_file"], cell["config_file"]
    assert configs.load_module("drivers", traffic["driver"]).run
    assert traffic["driver"] in cf["correct_limits"]
    assert configs.load_module("reference", cf["reference"]).forward
    slots = cf["engine"]["max_batch"]
    assert (slots, traffic["callers"], traffic["list_size"], traffic["stream"]) == (
        128, 136, 256, False)
    assert (traffic["caller_stagger_s"], traffic["lead_in_s"]) == (0.1, 20)
    assert traffic["prompt"] == {"dist": "lognormal", "median": 768,
                                 "sigma": 0.6, "lengths": [512, 1024, 2048]}
    assert traffic["output"] == {"dist": "uniform", "min": 512, "max": 2048}
    assert traffic["max_total"] == 4096 == cf["engine"]["max_seq_len"]
    assert traffic["reference_check"] == [
        {"prompt_len": 2048, "max_tokens": 24},
        {"prompt_len": 200, "max_tokens": 24}]
    e2e = {m["name"] for m in configs.cell_metrics(cell, "end_to_end")}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    layer = configs.cell_metrics(cell, "per_layer")
    assert {m["moves"] for m in layer} >= {"serve_tokens_per_s"}
    names = {m["name"] for m in layer}
    assert {"engine.decode_step_ms.batch", "engine.prefill_share.batch",
            "kernel.ssm_moe_decode_roofline", "kernel.ssm_state_update_roofline",
            "kernel.ssm_moe_prefill_roofline", "kernel.ssm_share.reason",
            "kernel.grouped_matmul_share",
            "moe.experts_touched_share", "moe.load_imbalance",
            "kernel.decode_kv_read_amplification.batch",
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
        for n in ("state_rel_err", "kv_row_err_p50", "deep_state_err_p50",
                  "deep_kv_row_err_p50")} - {"deep_state_err_p50.decode",
                                             "short.deep_state_err_p50.decode"}
    from ray_tpu.llm.ssm_moe import WAVE_LIMIT
    assert WAVE_LIMIT == (8, 16384) and "wave_limit" not in traffic


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_the_traffics_multiset_whatever_the_seed(seed, monkeypatch):
    """256 quantiles of lognormal(768, 0.6) snapped to the three lengths are
    128 / 96 / 32 (mean 896), outputs uniform 512-2048 (mean 1,280): the
    multiset ``lib/traffic.py`` makes, which this driver cycles in ONE order
    whatever the seed, every aligned run of 8 holding the file's own mix."""
    from benchmarks.drivers import serve_ssm_moe as D
    from benchmarks.lib import traffic as T

    traffic = configs.load_cell(CELL)["traffic_file"]
    a, b = D.even_list(traffic), T.closed_list(traffic, seed)
    assert Counter(p for p, _ in a) == {512: 128, 1024: 96, 2048: 32}
    assert sum(p for p, _ in a) / 256 == 896 and sum(o for _, o in a) / 256 == 1280
    assert sorted(p for p, _ in a) == sorted(p for p, _ in b)
    assert sorted(o for _, o in a) == sorted(o for _, o in b)
    assert {sum(p == 2048 for p, _ in a[k:k + 8]) for k in range(0, 256, 8)} == {1}
    assert max(p + o for p, o in a) <= traffic["max_total"]
    # the prefill programs a wave limit of (8, 16384) lets these lengths form
    assert D.reachable_pads(traffic, 16) == [512, 1024, 2048]
    waves = {(p, w) for p in (512, 1024, 2048) for w in (1, 2, 4, 8)
             if w * p <= 16384}
    assert len(waves) == 12
    # under this driver's run the window gets that one list, and afterwards
    # the library is as it was
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
                     "kv_row_err_p50.decode", "deep_state_err_p50.prefill",
                     "deep_kv_row_err_p50.decode"):
            assert ref[prefix + name] < 1e-5, prefix + name
        assert ref[prefix + "token_logit_gap"] == 0.0
    assert ref["repeats"]
    rehearsed = line["rehearsal"]
    assert rehearsed["cpu-rehearsal.engine.compiles_in_window.batch"] == 0
    assert 0 < rehearsed["cpu-rehearsal.moe.experts_touched_share"] <= 100

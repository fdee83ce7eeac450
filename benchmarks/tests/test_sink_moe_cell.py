"""What PR 54 added to the benchmark, on the CPU: the window-with-a-sink
expert reference and its controls at the configuration's tiny size, the
decode, prefill and kernel counts against hand counts, the new reader and the
new metrics on a hand-made run, and the new cell found by name as files
alone."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.control_sink_moe import CONTROLS, variant_of
from benchmarks.drivers.serve_sink_moe import (pads_of, sink_moe_config,
                                               worst_of)
from benchmarks.lib import configs
from benchmarks.lib import weights_sink_moe as W
from benchmarks.reference import sink_moe as R
from benchmarks.roofline import sink_kind_attention as paged_count
from benchmarks.roofline import sink_moe_decode_multi as count
from benchmarks.roofline import sink_moe_prefill_batch as prefill_count
from benchmarks.roofline import sink_prefill_attention as kernel_count

CELL, CONFIG = "mimov2flash_agent_closed", "mimo-v2-flash.json"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def tiny():
    cf = configs.load_json("configs", CONFIG)
    return sink_moe_config({**cf, **cf["tiny"]})


def full():
    return sink_moe_config(configs.load_json("configs", CONFIG))


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_program_forward_agrees_and_lower_precision_does_not(seed):
    from ray_tpu.models.sink_moe import sink_moe_forward

    cfg = tiny()
    assert cfg.held == (4, 12) and cfg.n_experts == 16 and cfg.vocab_held == (256, 512)
    assert (cfg.kv_heads(False), cfg.kv_heads(True), cfg.rotary_lanes) == (2, 4, 8)
    tokens = np.random.default_rng(seed % 1000).integers(3, cfg.vocab_size, 60)
    params = W.make_params(W.seed_key(seed), cfg)
    want = R.forward(seed, cfg, tokens, q_block=32)
    got = sink_moe_forward(params, jnp.asarray(tokens)[None], cfg)[0]
    assert rel(got, want["logits"]) < 1e-5
    assert [k.shape[1] for k in want["k"]] == [48, 96, 96, 96, 96, 48, 96]
    assert [v.shape[1] for v in want["v"]] == [32, 64, 64, 64, 64, 32, 64]
    # layer 2's rows, behind layer 1's sink and experts: lower precision
    # stands apart
    errs = {m: rel(R.forward(seed, cfg, tokens, mode=m, q_block=32)["k"][2],
                   want["k"][2]) for m in ("bfloat16", "fp8")}
    assert errs["fp8"] > 2.5 * errs["bfloat16"] > 1e-4, errs


@pytest.mark.parametrize("name", sorted(CONTROLS))
def test_a_control_departs_from_the_reference_where_it_should(name):
    """Each control's first departing layer, at the tiny size: what precedes
    it agrees to rounding, what follows does not."""
    cfg = tiny()
    tokens = np.random.default_rng(1).integers(3, cfg.vocab_size, 60)
    want = R.forward(7, cfg, tokens, q_block=32)
    variant = variant_of(name, cfg)
    if name in ("window_127", "window_129"):    # the tiny window is 16
        variant = {"sliding_window": 16 + (1 if name.endswith("9") else -1)}
    elif name == "group_16":                    # 8 heads on 2: the full layers'
        variant = {"window_group": 4}
    got = R.forward(7, cfg, tokens, variant=variant, q_block=32)
    # layer 0's and layer 1's own rows precede every window layer's
    # attention and every routing; only layer 0's second half, the rotation
    # and the value's scale reach them
    early = {"layer0_routed": 1, "whole_head": 0, "lanes_96": 0, "unscaled": 0,
             "base_5e6": 1}.get(name, 2)
    if name == "sink_full_too":
        early = 1   # layer 0 is full: its attention now has a sink
    for i in range(early):
        assert rel(got["k"][i], want["k"][i]) < 1e-5, i
    late = max(early, 1) if name in ("whole_head", "lanes_96", "base_5e6") else early
    name_of = "v" if name == "unscaled" else "k"
    assert rel(got[name_of][late], want[name_of][late]) > 1e-3
    assert rel(got["logits"], want["logits"]) > 1e-3


def test_the_published_configuration_is_what_the_program_gets():
    cf = configs.load_json("configs", CONFIG)
    cfg = full()
    assert (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.v_head_dim) == (4096, 64, 192, 128)
    assert (cfg.n_kv_heads, cfg.swa_n_kv_heads, cfg.sliding_window) == (4, 8, 128)
    assert (cfg.n_experts, cfg.n_experts_per_tok, cfg.d_expert, cfg.d_ff) == (
        256, 8, 2048, 16384)
    assert (cfg.rotary_lanes, cfg.rope_theta, cfg.swa_rope_theta) == (64, 5e6, 1e4)
    assert (cfg.value_scale, cfg.sink_window, cfg.sink_full) == (0.707, True, False)
    assert cfg.held == (0, 16) and cfg.vocab_size == 19072 and cfg.n_layers == 7
    assert cfg.layer_window == (False, True, True, True, True, False, True)
    assert cfg.layer_moe == (False,) + (True,) * 6
    assert len(cf["hybrid_layer_pattern"]) == len(cf["moe_layer_freq"]) == 48
    assert cf["published"] == {
        "num_hidden_layers": 48, "n_routed_experts": 256, "vocab_size": 152576,
        "max_position_embeddings": 262144}
    assert len(cf["assumed"]) >= 8 and "16 chips share each layer" in cf["deployment"]
    # every number of the catalog's row under its own key, the four cuts apart
    row = next(json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"name": "MiMo-V2-Flash"' in line) if _catalog() else None
    if row:
        assert cf["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in cf["reduced"]:
                assert cf[key] == value, key
    with pytest.raises(ValueError, match="n_shared_experts"):
        sink_moe_config({**cf, "n_shared_experts": 1})
    with pytest.raises(ValueError, match="attention_chunk_size"):
        sink_moe_config({**cf, "attention_chunk_size": 256})


def _catalog() -> bool:
    import os
    return os.path.exists("/opt/skills/guides/model-configs/architectures.jsonl")


def test_decode_count_against_a_hand_count():
    cfg = full()
    q_o = 4096 * 64 * 192 + 64 * 128 * 4096                          # 83.9 M
    assert count.attn_params(cfg, False) == q_o + 4096 * 4 * 320 == 89_128_960
    assert count.attn_params(cfg, True) == q_o + 4096 * 8 * 320 == 94_371_840
    assert count.expert_params(cfg) == 3 * 4096 * 2048 == 25_165_824
    dense = 89_128_960 + 3 * 4096 * 16384                            # 290.5 M
    window = 94_371_840 + 4096 * 256                                 # 95.4 M
    full_moe = 89_128_960 + 4096 * 256
    assert count.layer_fixed_params(cfg, 0) == dense == 290_455_552
    assert count.layer_fixed_params(cfg, 1) == window
    assert count.layer_fixed_params(cfg, 5) == full_moe
    fixed = dense + 5 * window + full_moe + 4096 * 19072
    assert count.fixed_params(cfg) == fixed == 935_854_080
    assert (count.kv_row_bytes(cfg, False), count.kv_row_bytes(cfg, True)) == (2560, 5120)
    # 64 slots at 7,960 live positions: 509,440 in a full layer's reach, 8,192
    # in a window layer's; 83 of the 96 held experts touched
    got = count.bytes_per_step(cfg, 509_440, 8_192, 83.0, 74_304)
    weights = (fixed + 83 * 25_165_824) * 2
    rows = 509_440 * 2 * 2560 + 8_192 * 5 * 5120
    assert got == weights + rows + 4 * 74_304
    assert 6.04e9 < weights < 6.06e9 and 2.81e9 < rows < 2.82e9
    assert abs(count.least_seconds(cfg, PEAKS, 64, 509_440, 8_192, 83.0, 192.0,
                                   74_304) - got / 819e9) < 1e-12  # by bytes
    assert count.bytes_per_step(cfg, 509_440, 8_192, 60.0) < got
    # the window layers without their ring would read every live position
    assert count.kv_bytes(cfg, 509_440, 509_440) > 5 * count.kv_bytes(cfg, 509_440, 8_192)


def test_prefill_counts_against_hand_counts():
    cfg = full()
    pairs = kernel_count.pairs
    assert pairs(100, 128) == pairs(100, None) == 100 * 101 / 2
    band = 128 * 129 / 2 + (8192 - 128) * 128
    assert pairs(8192, 128) == band
    per_pair = 2 * 64 * (192 + 128)
    want = per_pair * (2 * 8192 * 8193 / 2 + 5 * band)
    assert kernel_count.flops(cfg, [8192]) == want
    assert 2.9e12 < want < 3.0e12
    # the window-with-sink kernel's own share: five layers' bands alone
    assert kernel_count.least_seconds(cfg, PEAKS, [8192]) == pytest.approx(
        per_pair * 5 * band / 197e12)
    # a token meets every layer outside its routed experts (916.7 M less the
    # head) and, of its 8 choices a layer, the 16 / 256 held here: half an
    # expert a layer in six layers
    assert prefill_count.token_params(cfg) == (
        935_854_080 - 4096 * 19072 + 6 * 0.5 * 25_165_824)
    want = (2 * 8192 * prefill_count.token_params(cfg)
            + kernel_count.flops(cfg, [8192]) + 2 * 4096 * 19072)
    assert prefill_count.flops(cfg, [8192.0]) == want
    assert 18.2e12 < want < 18.3e12   # 18.3 TFLOP: 0.093 s of the MXU's peak
    assert prefill_count.flops(cfg, [2048.0] * 2) == 2 * prefill_count.flops(cfg, [2048.0])


def test_paged_kernel_count_against_a_hand_count():
    cfg = full()
    assert (paged_count.layers(cfg, "window"), paged_count.layers(cfg, "full")) == (5, 2)
    rows = 64 * 64 * (192 + 128) * 2               # q in and o out: 2.6 MB
    assert paged_count.bytes_per_call(cfg, 64, "full", 509_440) == 509_440 * 2560 + rows
    assert paged_count.bytes_per_call(cfg, 64, "window", 8_192) == 8_192 * 5120 + rows
    assert paged_count.flops_per_call(cfg, 8_192) == 2 * 8_192 * 64 * 320
    one = (509_440 * 2560 + rows) / 819e9                     # bound by bytes
    assert paged_count.least_seconds(cfg, PEAKS, 64, "full", 509_440) == pytest.approx(2 * one)
    assert 1.59e-3 < one < 1.60e-3
    one = (8_192 * 5120 + rows) / 819e9
    assert paged_count.least_seconds(cfg, PEAKS, 64, "window", 8_192) == pytest.approx(5 * one)
    assert 54e-6 < one < 55e-6


def _run(steps=12):
    cfg = full()

    def snap(scale):
        def s(v):
            return {"sum": v * steps * scale}
        return {"steps": steps * scale, "block_buckets": [4, 8, 16, 32, 64], "stages": {
            "rt_llm_moe_experts_touched_total": {"": s(83.0)},
            "rt_llm_moe_expert_slots_total": {"": s(96.0)},
            "rt_llm_moe_max_load_total": {"": s(60.0)},
            "rt_llm_moe_assignments_total": {"": s(192.0)},
            "rt_llm_decode_kv_tokens_live_total": {"": s(151_405.7),
                                                   "window": s(8_192.0),
                                                   "full": s(509_440.0)},
            "rt_llm_decode_kv_tokens_read_total": {"": s(152_000.0)},
            "rt_llm_pages_drawn_total": {"full": s(500.0), "window": s(9.0)}}}

    return {"cfg": cfg, "engine": {"max_batch": 64, "page_size": 16,
                                   "max_seq_len": 18432},
            "peaks": PEAKS,
            "counters": {"before": snap(1), "after": snap(2)},
            "trace": {"busy_s": 2.0, "window_s": 2.0, "programs": {
                "jit_sink_moe_decode_multi": {
                    "durations": [0.2] * 3 + [0.1] * 4, "seconds": 1.0},
                "jit_sink_moe_prefill_batch": {"durations": [0.8],
                                               "seconds": 0.8}},
                "ops": [["pallas:gqa_sink_prefill_attention:bf16_1_8192_8192", 0.05],
                        ["pallas:gqa_prefill_attention:bf16_1_8192_8192", 0.05],
                        ["pallas:paged_window_part:f32_64_64_128", 0.1],
                        ["pallas:_paged_decode_attention:bf16_64_64_128", 0.2],
                        ["pallas:ragged-dot-none:bf16_384_4096", 0.5]]},
            "trace_window": (0.0, 1.0),
            "dispatched_steps": [64, 8, 8, 8, 4, 4, 4, 4],
            "admitted_lens": [8192.0],
            "recs_all": [{"sent": 0.2, "done": 0.6, "tokens": 11,
                          "prompt_len": 8192}]}


def test_new_readers_on_a_hand_made_run():
    from benchmarks import run as bench_run

    cell = configs.load_cell(CELL)
    run = _run()
    got = {k: v["value"] for k, v in
           bench_run.read_metrics(cell, "per_layer", run).items()}
    assert got["moe.experts_touched_share"] == pytest.approx(100 * 83 / 96)
    assert got["engine.decode_step_ms.batch"] == pytest.approx(25.0)
    assert got["engine.prefill_share.batch"] == pytest.approx(40.0)
    assert got["cache.window_pages_held_share.mixed"] == pytest.approx(1.8)
    # both tables once a step: 64 x (1,152 + 9) int32 entries
    least = count.least_seconds(run["cfg"], PEAKS, 64, 509_440.0, 8_192.0, 83.0,
                                192.0, table_entries=64 * 1161)
    assert got["kernel.sink_moe_decode_roofline"] == pytest.approx(
        100 * 40 * least / 1.0)  # three 8-step and four 4-step blocks
    assert 40 < got["kernel.sink_moe_decode_roofline"] < 100
    assert got["kernel.sink_prefill_attention_roofline"] == pytest.approx(
        100 * kernel_count.least_seconds(run["cfg"], PEAKS, [8192]) / 0.05)
    assert got["kernel.sink_moe_prefill_roofline"] == pytest.approx(
        100 * prefill_count.flops(run["cfg"], [8192.0]) / 197e12 / 0.8)
    for name, kind, reach, took in (
            ("kernel.paged_window_attention_roofline.agent", "window", 8_192.0, 0.1),
            ("kernel.paged_decode_attention_roofline.agent", "full", 509_440.0, 0.2)):
        assert got[name] == pytest.approx(100 * 40 * paged_count.least_seconds(
            run["cfg"], PEAKS, 64, kind, reach) / took)
        assert got[name] < 100
    # the accepted cell's kernel names are not this family's: its shares of
    # Command A's walks read nothing here
    assert "kernel.paged_window_attention_roofline" not in got
    # a program without the counters or the kernels (the parent) reads as
    # nothing, and nothing raises
    bare = _run()
    for snap in bare["counters"].values():
        snap["stages"] = {}
    bare["trace"]["ops"] = []
    bare["dispatched_steps"] = []
    bare["admitted_lens"] = []
    left = bench_run.read_metrics(cell, "per_layer", bare)
    assert not {"kernel.sink_moe_decode_roofline",
                "kernel.sink_moe_prefill_roofline",
                "kernel.sink_prefill_attention_roofline",
                "kernel.paged_window_attention_roofline.agent",
                "kernel.paged_decode_attention_roofline.agent",
                "kernel.attn_project_share.agent", "kernel.kv_write_share.agent",
                "cache.window_pages_held_share.mixed"} & set(left)


def test_the_new_cell_is_found_by_name_as_files_alone():
    manifest = configs.load_manifest()
    cell = configs.load_cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "agent_closed"
    traffic, cf = cell["traffic_file"], cell["config_file"]
    assert configs.load_module("drivers", traffic["driver"]).run
    assert traffic["driver"] in cf["correct_limits"]
    slots = cf["engine"]["max_batch"]
    assert (slots, traffic["callers"], traffic["list_size"], traffic["stream"]) == (
        64, 72, 256, False)
    assert (traffic["caller_stagger_s"], traffic["lead_in_s"]) == (0.25, 20)
    assert "temperature" not in traffic
    assert traffic["prompt"] == {"dist": "lognormal", "median": 6144, "sigma": 0.8,
                                 "lengths": [2048, 4096, 8192, 16384]}
    assert (traffic["output"]["min"], traffic["output"]["max"]) == (512, 2048)
    assert traffic["reference_check"] == [
        {"prompt_len": 4096, "max_tokens": 24}, {"prompt_len": 100, "max_tokens": 64}]
    assert cf["engine"]["n_pages"] == {"full": 44600, "window": 600}
    assert cf["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size",
                             "max_position_embeddings"]
    e2e = {m["name"] for m in configs.cell_metrics(cell, "end_to_end")}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    layer = configs.cell_metrics(cell, "per_layer")
    names = {m["name"] for m in layer}
    # this PR's own, and the accepted ones the cell joins by name
    assert {"kernel.sink_moe_decode_roofline", "kernel.sink_moe_prefill_roofline",
            "kernel.paged_window_attention_roofline.agent",
            "kernel.paged_decode_attention_roofline.agent",
            "kernel.sink_prefill_attention_roofline",
            "kernel.attn_project_share.agent", "kernel.kv_write_share.agent",
            "cache.window_pages_held_share.mixed", "engine.decode_step_ms.batch",
            "engine.prefill_share.batch", "moe.load_imbalance",
            "kernel.unnamed_share.batch", "device.idle_share.batch",
            "engine.compiles_in_window.batch", "setup.weights_s"} <= names
    assert len(manifest["per_layer"]) <= 112
    for m in layer:
        spec = configs.load_json("layer_metrics", m["name"] + ".json")
        assert set(spec) == {"name", "reader", "args"}
        assert configs.load_module("readers", spec["reader"]).read
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == cf["reduced"] and set(cf["published"]) == set(cf["reduced"])
    # the issue's list: 256 quantiles are 49 / 79 / 79 / 49 of the four
    # lengths, mean 7,320, in one order whatever the seed
    from collections import Counter

    from benchmarks.drivers.serve_cohere2_moe import even_list
    from benchmarks.lib import traffic as T
    a, b = even_list(traffic), T.closed_list(traffic, 2**31 + 5)
    assert Counter(p for p, _ in a) == {2048: 49, 4096: 79, 8192: 79, 16384: 49}
    assert sum(p for p, _ in a) / 256 == 7320
    assert sorted(p for p, _ in a) == sorted(p for p, _ in b)
    assert max(p + o for p, o in a) <= traffic["max_total"] == cf["engine"]["max_seq_len"]
    # ten prefill programs of the traffic's, one more for the short check
    assert pads_of(T.quantile_lengths(traffic["prompt"], 4096), 16) == [
        2048, 4096, 8192, 16384]
    assert pads_of([4096, 100], 16) == [112, 4096]
    from ray_tpu.llm.sink_moe import WAVE_LIMIT
    assert WAVE_LIMIT == (8, 16384) and "wave_limit" not in traffic


def test_the_worst_of_several_checked_requests_stands():
    a = {"kv_rel_err.prefill": 0.001, "repeats": True, "tokens": 24, "mode": "float32"}
    b = {"kv_rel_err.prefill": 0.003, "repeats": False, "tokens": 64, "mode": "float32"}
    assert worst_of([a, b]) == {"kv_rel_err.prefill": 0.003, "repeats": False,
                                "tokens": 24, "mode": "float32"}
    assert worst_of([a]) == a


def test_the_cell_rehearses_on_the_cpu_at_tiny_sizes(tmp_path):
    """The whole cell through ``run.py --allow-cpu``: deploy, warm-up (the
    checks' own pads too), both checked requests against the reference, the
    closed loop, the readers."""
    import os
    import subprocess
    import sys

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
    limits = configs.load_json("configs", CONFIG)["correct_limits"]["serve_sink_moe"]
    for name in limits:
        assert ref[name] < 1e-5, name
    assert ref["repeats"] and 0.1 < ref["sink_share_p50"] < 0.6
    rehearsed = line["rehearsal"]
    assert rehearsed["cpu-rehearsal.engine.compiles_in_window.batch"] == 0
    assert 0 < rehearsed["cpu-rehearsal.cache.window_pages_held_share.mixed"] < 100
    assert 0 < rehearsed["cpu-rehearsal.moe.experts_touched_share"] <= 100

"""What PR 31 added to the benchmark, on the CPU: the window + full attention
expert reference and its controls at the configuration's tiny size, the decode
and prefill-kernel counts against hand counts, the new readers on a hand-made
run, and the new cell found by name as files alone."""
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers.serve_cohere2_moe import cohere2_moe_config
from benchmarks.lib import configs
from benchmarks.lib import weights_cohere2_moe as W
from benchmarks.reference import cohere2_moe as R
from benchmarks.roofline import cohere2_moe_decode_multi as count
from benchmarks.roofline import cohere2_moe_prefill_batch as prefill_count
from benchmarks.roofline import gqa_prefill_attention as kernel_count
from benchmarks.roofline import paged_kind_attention as paged_count

CELL, CONFIG = "commandaplus_mixed_closed", "command-a-plus-05-2026.json"


def tiny():
    cf = configs.load_json("configs", CONFIG)
    return cohere2_moe_config({**cf, **cf["tiny"]})


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_program_forward_agrees_and_the_controls_do_not(seed):
    from ray_tpu.models.cohere2_moe import cohere2_moe_forward

    cfg = tiny()
    assert cfg.held == (4, 12) and cfg.n_experts == 16 and cfg.vocab_held == (256, 512)
    tokens = np.random.default_rng(seed % 1000).integers(3, cfg.vocab_size, 80)
    params = W.make_params(W.seed_key(seed), cfg)
    want = R.forward(seed, cfg, tokens, q_block=32)
    got = cohere2_moe_forward(params, jnp.asarray(tokens)[None], cfg)[0]
    assert rel(got, want["logits"]) < 1e-5
    assert want["k"].shape == (4, 80, cfg.n_kv_heads * cfg.head_dim)
    # layer 1's rows, one whole block on: lower precision and a reference
    # whose window layers attend everything both stand apart, the second
    # only past the window (32)
    errs = {m: rel(R.forward(seed, cfg, tokens, mode=m, q_block=32)["k"][1],
                   want["k"][1]) for m in ("bfloat16", "fp8")}
    assert errs["fp8"] > 2.5 * errs["bfloat16"] > 1e-4, errs
    open_ = R.forward(seed, cfg, tokens, variant={"window": None}, q_block=32)
    assert rel(open_["k"][1, :32], want["k"][1, :32]) < 1e-5
    assert rel(open_["k"][1, 40:], want["k"][1, 40:]) > 5 * errs["bfloat16"]


def test_the_published_configuration_is_what_the_program_gets():
    cf = configs.load_json("configs", CONFIG)
    cfg = cohere2_moe_config(cf)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (4096, 128, 8, 128)
    assert (cfg.n_experts, cfg.n_experts_per_tok, cfg.d_expert,
            cfg.n_shared_experts, cfg.sliding_window) == (128, 8, 4096, 4, 4096)
    assert cfg.held == (0, 16) and cfg.vocab_size == 32768 and cfg.n_layers == 4
    assert cfg.layer_types == ("sliding_attention",) * 3 + ("full_attention",)
    assert len(cf["layer_types"]) == 32 and cf["published"] == {
        "num_hidden_layers": 32, "num_experts": 128, "vocab_size": 262144,
        "max_position_embeddings": 200000}
    assert len(cf["assumed"]) >= 3 and "8 chips share each layer" in cf["deployment"]
    with pytest.raises(ValueError, match="use_parallel_block"):
        cohere2_moe_config({**cf, "use_parallel_block": False})
    with pytest.raises(ValueError, match="shared_expert_combination_strategy"):
        cohere2_moe_config({**cf, "shared_expert_combination_strategy": "sum"})


def test_decode_count_against_a_hand_count():
    cfg = cohere2_moe_config(configs.load_json("configs", CONFIG))
    attn = 2 * 4096 * 16384 + 2 * 4096 * 1024                        # 142.6 M
    assert count.attn_params(cfg) == attn == 142_606_336
    assert count.expert_params(cfg) == 3 * 4096 * 4096 == 50_331_648
    layer = attn + 4096 * 128 + 4 * 50_331_648                       # 344.4 M
    assert layer == 344_457_216
    assert count.fixed_params(cfg) == 4 * layer + 4096 * 32768
    assert count.kv_row_bytes(cfg) == 4096                           # 4 KB
    # all 16 held experts touched, 150,000 positions within reach a layer:
    # 9.47 GB of weights and 2.46 GB of rows a step
    got = count.bytes_per_step(cfg, 150_000, 16.0)
    weights = (4 * layer + 4096 * 32768 + 4 * 16 * 50_331_648) * 2
    assert got == weights + 150_000 * 4 * 4096
    assert 9.46e9 < weights < 9.48e9 and 11.9e9 < got < 12.0e9
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    assert abs(count.least_seconds(cfg, peaks, 48, 150_000, 16.0, 48.0)
               - got / 819e9) < 1e-12                       # bound by bytes
    assert count.bytes_per_step(cfg, 150_000, 12.0) < got


def test_prefill_kernel_count_against_a_hand_count():
    cfg = cohere2_moe_config(configs.load_json("configs", CONFIG))
    assert kernel_count.pairs(512, None) == kernel_count.pairs(512, 4096) == 512 * 513 / 2
    band = 4096 * 4097 / 2 + (8192 - 4096) * 4096
    assert kernel_count.pairs(8192, 4096) == band
    want = 4 * 128 * 128 * (3 * band + 8192 * 8193 / 2)
    assert kernel_count.flops(cfg, [8192]) == want
    assert 7.1e12 < want < 7.2e12     # 7.1 TFLOP: 36 ms of the MXU's peak


def test_prefill_program_count_against_a_hand_count():
    cfg = cohere2_moe_config(configs.load_json("configs", CONFIG))
    # a token meets the layer outside its routed experts (344.4 M) and, of
    # its 8 choices, the 16 / 128 that are held here: one expert (50.3 M)
    assert prefill_count.token_params(cfg) == 344_457_216 + 50_331_648
    want = (2 * 8192 * 4 * 394_788_864 + kernel_count.flops(cfg, [8192])
            + 2 * 4096 * 32768)
    assert prefill_count.flops(cfg, [8192.0]) == want
    assert 33.0e12 < want < 33.1e12   # 33 TFLOP: 0.168 s of the MXU's peak
    assert prefill_count.flops(cfg, [512.0] * 2) == 2 * prefill_count.flops(cfg, [512.0])


def test_paged_kernel_count_against_a_hand_count():
    cfg = cohere2_moe_config(configs.load_json("configs", CONFIG))
    assert (paged_count.layers(cfg, "window"), paged_count.layers(cfg, "full")) == (3, 1)
    # 94,500 positions within a window layer's reach over 48 slots: 387 MB of
    # rows, 3 MB of q and o; 6.2 GFLOP a call
    rows = 2 * 48 * 128 * 128 * 2
    assert paged_count.bytes_per_call(cfg, 48, 94_500) == 94_500 * 4096 + rows
    assert paged_count.flops_per_call(cfg, 94_500) == 4 * 94_500 * 128 * 128
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    one = (94_500 * 4096 + rows) / 819e9                     # bound by bytes
    assert paged_count.least_seconds(cfg, peaks, 48, "window", 94_500) == pytest.approx(3 * one)
    assert paged_count.least_seconds(cfg, peaks, 48, "full", 94_500) == pytest.approx(one)
    assert 0.47e-3 < one < 0.48e-3


def _run(steps=12):
    cfg = cohere2_moe_config(configs.load_json("configs", CONFIG))

    def snap(scale):
        def s(v):
            return {"sum": v * steps * scale}
        return {"steps": steps * scale, "block_buckets": [4, 8, 16, 32, 64], "stages": {
            "rt_llm_moe_experts_touched_total": {"": s(60.0)},
            "rt_llm_moe_expert_slots_total": {"": s(64.0)},
            "rt_llm_moe_max_load_total": {"": s(160.0)},
            "rt_llm_moe_assignments_total": {"": s(192.0)},
            "rt_llm_decode_kv_tokens_live_total": {"": s(150_000.0),
                                                   "window": s(120_000.0),
                                                   "full": s(240_000.0)},
            "rt_llm_decode_kv_tokens_read_total": {"": s(150_600.0)},
            "rt_llm_pages_drawn_total": {"full": s(50.0), "window": s(24.0)}}}

    return {"cfg": cfg, "engine": {"max_batch": 48},
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
            "counters": {"before": snap(1), "after": snap(2)},
            "trace": {"busy_s": 2.0, "window_s": 2.0, "programs": {
                "jit_cohere2_moe_decode_multi": {
                    "durations": [0.2] * 3 + [0.1] * 4, "seconds": 1.0},
                "jit_cohere2_moe_prefill_batch": {"durations": [0.8],
                                                  "seconds": 0.8}},
                "ops": [["pallas:gqa_prefill_attention:bf16_1_8192_16384", 0.05],
                        ["pallas:_paged_window_attention:bf16_48_128_128", 0.1],
                        ["pallas:_paged_decode_attention:bf16_48_128_128", 0.06],
                        ["pallas:ragged-dot-none:bf16_384_4096", 0.5]]},
            "trace_window": (0.0, 1.0),
            # the steps annotated on the trace's decode dispatches; a block
            # of 64 dispatched before the span opened stands at its edge
            "dispatched_steps": [64, 8, 8, 8, 4, 4, 4, 4],
            # the one prefill wave admitted inside the span, from its
            # engine.admit annotation: one prompt of 8,192 true tokens
            "admitted_lens": [8192.0],
            "recs_all": [{"sent": 0.2, "done": 0.6, "tokens": 11,
                          "prompt_len": 8192}]}


def test_new_readers_on_a_hand_made_run():
    from benchmarks import run as bench_run

    cell = configs.load_cell(CELL)
    run = _run()
    got = {k: v["value"] for k, v in
           bench_run.read_metrics(cell, "per_layer", run).items()}
    assert got["moe.experts_touched_share"] == pytest.approx(93.75)
    # largest 40 a layer (160 / 4) over the mean 3 (192 / 64)
    assert got["moe.load_imbalance"] == pytest.approx(16 * 160 / 192)
    assert got["engine.decode_step_ms.batch"] == pytest.approx(25.0)
    assert got["engine.prefill_share.batch"] == pytest.approx(40.0)
    assert got["kernel.decode_kv_read_amplification.batch"] == pytest.approx(1.004)
    assert got["cache.window_pages_held_share.mixed"] == pytest.approx(48.0)
    least = count.least_seconds(run["cfg"], run["peaks"], 48, 150_000.0, 15.0, 48.0)
    assert got["kernel.swa_moe_decode_roofline"] == pytest.approx(
        100 * 40 * least / 1.0)  # three 8-step and four 4-step blocks
    assert got["kernel.swa_moe_decode_roofline"] < 100
    assert got["kernel.gqa_prefill_attention_roofline"] == pytest.approx(
        100 * kernel_count.flops(run["cfg"], [8192]) / 197e12 / 0.05)
    assert got["kernel.swa_moe_prefill_roofline"] == pytest.approx(
        100 * prefill_count.flops(run["cfg"], [8192.0]) / 197e12 / 0.8)
    # 40 steps in the trace, each kind's positions a step from its own sample
    for name, kind, reach, took in (
            ("kernel.paged_window_attention_roofline", "window", 120_000.0, 0.1),
            ("kernel.paged_decode_attention_roofline.mixed", "full", 240_000.0, 0.06)):
        assert got[name] == pytest.approx(100 * 40 * paged_count.least_seconds(
            run["cfg"], run["peaks"], 48, kind, reach) / took)
        assert got[name] < 100
    assert got["kernel.grouped_matmul_share"] == pytest.approx(25.0)
    # a program without the counters or the kernel (the parent) reads as
    # nothing, and nothing raises
    bare = _run()
    for snap in bare["counters"].values():
        snap["stages"] = {}
    bare["trace"]["ops"] = []
    bare["dispatched_steps"] = []       # a trace with no annotation in it
    bare["admitted_lens"] = []
    left = bench_run.read_metrics(cell, "per_layer", bare)
    assert not {"moe.experts_touched_share", "moe.load_imbalance",
                "kernel.swa_moe_decode_roofline", "engine.decode_step_ms.batch",
                "kernel.gqa_prefill_attention_roofline",
                "kernel.swa_moe_prefill_roofline",
                "kernel.paged_window_attention_roofline",
                "kernel.paged_decode_attention_roofline.mixed",
                "kernel.grouped_matmul_share",
                "kernel.decode_kv_read_amplification.batch",
                "cache.window_pages_held_share.mixed"} & set(left)


def test_annotations_are_read_from_the_trace_and_an_older_trace_reads_as_nothing():
    """The two sure sources on the trace's host plane: ``steps`` of every
    decode dispatch and (``prompts``, ``tokens``) of every prefill wave's
    admit; the admit that only reserves slots (pad 0) and a program whose
    admit carries no ``tokens`` (the parent) give nothing."""
    from types import SimpleNamespace as NS

    from benchmarks.readers.decode_step_ms_dispatched import dispatched_steps
    from benchmarks.readers.prefill_roofline_admitted import admitted_lens

    def ev(name, **stats):
        return NS(name=name, stats=list(stats.items()))

    loop = NS(name="python3", events=[
        ev("engine.admit", pad=0, wave=0, prompts=3, splits=0),
        ev("engine.admit", pad=1024, wave=2, prompts=2, tokens=2040),
        ev("engine.admit", pad=12288, wave=1, prompts=1, tokens=12288),
        ev("engine.admit", pad=512, wave=1, prompts=1),
        ev("engine.decode_dispatch", steps=8, live=48),
        ev("engine.decode_dispatch", steps=32, live=48)])
    planes = [NS(name="/device:TPU:0", lines=[]), NS(name="/host:CPU", lines=[loop])]
    assert admitted_lens(planes) == [1020.0, 1020.0, 12288.0]
    assert dispatched_steps(planes) == [8, 32]
    assert admitted_lens([NS(name="/host:CPU", lines=[])]) == []


def test_the_new_cell_is_found_by_name_as_files_alone():
    manifest = configs.load_manifest()
    cell = configs.load_cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "mixed_closed"
    traffic, cf = cell["traffic_file"], cell["config_file"]
    assert configs.load_module("drivers", traffic["driver"]).run
    assert traffic["driver"] in cf["correct_limits"]
    slots = cf["engine"]["max_batch"]
    assert (traffic["callers"], traffic["list_size"], traffic["stream"]) == (
        slots + 8, 256, False)
    assert (traffic["caller_stagger_s"], traffic["lead_in_s"]) == (0.25, 15)
    assert "temperature" not in traffic
    assert traffic["prompt"]["lengths"] == [512, 1024, 8192, 12288]
    assert (traffic["output"]["min"], traffic["output"]["max"]) == (384, 1024)
    assert cf["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size",
                             "max_position_embeddings"]
    e2e = {m["name"] for m in configs.cell_metrics(cell, "end_to_end")}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    layer = configs.cell_metrics(cell, "per_layer")
    assert {m["moves"] for m in layer} >= {"serve_tokens_per_s"}
    names = {m["name"] for m in layer}
    assert {"engine.decode_step_ms.batch", "kernel.swa_moe_decode_roofline",
            "kernel.gqa_prefill_attention_roofline",
            "cache.window_pages_held_share.mixed", "moe.load_imbalance",
            "device.idle_share.batch", "engine.compiles_in_window.batch"} <= names
    for m in layer:
        spec = configs.load_json("layer_metrics", m["name"] + ".json")
        assert set(spec) == {"name", "reader", "args"}
        assert configs.load_module("readers", spec["reader"]).read
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == cf["reduced"] and set(cf["published"]) == set(cf["reduced"])
    # the issue's list: 256 quantiles of the distribution are 54 / 129 / 43 /
    # 30 of the four lengths: the multiset lib/traffic.py makes, in one order
    # whatever the seed, every aligned run of 8 holding the file's own mix
    from collections import Counter

    from benchmarks.drivers.serve_cohere2_moe import even_list
    from benchmarks.lib import traffic as T
    a, b = even_list(traffic), T.closed_list(traffic, 2**31 + 5)
    assert Counter(p for p, _ in a) == {512: 54, 1024: 129, 8192: 43, 12288: 30}
    assert sorted(p for p, _ in a) == sorted(p for p, _ in b)
    assert sorted(o for _, o in a) == sorted(o for _, o in b)
    assert {sum(p >= 8192 for p, _ in a[k:k + 8]) for k in range(0, 256, 8)} == {2, 3}
    long_outputs = [o for p, o in a if p >= 8192]  # lengths pair freely
    assert min(long_outputs) < 420 and max(long_outputs) > 990
    assert max(p + o for p, o in a) <= traffic["max_total"] == cf["engine"]["max_seq_len"]
    # the issue's wave limit is the family's own: the traffic file has none
    from ray_tpu.llm.cohere2_moe import WAVE_LIMIT
    assert WAVE_LIMIT == (8, 16384) and "wave_limit" not in traffic


def test_the_window_gets_one_list_whatever_the_seed(monkeypatch):
    """``drivers/serve.py``'s ``window`` asks ``lib/traffic.py`` for the list
    by the seed; under this driver's ``run`` the answer is ``even_list``, and
    afterwards the library is as it was."""
    from types import SimpleNamespace as NS

    from benchmarks.drivers import serve_cohere2_moe as D
    from benchmarks.lib import traffic as T

    traffic = configs.load_cell(CELL)["traffic_file"]
    monkeypatch.setattr(D, "setup", lambda cell, args, clock: {"traffic": traffic})
    monkeypatch.setattr(D, "window", lambda ctx, seed, *a: T.closed_list(
        ctx["traffic"], seed))
    got = [D.run({}, NS(seed=seed, seconds=1, trace=0, trace_seconds=1), None)
           for seed in (1, 2**31 + 5)]
    assert got[0] == got[1] == D.even_list(traffic)
    assert T.closed_list(traffic, 1) != T.closed_list(traffic, 2)

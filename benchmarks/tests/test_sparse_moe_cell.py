"""What PR 33 added to the benchmark, on the CPU: the learned-sparse-attention
expert reference and its controls at the configuration's tiny size, every new
roofline count against a hand count, the new readers on a hand-made run, the
new cell found by name as files alone, its traffic's multiset whatever the
seed, and the ``--allow-cpu`` rehearsal of the whole cell."""
import json
import os
import subprocess
import sys
from collections import Counter
from types import SimpleNamespace as NS

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers.serve_sparse_moe import sparse_moe_config
from benchmarks.lib import configs
from benchmarks.lib import weights_sparse_moe as W
from benchmarks.reference import sparse_moe as R
from benchmarks.roofline import sparse_decode_attention as kernels_count
from benchmarks.roofline import sparse_moe_decode_multi as count
from benchmarks.roofline import sparse_moe_prefill_batch as prefill_count
from benchmarks.roofline import sparse_prefill_attention as picked_count

CELL, CONFIG = "keyevl2_longctx_closed", "keye-vl-2.0-30b-a3b.json"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def published():
    return sparse_moe_config(configs.load_json("configs", CONFIG))


def tiny():
    cf = configs.load_json("configs", CONFIG)
    return sparse_moe_config({**cf, **cf["tiny"]})


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_program_forward_agrees_and_the_controls_do_not(seed):
    from ray_tpu.models.sparse_moe import sparse_moe_forward

    cfg = tiny()
    assert cfg.held == (4, 12) and cfg.n_experts == 16 and cfg.topk == 32
    tokens = np.random.default_rng(seed % 1000).integers(3, cfg.vocab_size, 100)
    params = W.make_params(W.seed_key(seed), cfg)
    want = R.forward(seed, cfg, tokens, q_block=32)
    got = sparse_moe_forward(params, jnp.asarray(tokens)[None], cfg)[0]
    assert rel(got, want["logits"]) < 1e-5
    assert want["ki"].shape == (3, 100, cfg.indexer_head_dim)
    assert np.asarray(want["attended"])[2].tolist() == [
        min(t + 1, 32) for t in range(100)]
    # the last layer's rows: a lower precision stands apart everywhere; the
    # controls only this model has stand apart past topk and not before it
    # (queries under topk select everything, whatever the rule)
    errs = {m: rel(R.forward(seed, cfg, tokens, mode=m, q_block=32)["k"][2],
                   want["k"][2]) for m in ("bfloat16", "fp8")}
    assert errs["fp8"] > 2.5 * errs["bfloat16"] > 1e-4, errs
    for variant in ({"select": "none"}, {"select": "recent"}):
        other = R.forward(seed, cfg, tokens, variant=variant, q_block=32)
        assert rel(other["k"][2, :32], want["k"][2, :32]) < 1e-5
        assert rel(other["k"][2, 40:], want["k"][2, 40:]) > 5 * errs["bfloat16"]
    half = R.forward(seed, cfg, tokens, variant={"topk": 16}, q_block=32)
    assert rel(half["k"][2, :16], want["k"][2, :16]) < 1e-5
    assert rel(half["k"][2, 20:32], want["k"][2, 20:32]) > 5 * errs["bfloat16"]


def test_the_published_configuration_is_what_the_program_gets():
    cf = configs.load_json("configs", CONFIG)
    cfg = sparse_moe_config(cf)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (2048, 32, 4, 128)
    assert (cfg.indexer_heads, cfg.indexer_head_dim, cfg.topk, cfg.q_chunk,
            cfg.kv_chunk) == (16, 64, 2048, 512, 512)
    assert (cfg.n_experts, cfg.n_experts_per_tok, cfg.d_expert) == (128, 8, 768)
    assert cfg.held == (0, 16) and cfg.vocab_size == 18992 and cfg.n_layers == 12
    assert cfg.rope_theta == 1e7 and cfg.max_seq_len == 16384 == 8 * cfg.topk
    assert cf["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                               "vocab_size": 151936,
                               "max_position_embeddings": 262144}
    # every number of the catalog's config under its own key, unchanged but
    # for the four in ``reduced``
    catalog = {"head_dim": 128, "hidden_size": 2048, "intermediate_size": 6144,
               "max_window_layers": 48, "moe_intermediate_size": 768,
               "num_attention_heads": 32, "num_experts_per_tok": 8,
               "num_key_value_heads": 4, "num_local_experts": 128,
               "rms_norm_eps": 1e-06, "rope_theta": 10000000,
               "decoder_sparse_step": 1}
    assert {k: cf[k] for k in catalog} == catalog
    assert cf["sa_config"] == {"indexer_head_dim": 64, "indexer_num_heads": 16,
                               "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                               "q_chunk_size": 512, "topk": 2048}
    assert cf["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert [a[:3] for a in cf["assumed"][:5]] == ["(a)", "(b)", "(c)", "(d)", "(e)"]
    assert "8 chips share each layer" in cf["deployment"]
    assert not cfg.vocab_held[0] <= cf["engine"]["eos_id"] < cfg.vocab_held[1]
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        sparse_moe_config({**cf, "tie_word_embeddings": True})
    with pytest.raises(ValueError, match="experts held"):
        sparse_moe_config({**cf, "num_experts": 128})


def test_decode_count_against_a_hand_count():
    cfg = published()
    attn = 2 * 2048 * 4096 + 2 * 2048 * 512                          # 18.87 M
    assert count.attn_params(cfg) == attn == 18_874_368
    assert count.indexer_params(cfg) == 2048 * (1024 + 64 + 16) == 2_260_992
    assert count.expert_params(cfg) == 3 * 2048 * 768 == 4_718_592
    layer = attn + 2_260_992 + 2048 * 128                            # 21.4 M
    assert count.layer_params(cfg) == layer == 21_397_504
    assert count.fixed_params(cfg) == 12 * layer + 2048 * 18992 == 295_665_664
    assert (count.kv_row_bytes(cfg), count.index_row_bytes(cfg)) == (2048, 128)
    # 300,000 live positions scored in each of 12 layers, 32 slots past topk
    # attend 2,048 rows a layer, 14 of 16 held experts touched: 2.18 GB of
    # weights, 0.46 GB of indexer keys, 1.61 GB of picked rows
    scored, attended = 12 * 300_000, 12 * 32 * 2048
    got = count.bytes_per_step(cfg, scored, attended, 14.0)
    weights = (295_665_664 + 12 * 14 * 4_718_592) * 2
    assert weights == 2_176_778_240
    assert got == weights + 3_600_000 * 128 + 786_432 * 2048 == 4_248_190_976
    assert abs(count.least_seconds(cfg, PEAKS, 32, scored, attended, 14.0, 16.0)
               - got / 819e9) < 1e-12                       # bound by bytes
    # a walk of every live page would read 7.4 GB of rows: not in the count
    assert got < weights + scored * (128 + 2048)
    flops = count.flops_per_step(cfg, 32, scored, attended, 16.0)
    assert flops == (2 * 32 * 295_665_664 + 2 * 12 * 16 * 4_718_592
                     + 2 * scored * 16 * 64 + 4 * attended * 32 * 128)


def test_decode_kernels_count_against_a_hand_count():
    cfg = published()
    scored, attended = 12 * 300_000, 12 * 32 * 2048
    rows = 12 * 32 * 2 * (2 * 32 * 128 + 16 * 64)   # q and o, qI: 7.1 MB
    assert kernels_count.bytes_per_step(cfg, 32, scored, attended) == (
        scored * 128 + attended * 2048 + rows)
    assert kernels_count.flops_per_step(cfg, scored, attended) == (
        2 * scored * 1024 + 4 * attended * 4096)
    least = kernels_count.least_seconds(cfg, PEAKS, 32, scored, attended)
    assert least == pytest.approx((460_800_000 + 1_610_612_736 + rows) / 819e9)
    assert 2.5e-3 < least < 2.6e-3


def test_prefill_counts_against_hand_counts():
    cfg = published()
    assert picked_count.picked_pairs(2048, 2048) == 2048 * 2049 / 2
    assert picked_count.picked_pairs(100, 2048) == 100 * 101 / 2
    band = 2048 * 2049 / 2 + (8192 - 2048) * 2048
    assert picked_count.picked_pairs(8192, 2048) == band == 14_681_088
    assert picked_count.causal_pairs(8192) == 8192 * 8193 / 2
    attention = 4 * 32 * 128 * 12 * band
    assert picked_count.flops(cfg, [8192]) == attention
    assert 2.88e12 < attention < 2.89e12      # 2.9 TFLOP of 6.6 dense causal
    # a token meets the layer outside its routed experts (21.4 M) and, of its
    # 8 choices, the 16 / 128 held here: one expert (4.72 M)
    assert prefill_count.token_params(cfg) == 21_397_504 + 4_718_592
    indexer = 2 * 16 * 64 * 12 * 8192 * 8193 / 2
    assert prefill_count.indexer_flops(cfg, [8192]) == indexer
    want = (2 * 8192 * 12 * 26_116_096 + indexer + attention + 2 * 2048 * 18992)
    assert prefill_count.flops(cfg, [8192.0]) == want
    assert 8.84e12 < want < 8.86e12     # 8.85 TFLOP: 45 ms of the MXU's peak
    assert prefill_count.flops(cfg, [4096.0] * 2) == 2 * prefill_count.flops(
        cfg, [4096.0])


def _run(steps=12):
    cfg = published()

    def snap(scale):
        def s(v):
            return {"sum": v * steps * scale}
        return {"steps": steps * scale, "block_buckets": [4, 8, 16, 32, 64], "stages": {
            "rt_llm_moe_experts_touched_total": {"": s(168.0)},
            "rt_llm_moe_expert_slots_total": {"": s(192.0)},
            "rt_llm_moe_max_load_total": {"": s(60.0)},
            "rt_llm_moe_assignments_total": {"": s(192.0)},
            "rt_llm_moe_expert_passes_total": {"": s(168.0)},
            "rt_llm_sparse_positions_scored_total": {"": s(3_600_000.0)},
            "rt_llm_sparse_rows_attended_total": {"": s(786_432.0)},
            "rt_llm_sparse_kv_positions_fetched_total": {"": s(3_606_000.0)}}}

    return {"cfg": cfg, "engine": {"max_batch": 32}, "peaks": PEAKS,
            "counters": {"before": snap(1), "after": snap(2)},
            "trace": {"busy_s": 2.0, "window_s": 2.0, "programs": {
                "jit_sparse_moe_decode_multi": {
                    "durations": [0.2] * 3 + [0.1] * 4, "seconds": 1.0},
                "jit_sparse_moe_prefill_batch": {"durations": [0.8],
                                                 "seconds": 0.8}},
                "ops": [["pallas:gqa_picked_attention:bf16_1_8192_4096", 0.25],
                        ["pallas:_paged_selected_attention:bf16_32_32_128", 0.5],
                        ["pallas:paged_index_scores:f32_2_32_8192", 0.1],
                        ["pallas:ragged-dot-swiglu:f32_320_2048", 0.1],
                        ["pallas:ragged-dot-none:bf16_16384_768", 0.1]]},
            "trace_window": (0.0, 1.0),
            "dispatched_steps": [64, 8, 8, 8, 4, 4, 4, 4],
            "admitted_lens": [8192.0]}


def test_new_readers_on_a_hand_made_run():
    from benchmarks import run as bench_run

    cell = configs.load_cell(CELL)
    run = _run()
    got = {k: v["value"] for k, v in
           bench_run.read_metrics(cell, "per_layer", run).items()}
    assert got["moe.experts_touched_share"] == pytest.approx(87.5)
    assert got["moe.expert_passes_per_touched"] == pytest.approx(1.0)
    assert got["engine.decode_step_ms.batch"] == pytest.approx(25.0)
    assert got["engine.prefill_share.batch"] == pytest.approx(40.0)
    assert got["sparse.attended_share.longctx"] == pytest.approx(
        100 * 786_432 / 3_600_000)
    assert got["kernel.decode_kv_read_amplification.longctx"] == pytest.approx(
        3_606_000 / 786_432)
    assert got["kernel.grouped_matmul_share"] == pytest.approx(10.0)
    # 40 steps in the trace (three 8-step and four 4-step blocks)
    least = count.least_seconds(run["cfg"], PEAKS, 32, 3_600_000.0, 786_432.0,
                                14.0, 16.0)
    assert got["kernel.sparse_moe_decode_roofline"] == pytest.approx(
        100 * 40 * least / 1.0)
    both = kernels_count.least_seconds(run["cfg"], PEAKS, 32, 3_600_000.0,
                                       786_432.0)
    assert got["kernel.sparse_decode_attention_roofline"] == pytest.approx(
        100 * 40 * both / 0.6)
    assert got["kernel.sparse_prefill_attention_roofline"] == pytest.approx(
        100 * picked_count.flops(run["cfg"], [8192]) / 197e12 / 0.25)
    assert got["kernel.sparse_moe_prefill_roofline"] == pytest.approx(
        100 * prefill_count.flops(run["cfg"], [8192.0]) / 197e12 / 0.8)
    for name in ("kernel.sparse_moe_decode_roofline",
                 "kernel.sparse_decode_attention_roofline",
                 "kernel.sparse_prefill_attention_roofline",
                 "kernel.sparse_moe_prefill_roofline"):
        assert 0 < got[name] < 100, name
    # a program without the counters or the kernels (the parent) reads as
    # nothing, and nothing raises
    bare = _run()
    for snap in bare["counters"].values():
        snap["stages"] = {}
    bare["trace"]["ops"] = []
    bare["dispatched_steps"] = []
    bare["admitted_lens"] = []
    left = bench_run.read_metrics(cell, "per_layer", bare)
    joined = {"engine.decode_step_ms.batch", "moe.experts_touched_share",
              "moe.load_imbalance", "kernel.grouped_matmul_share"}
    assert not {m for m in left if "sparse" in m or "longctx" in m or m in joined}


def test_the_new_cell_is_found_by_name_as_files_alone():
    manifest = configs.load_manifest()
    cell = configs.load_cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "longctx_closed"
    traffic, cf = cell["traffic_file"], cell["config_file"]
    assert configs.load_module("drivers", traffic["driver"]).run
    assert traffic["driver"] in cf["correct_limits"]
    assert configs.load_module("reference", cf["reference"]).forward
    slots = cf["engine"]["max_batch"]
    assert (slots, traffic["callers"], traffic["list_size"], traffic["stream"]) == (
        32, 40, 256, False)
    assert (traffic["caller_stagger_s"], traffic["lead_in_s"]) == (0.25, 20)
    assert traffic["prompt"] == {"dist": "lognormal", "median": 8192,
                                 "sigma": 0.5,
                                 "lengths": [4096, 8192, 12288, 14336]}
    assert traffic["output"] == {"dist": "uniform", "min": 256, "max": 1024}
    assert traffic["max_total"] == 15360 <= cf["engine"]["max_seq_len"] == 16384
    assert traffic["reference_check"] == [
        {"prompt_len": 8192, "max_tokens": 24},
        {"prompt_len": 1024, "max_tokens": 24}]
    e2e = {m["name"] for m in configs.cell_metrics(cell, "end_to_end")}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    layer = configs.cell_metrics(cell, "per_layer")
    assert {m["moves"] for m in layer} >= {"serve_tokens_per_s"}
    names = {m["name"] for m in layer}
    assert {"engine.decode_step_ms.batch", "engine.prefill_share.batch",
            "kernel.sparse_moe_decode_roofline",
            "kernel.sparse_decode_attention_roofline",
            "kernel.sparse_prefill_attention_roofline",
            "kernel.sparse_moe_prefill_roofline", "sparse.attended_share.longctx",
            "kernel.decode_kv_read_amplification.longctx",
            "kernel.grouped_matmul_share",
            "moe.experts_touched_share", "moe.load_imbalance",
            "moe.expert_passes_per_touched", "device.idle_share.batch",
            "engine.compiles_in_window.batch"} <= names
    for m in layer:
        spec = configs.load_json("layer_metrics", m["name"] + ".json")
        assert set(spec) == {"name", "reader", "args"}
        assert configs.load_module("readers", spec["reader"]).read
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == cf["reduced"] and set(cf["published"]) == set(cf["reduced"])
    assert entry["source"] == cf["source"]
    # every limit is judged on a name the replica reports
    assert set(cf["correct_limits"][traffic["driver"]]) <= {
        p + n for p in ("", "short.") for n in (
            "kv_rel_err.prefill", "kv_rel_err.decode", "near_row_err_p50.prefill",
            "far_row_err_p50.prefill", "far_row_err_p10.prefill",
            "row_err_p50.decode")}
    from ray_tpu.llm.sparse_moe import WAVE_LIMIT
    assert WAVE_LIMIT == (8, 16384) and "wave_limit" not in traffic


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_the_traffics_multiset_whatever_the_seed(seed, monkeypatch):
    """256 quantiles of lognormal(8192, 0.5) snapped to the four lengths are
    72 / 100 / 42 / 42 (mean 8,720; every prompt past topk): the multiset
    ``lib/traffic.py`` makes, which this driver cycles in ONE order whatever
    the seed, every aligned run of 8 holding the file's own mix."""
    from benchmarks.drivers import serve_sparse_moe as D
    from benchmarks.lib import traffic as T

    traffic = configs.load_cell(CELL)["traffic_file"]
    a, b = D.even_list(traffic), T.closed_list(traffic, seed)
    assert Counter(p for p, _ in a) == {4096: 72, 8192: 100, 12288: 42, 14336: 42}
    assert sum(p for p, _ in a) / 256 == 8720
    assert sorted(p for p, _ in a) == sorted(p for p, _ in b)
    assert sorted(o for _, o in a) == sorted(o for _, o in b)
    assert min(p for p, _ in a) > published().topk
    assert {sum(p >= 12288 for p, _ in a[k:k + 8]) for k in range(0, 256, 8)} <= {2, 3}
    assert max(p + o for p, o in a) <= traffic["max_total"]
    # the prefill programs a wave limit of (8, 16384) lets these lengths form
    waves = {(p, w) for p in (4096, 8192, 12288, 14336) for w in (1, 2, 4, 8)
             if w * p <= 16384}
    assert len(waves) == 7
    # under this driver's run the window gets that one list, and afterwards
    # the library is as it was
    monkeypatch.setattr(D, "setup", lambda cell, args, clock: {"traffic": traffic})
    monkeypatch.setattr(D, "window", lambda ctx, s, *rest: T.closed_list(
        ctx["traffic"], s))
    got = D.run({}, NS(seed=seed, seconds=1, trace=0, trace_seconds=1), None)
    assert got == a and T.closed_list(traffic, 1) != T.closed_list(traffic, 2)


def test_the_cell_rehearses_on_the_cpu_at_tiny_sizes(tmp_path):
    """The whole cell through ``run.py --allow-cpu``: deploy, warm-up, both
    checked requests against the reference, the closed loop, the readers."""
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
        assert ref[prefix + "kv_rel_err.prefill"] < 1e-5
        assert ref[prefix + "row_err_p50.decode"] < 1e-5
        assert ref[prefix + "token_logit_gap"] == 0.0
    assert ref["far_row_err_p50.prefill"] < 1e-5 and ref["repeats"]
    rehearsed = line["rehearsal"]
    assert rehearsed["cpu-rehearsal.engine.compiles_in_window.batch"] == 0
    assert 0 < rehearsed["cpu-rehearsal.sparse.attended_share.longctx"] < 100

"""What PR 27 added to the benchmark, on the CPU: the MLA + sparse-expert
reference and its fp8 control at the configuration's tiny size, the decode
count against a hand count, the new readers on a hand-made run, and the new
cell found by name as files alone."""
import jax
import jax.numpy as jnp
import pytest

from benchmarks.drivers.serve_mla_moe import mla_moe_config
from benchmarks.lib import configs
from benchmarks.lib import weights_mla_moe as W
from benchmarks.reference import mla_moe as R
from benchmarks.roofline import mla_moe_decode_multi as count

CELL = "kanana2_gen_closed"


def tiny():
    cf = configs.load_json("configs", "kanana-2-30b-a3b-instruct-2601.json")
    return mla_moe_config({**cf, **cf["tiny"]})


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("seed", [3, 2**31 + 7, 99])
def test_program_forward_agrees_and_lower_precision_does_not(seed):
    from ray_tpu.models.mla_moe import mla_moe_forward

    cfg = tiny()
    tokens = jax.random.randint(jax.random.PRNGKey(seed % 1000), (1, 48), 3,
                                cfg.vocab_size)
    params = W.make_params(W.seed_key(seed), cfg, 2)
    want = R.forward(seed, cfg, tokens, zero_col=2)
    assert rel(mla_moe_forward(params, tokens, cfg), want["logits"]) < 1e-5
    assert float(jnp.abs(want["logits"][..., 2]).max()) == 0.0
    assert want["chosen"].shape == (cfg.n_moe_layers, 48, cfg.n_experts_per_tok)
    errs, flips = {}, {}
    for mode in ("bfloat16", "fp8"):
        low = R.forward(seed, cfg, tokens, mode=mode, zero_col=2)
        # the first expert layer's rows: before any routing, so no flip blurs it
        errs[mode] = rel(low["rows"][cfg.first_dense_layers],
                         want["rows"][cfg.first_dense_layers])
        flips[mode] = float(jnp.mean(jnp.any(
            jnp.sort(low["chosen"], -1) != jnp.sort(want["chosen"], -1), -1)))
    assert errs["fp8"] > 2.5 * errs["bfloat16"] > 1e-4, errs
    assert flips["fp8"] > flips["bfloat16"], flips


def test_the_published_configuration_is_what_the_program_gets():
    cf = configs.load_json("configs", "kanana-2-30b-a3b-instruct-2601.json")
    cfg = mla_moe_config(cf)
    assert (cfg.d_model, cfg.n_heads, cfg.latent_width, cfg.qk_head_dim) == (
        2048, 32, 576, 192)
    assert (cfg.n_experts, cfg.n_experts_per_tok, cfg.d_expert,
            cfg.n_shared_experts, cfg.vocab_size) == (128, 6, 768, 2, 128256)
    assert cfg.held == (0, 128) and cfg.n_layers == 8 and cfg.n_moe_layers == 7
    with pytest.raises(ValueError, match="q_lora_rank"):
        mla_moe_config({**cf, "q_lora_rank": 1536})


def test_decode_count_against_a_hand_count():
    cfg = mla_moe_config(configs.load_json(
        "configs", "kanana-2-30b-a3b-instruct-2601.json"))
    attn = 2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048       # 26.35 M
    assert count.attn_params(cfg) == attn == 26_345_472
    assert count.expert_params(cfg) == 3 * 2048 * 768 == 4_718_592
    assert count.shared_params(cfg) == 2048 * 128 + 3 * 2048 * 1536  # 9.70 M
    fixed = (8 * attn + 3 * 2048 * 6144 + 7 * count.shared_params(cfg)
             + 2048 * 128256)
    assert count.fixed_params(cfg) == fixed
    # 100 experts touched a layer, 30,000 live tokens: about 8 GB a step
    got = count.bytes_per_step(cfg, 30_000, 100.0)
    assert got == (fixed + 7 * 100 * 4_718_592 + 30_000 * 8 * 576) * 2
    assert 7.9e9 < got < 8.2e9
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    assert abs(count.least_seconds(cfg, peaks, 32, 30_000, 100.0)
               - got / 819e9) < 1e-12                      # bound by bytes
    # touching all 128 costs more than touching 100; never counted by default
    assert count.bytes_per_step(cfg, 30_000, 128.0) > got


def _run(touched=700.0, slots=896.0, top=35.0, rows=1344.0, steps=12):
    cfg = mla_moe_config(configs.load_json(
        "configs", "kanana-2-30b-a3b-instruct-2601.json"))

    def snap(scale):
        return {"steps": steps * scale, "block_buckets": [4, 8, 16, 32, 64], "stages": {
            "rt_llm_moe_experts_touched_total": {"": {"sum": touched * steps * scale}},
            "rt_llm_moe_expert_slots_total": {"": {"sum": slots * steps * scale}},
            "rt_llm_moe_max_load_total": {"": {"sum": top * steps * scale}},
            "rt_llm_moe_assignments_total": {"": {"sum": rows * steps * scale}}}}

    return {"cfg": cfg, "engine": {"max_batch": 32},
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
            "counters": {"before": snap(1), "after": snap(2)},
            "trace": {"busy_s": 0.0, "window_s": 1.0, "ops": [], "programs": {
                "jit_mla_moe_decode_multi": {"durations": [0.2, 0.1],
                                             "seconds": 0.3}}},
            "trace_window": (0.0, 1.0),
            # the steps annotated on the trace's two decode dispatches
            "dispatched_steps": [8, 4],
            "recs_all": [{"first": 0.0, "last": 1.0, "tokens": 11,
                          "prompt_len": 1000}] * 30}


def test_new_readers_on_a_hand_made_run():
    from benchmarks import run as bench_run

    cell = configs.load_cell(CELL)
    run = _run()
    got = bench_run.read_metrics(cell, "per_layer", run)
    # 700 of 896 expert slots a step: 78.125 %, 100 of 128 a layer
    assert got["moe.experts_touched_share"]["value"] == pytest.approx(78.125)
    # largest 5 a layer (35 / 7) over the mean 1.5 (1344 / 896)
    assert got["moe.load_imbalance"]["value"] == pytest.approx(5 / 1.5)
    assert got["engine.decode_step_ms.batch"]["value"] == pytest.approx(25.0)
    live = 30 * (1001 + 10 * 0.5)
    least = count.least_seconds(run["cfg"], run["peaks"], 32, live, 100.0)
    assert got["kernel.mla_moe_decode_roofline"]["value"] == pytest.approx(
        100 * 12 * least / 0.3)  # an 8-step and a 4-step block
    assert got["kernel.mla_moe_decode_roofline"]["value"] < 100
    # a program without the counters (the parent) reads as nothing, no raise
    bare = _run()
    for snap in bare["counters"].values():
        snap["stages"] = {}
    got = bench_run.read_metrics(cell, "per_layer", bare)
    assert not {"moe.experts_touched_share", "moe.load_imbalance",
                "kernel.mla_moe_decode_roofline"} & set(got)


def test_the_new_cell_is_found_by_name_as_files_alone():
    manifest = configs.load_manifest()
    cell = configs.load_cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "gen_closed"
    traffic, cf = cell["traffic_file"], cell["config_file"]
    assert configs.load_module("drivers", traffic["driver"]).run
    assert traffic["driver"] in cf["correct_limits"]
    assert (traffic["callers"], traffic["list_size"], traffic["stream"]) == (40, 256, False)
    assert traffic["prompt"]["lengths"] == [512, 768, 1024, 1536]
    assert (traffic["output"]["min"], traffic["output"]["max"]) == (128, 512)
    assert cf["engine"]["max_batch"] == 32 and cf["reduced"] == [
        "num_hidden_layers", "max_position_embeddings"]
    e2e = {m["name"] for m in configs.cell_metrics(cell, "end_to_end")}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    layer = configs.cell_metrics(cell, "per_layer")
    assert {m["moves"] for m in layer} >= {"serve_tokens_per_s"}
    names = {m["name"] for m in layer}
    assert {"engine.decode_step_ms.batch", "kernel.mla_moe_decode_roofline",
            "moe.experts_touched_share", "moe.load_imbalance",
            "device.idle_share.batch", "engine.compiles_in_window.batch"} <= names
    for m in layer:
        spec = configs.load_json("layer_metrics", m["name"] + ".json")
        assert set(spec) == {"name", "reader", "args"}
        assert configs.load_module("readers", spec["reader"]).read
    # the catalog's numbers under their own names; only depth and positions cut
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == cf["reduced"] and set(cf["published"]) == set(cf["reduced"])
    # the work a window offers does not depend on the seed
    from benchmarks.lib import traffic as T
    a, b = T.closed_list(traffic, 1), T.closed_list(traffic, 2**31 + 5)
    assert sorted(p for p, _ in a) == sorted(p for p, _ in b)
    assert max(p + o for p, o in a) <= traffic["max_total"] == 2048

"""Each reader's arithmetic on a run written down by hand."""
from types import SimpleNamespace

import pytest

from benchmarks.lib import stats
from benchmarks.lib.configs import load_json, load_manifest, load_module

CFG = SimpleNamespace(d_model=4096, head_dim=128, n_heads=32, n_kv_heads=8,
                      d_ff=14336, vocab_size=32768, n_layers=13, dtype="bfloat16")
PEAKS = load_json("peaks.json")["TPU v5 lite"]


def read(name, run, folder="layer_metrics"):
    spec = load_json(folder, name + ".json")
    return load_module("readers", spec["reader"]).read(run, **spec["args"])


def rec(due, sent, first, last, tokens, prompt_len=256, replica_ttft=None):
    return {"due": due, "sent": sent, "first": first, "last": last,
            "tokens": tokens, "max_tokens": tokens, "prompt_len": prompt_len,
            "replica_ttft_s": replica_ttft, "sampled": True}


def test_client_times():
    recs = [rec(0.0, 0.001, 0.5, 0.5 + 0.04 * 9, 10, replica_ttft=0.4),
            rec(1.0, 1.003, 1.2, 1.2 + 0.05 * 19, 20, replica_ttft=0.15),
            rec(2.0, 2.002, 2.9, 2.9 + 0.06 * 4, 5, replica_ttft=0.7)]
    run = {"recs": recs}
    assert read("client.ttft_p50_ms", run) == pytest.approx(500.0)
    assert read("client.tpot_p50_ms", run) == pytest.approx(50.0)
    # (0.36 + 0.95 + 0.24) s over 9 + 19 + 4 gaps
    assert read("tpot_mean_ms", run, "end_to_end") == pytest.approx(1e3 * 1.55 / 32)
    assert read("client.send_lag_p95_ms", run) == pytest.approx(2.9, abs=1e-6)
    # client ttft from sent: 499, 197, 898 ms; less the replica's 400, 150, 700
    assert read("router.overhead_p50_ms", run) == pytest.approx(99.0)
    assert read("client.ttft_p50_ms", {"recs": []}) is None


def test_serve_tokens_per_s_counts_prompt_and_output():
    recs = [{"prompt_len": 1000, "tokens": 24}, {"prompt_len": 1200, "tokens": 40},
            {"prompt_len": 1300, "tokens": 16, "error": "x"}]
    assert read("serve_tokens_per_s", {"recs": recs, "seconds": 4.0},
                "end_to_end") == pytest.approx(566.0)


def test_train_rate_and_mfu():
    # a made-up 13-layer trainer at 16,384 tokens/s: the arithmetic only
    train = {"steps": 50, "tokens_per_step": 8192, "span_s": 25.0,
             "trace": {"steps": 10, "span_s": 5.0}}
    run = {"train": train, "cfg": CFG, "peaks": PEAKS, "traffic": {"seq_len": 4096}}
    assert read("train_tokens_per_s", run, "end_to_end") == pytest.approx(16384.0)
    per_token = 6 * (13 * 218_103_808 + 134_217_728) + 6 * 4096 * 4096 * 13
    assert read("train.mfu", run) == pytest.approx(
        100 * 16384.0 * per_token / 197e12)


def trace(programs, busy, window, ops=()):
    return {"busy_s": busy, "window_s": window, "chips": 1,
            "programs": {k: {"count": len(d), "seconds": sum(d), "durations": d}
                         for k, d in programs.items()},
            "ops": [list(o) for o in ops], "idle_gaps": []}


def test_decode_step_and_roofline():
    # 3 blocks: 8, 32 and 16 steps of 40 ms; the counter saw 48 of the 56
    t = trace({"jit_paged_decode_multi": [0.32, 1.28, 0.64]}, busy=2.3, window=2.5)
    live = [rec(0, 0, 0.0, 10.0, 101, prompt_len=500)]  # 500 -> 600 tokens held
    run = {"trace": t, "trace_window": (2.0, 4.0), "trace_span_s": 2.5,
           "counters": {"before": {"steps": 100},
                        "after": {"steps": 148, "block_buckets": [4, 8, 16, 32, 64]}},
           "recs_all": live,
           "cfg": CFG, "peaks": PEAKS, "engine": {"max_batch": 16}}
    assert read("engine.decode_step_ms", run) == pytest.approx(40.0)
    held = stats.live_kv_tokens(live, 2.0, 4.0)
    assert held == pytest.approx(531.0)  # prompt + 1 + 10 tokens/s * 3 s
    bytes_ = (13 * 218_103_808 + 134_217_728) * 2 + held * 13 * 2 * 8 * 128 * 2
    assert read("kernel.decode_roofline", run) == pytest.approx(
        100 * (bytes_ / 819e9) / 0.040)
    assert read("device.idle_share.chat", run) == pytest.approx(8.0)
    assert read("engine.decode_step_ms", {**run, "trace": None}) is None


def test_prefill_share_and_roofline():
    t = trace({"jit_paged_prefill_batch": [0.2, 0.3],
               "jit_paged_decode_multi": [0.4]}, busy=1.0, window=1.2)
    recs = [{"prompt_len": 1024, "sent": 0.0, "done": 1.0, "tokens": 16},
            {"prompt_len": 1536, "sent": 0.2, "done": 1.4, "tokens": 16},
            {"prompt_len": 1792, "sent": 5.0, "done": 6.0, "tokens": 16}]  # outside
    run = {"trace": t, "trace_window": (0.0, 1.0), "trace_span_s": 1.2,
           "recs_all": recs, "cfg": CFG, "peaks": PEAKS}
    assert read("engine.prefill_share.batch", run) == pytest.approx(50.0)
    flops = load_module("roofline", "paged_prefill_batch").flops(CFG, [1024, 1536])
    assert read("kernel.prefill_roofline", run) == pytest.approx(
        100 * flops / 197e12 / 0.5)
    # the two prompts of the span went in as two runs of the prefill program
    assert read("engine.prompts_per_prefill.batch", run) == pytest.approx(1.0)
    one_wave = {**run, "trace": trace({"jit_paged_prefill_batch": [0.5]}, 1.0, 1.2)}
    assert read("engine.prompts_per_prefill.batch", one_wave) == pytest.approx(2.0)


def test_op_share_and_missing_trace():
    t = trace({}, busy=2.0, window=2.0, ops=[("flash_fwd:bf16_1_32", 0.3),
                                            ("fusion:bf16_8", 1.0)])
    spec = load_json("layer_metrics", "kernel.flash_share.train.json")
    mod = load_module("readers", spec["reader"])
    assert mod.read({"trace": t}, patterns=["flash"]) == pytest.approx(15.0)
    assert mod.read({"trace": None}, patterns=["flash"]) is None


def test_every_metric_has_a_reader_that_takes_its_args():
    manifest = load_manifest()
    empty = {"recs": [], "recs_all": [], "trace": None, "train": None,
             "setup_s": 1.0, "compiles_in_window": 0}
    for group, folder in (("end_to_end", "end_to_end"), ("per_layer", "layer_metrics")):
        for m in manifest[group]:
            read(m["name"], empty, folder)  # nothing to read is not an error

"""Driver of the serving cells of the window-with-a-sink sparse-expert family:
a copy of ``drivers/serve_cohere2_moe.py`` (README_sink_moe.md says what
differs) — the loops, the window and the hooks are ``drivers/serve.py``'s;
what that file ties to the Llama family by name (the program's config, the
replica's weights and its reference) is brought here. ``correct_limits`` of a
configuration are read under this driver's name. The closed loop's list keeps
ONE order whatever the seed (``even_list``, that driver's), and the reference
is compared on EVERY request of the traffic file's ``reference_check`` list
(the worst reading of each name stands)."""
from __future__ import annotations

from unittest import mock

from benchmarks.drivers.serve import APP, DEPLOYMENT, say, window
from benchmarks.drivers.serve_cohere2_moe import even_list
from benchmarks.lib import traffic as T


def sink_moe_config(config_file: dict, **overrides):
    """The published keys of a configuration file as the program's
    ``SinkMoeConfig``; what the program cannot express is refused. The
    file's ``n_routed_experts`` and ``vocab_size`` are what is HELD here
    (``experts_held``, ``vocab_held``); the router's width is the published
    count, and the two layer patterns are cut to the file's depth."""
    from ray_tpu.models.sink_moe import SinkMoeConfig

    c = {**config_file, **overrides}
    fixed = {"model_type": "mimo_v2_flash", "hidden_act": "silu",
             "attention_bias": False, "tie_word_embeddings": False,
             "scoring_func": "sigmoid", "topk_method": "noaux_tc",
             "n_group": 1, "topk_group": 1, "n_shared_experts": None,
             "routed_scaling_factor": None,
             "swa_num_attention_heads": c["num_attention_heads"],
             "swa_head_dim": c["head_dim"], "swa_v_head_dim": c["v_head_dim"],
             "sliding_window_size": c["sliding_window"],
             "attention_chunk_size": c["sliding_window"]}
    for key, want in fixed.items():
        if c.get(key, want) != want:
            raise ValueError(f"models/sink_moe.py has no {key}={c[key]!r}")
    depth = c["num_hidden_layers"]
    held = tuple(c.get("experts_held") or (0, c["n_routed_experts"]))
    if held[1] - held[0] != c["n_routed_experts"]:
        raise ValueError("n_routed_experts is not the experts held here")
    published = c.get("published", {})
    vocab_held = c.get("vocab_held")
    return SinkMoeConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=depth,
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        swa_n_kv_heads=c["swa_num_key_value_heads"], head_dim=c["head_dim"],
        v_head_dim=c["v_head_dim"],
        layer_window=tuple(bool(x) for x in c["hybrid_layer_pattern"][:depth]),
        layer_moe=tuple(bool(x) for x in c["moe_layer_freq"][:depth]),
        sliding_window=c["sliding_window"],
        partial_rotary_factor=float(c["partial_rotary_factor"]),
        rope_theta=float(c["rope_theta"]),
        swa_rope_theta=float(c["swa_rope_theta"]),
        value_scale=float(c["attention_value_scale"]),
        sink_window=bool(c["add_swa_attention_sink_bias"]),
        sink_full=bool(c["add_full_attention_sink_bias"]),
        d_ff=c["intermediate_size"],
        n_experts=published.get("n_routed_experts", c["n_routed_experts"]),
        n_experts_per_tok=c["num_experts_per_tok"],
        d_expert=c["moe_intermediate_size"],
        norm_topk_prob=bool(c["norm_topk_prob"]),
        rms_norm_eps=float(c["layernorm_epsilon"]),
        max_seq_len=c["max_position_embeddings"], dtype=c["torch_dtype"],
        experts_held=held, vocab_held=tuple(vocab_held) if vocab_held else None)


def pads_of(lengths, page_size: int) -> list[int]:
    """The prefill pad buckets of prompts of these lengths, by the engine's
    own rule (a prompt pads to whole pages)."""
    return sorted({-(-int(n) // page_size) * page_size for n in lengths})


def deploy(cfg, engine_kw: dict, seed: int):
    from ray_tpu import serve as rt_serve
    from benchmarks.lib.replica_sink_moe import SinkMoeBenchServer, make_params_fn

    dep = rt_serve.deployment(SinkMoeBenchServer, name=DEPLOYMENT,
                              num_replicas=1, max_ongoing_requests=128,
                              ray_actor_options={"num_tpus": 1})
    app = dep.bind(cfg, None, make_params_fn(cfg, seed), **engine_kw)
    rt_serve.run(app, name=APP, timeout_s=1100)
    return rt_serve.get_deployment_handle(DEPLOYMENT, APP)


def worst_of(checks: list[dict]) -> dict:
    """One reading a name over several checked requests: the largest of the
    floats, all of the flags, the rest of the first."""
    out = dict(checks[0])
    for name, value in checks[0].items():
        if isinstance(value, bool):
            out[name] = all(c[name] for c in checks)
        elif isinstance(value, float):
            out[name] = max(c[name] for c in checks)
    return out


def setup(cell: dict, args, clock) -> dict:
    """``drivers/serve.py``'s set-up with this family's config and replica:
    deploy, check the device, warm every reachable program, compare with the
    plain reference on each checked request."""
    import ray_tpu

    cf, traffic = cell["config_file"], cell["traffic_file"]
    if args.allow_cpu:
        cf, traffic = {**cf, **cf["tiny"]}, {**traffic, **traffic["tiny"]}
    cfg = sink_moe_config(cf)
    engine_kw = dict(cf["engine"])

    handle = deploy(cfg, engine_kw, args.seed)
    clock.mark("deployed")
    device = ray_tpu.get(handle.bench_stats.remote(), timeout=300)["device"]
    if device["platform"] != "tpu" and not args.allow_cpu:
        raise RuntimeError(f"the replica runs on {device['platform']!r}: a CPU "
                           f"device is a failure, never a fallback")
    PS = engine_kw["page_size"]
    pads = pads_of(T.quantile_lengths(traffic["prompt"], 4096), PS)
    check_pads = pads_of((rc["prompt_len"] for rc in
                          traffic["reference_check"]), PS)
    warm = ray_tpu.get(handle.warm.remote(
        pads, traffic["warm_waves"], cfg.vocab_size,
        [p for p in check_pads if p not in pads]), timeout=1100)
    say(f"warm-up: {warm['programs']} programs in {warm['total_s']:.1f}s "
        f"(prefill waves {warm['prefill_s']:.1f}s), missing {warm['missing']}")
    if warm["missing"]:
        raise RuntimeError(f"warm-up did not reach {warm['missing']}")
    clock.mark("warmed")

    mode = getattr(args, "control_mode", None) or "float32"
    checks = [ray_tpu.get(handle.reference_check.remote(
        args.seed, cfg, rc["prompt_len"], rc["max_tokens"], mode), timeout=900)
        for rc in traffic["reference_check"]]
    for rc, got in zip(traffic["reference_check"], checks):
        say(f"reference {rc['prompt_len']} + {rc['max_tokens']}: " + ", ".join(
            f"{k} {v:.4g}" for k, v in got.items() if isinstance(v, float)))
    ref = worst_of(checks)
    limits = cf["correct_limits"][traffic["driver"]]
    for name, value in ref.items():  # run.py prints the judged and the *_err
        if name not in limits and "_err" not in name and isinstance(value, float):
            say(f"not judged (reported): {name} {value:.6g}")
    clock.mark("reference")
    return {"handle": handle, "cfg": cfg, "engine": engine_kw,
            "traffic": traffic, "reference": ref}


def run(cell: dict, args, clock) -> dict:
    ctx = setup(cell, args, clock)
    pairs = even_list(ctx["traffic"])
    # ``window`` asks ``lib/traffic.py`` for the list by the seed and takes no
    # other: the one name it looks up there is answered here for its call
    # (a ``pairs`` argument of ``window`` is D11's, README_cohere2_moe.md)
    with mock.patch.object(T, "closed_list", lambda traffic, seed: pairs):
        return window(ctx, args.seed, float(args.seconds), bool(args.trace),
                      float(args.trace_seconds), clock)

"""Driver of the serving cells of the MLA + sparse-expert family: the loops,
the window and the hooks are ``drivers/serve.py``'s; what that file ties to
the Llama family by name (the program's config, the replica's weights and its
reference) is brought here. ``correct_limits`` of a configuration are read
under this driver's name."""
from __future__ import annotations

from benchmarks.drivers.serve import APP, DEPLOYMENT, reachable_pads, say, window


def mla_moe_config(config_file: dict, **overrides):
    """The published keys of a configuration file as the program's
    ``MlaMoeConfig``; what the program cannot express is refused."""
    from ray_tpu.models.mla_moe import MlaMoeConfig

    c = {**config_file, **overrides}
    fixed = {"model_type": "deepseek_v3", "q_lora_rank": None, "n_group": 1,
             "topk_group": 1, "scoring_func": "sigmoid", "rope_scaling": None,
             "topk_method": "noaux_tc", "moe_layer_freq": 1,
             "attention_bias": False, "tie_word_embeddings": False,
             "hidden_act": "silu"}
    for key, want in fixed.items():
        if c.get(key, want) != want:
            raise ValueError(f"models/mla_moe.py has no {key}={c[key]!r}")
    if c["qk_head_dim"] != c["qk_nope_head_dim"] + c["qk_rope_head_dim"]:
        raise ValueError("qk_head_dim is not nope + rope")
    held = c.get("experts_held")
    return MlaMoeConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        kv_lora_rank=c["kv_lora_rank"], qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        d_ff=c["intermediate_size"], first_dense_layers=c["first_k_dense_replace"],
        n_experts=c["n_routed_experts"], n_experts_per_tok=c["num_experts_per_tok"],
        d_expert=c["moe_intermediate_size"], n_shared_experts=c["n_shared_experts"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        norm_topk_prob=bool(c["norm_topk_prob"]),
        max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), dtype=c["torch_dtype"],
        experts_held=tuple(held) if held else None)


def deploy(cfg, engine_kw: dict, seed: int):
    from ray_tpu import serve as rt_serve
    from benchmarks.lib.replica_mla_moe import MlaMoeBenchServer, make_params_fn

    dep = rt_serve.deployment(MlaMoeBenchServer, name=DEPLOYMENT, num_replicas=1,
                              max_ongoing_requests=64,
                              ray_actor_options={"num_tpus": 1})
    app = dep.bind(cfg, None, make_params_fn(cfg, seed, engine_kw.get("eos_id")),
                   **engine_kw)
    rt_serve.run(app, name=APP, timeout_s=1100)
    return rt_serve.get_deployment_handle(DEPLOYMENT, APP)


def setup(cell: dict, args, clock) -> dict:
    """``drivers/serve.py``'s set-up with this family's config and replica:
    deploy, check the device, warm every reachable program, compare with the
    plain reference."""
    import ray_tpu

    cf, traffic = cell["config_file"], cell["traffic_file"]
    if args.allow_cpu:
        cf, traffic = {**cf, **cf["tiny"]}, {**traffic, **traffic["tiny"]}
    cfg = mla_moe_config(cf)
    engine_kw = dict(cf["engine"])

    handle = deploy(cfg, engine_kw, args.seed)
    clock.mark("deployed")
    device = ray_tpu.get(handle.bench_stats.remote(), timeout=300)["device"]
    if device["platform"] != "tpu" and not args.allow_cpu:
        raise RuntimeError(f"the replica runs on {device['platform']!r}: a CPU "
                           f"device is a failure, never a fallback")
    pads = reachable_pads(traffic, engine_kw["page_size"])
    warm = ray_tpu.get(handle.warm.remote(pads, traffic["warm_waves"],
                                          cfg.vocab_size), timeout=1100)
    say(f"warm-up: {warm['programs']} programs in {warm['total_s']:.1f}s "
        f"(prefill waves {warm['prefill_s']:.1f}s), missing {warm['missing']}")
    if warm["missing"]:
        raise RuntimeError(f"warm-up did not reach {warm['missing']}")
    clock.mark("warmed")

    rc = traffic["reference_check"]
    ref = ray_tpu.get(handle.reference_check.remote(
        args.seed, cfg, rc["prompt_len"], rc["max_tokens"],
        engine_kw.get("eos_id"), getattr(args, "control_mode", None) or "float32"),
        timeout=600)
    limits = cf["correct_limits"][traffic["driver"]]
    for name, value in ref.items():  # run.py prints the judged and the *_err
        if name not in limits and "_err" not in name and isinstance(value, float):
            say(f"not judged (reported): {name} {value:.6g}")
    clock.mark("reference")
    return {"handle": handle, "cfg": cfg, "engine": engine_kw,
            "traffic": traffic, "reference": ref}


def run(cell: dict, args, clock) -> dict:
    ctx = setup(cell, args, clock)
    return window(ctx, args.seed, float(args.seconds), bool(args.trace),
                  float(args.trace_seconds), clock)


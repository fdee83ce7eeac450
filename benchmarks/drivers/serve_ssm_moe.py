"""Driver of the serving cells of the state-space + attention + ungated-expert
family: the loops, the window and the hooks are ``drivers/serve.py``'s; what
that file ties to the Llama family by name (the program's config, the
replica's weights and its reference) is brought here, as
``drivers/serve_sparse_moe.py`` brings that family's. ``correct_limits`` of a
configuration are read under this driver's name. As there, the closed loop's
list keeps ONE order whatever the seed (``even_list``) and the traffic file's
``reference_check`` is a LIST of requests — one of whole chunks and pages, one
that fills neither a page, a chunk nor a pad — whose readings are reported
side by side, the later ones under a prefix. The deployment takes as many
calls at once as the file has callers: 136 on 128 slots, so that 8 wait in
the engine's queue and not in the replica's."""
from __future__ import annotations

from unittest import mock

from benchmarks.drivers.serve import APP, DEPLOYMENT, say, window
from benchmarks.drivers.serve_cohere2_moe import even_list
from benchmarks.drivers.serve_sparse_moe import reachable_pads
from benchmarks.lib import traffic as T

# the checked requests' names, in the traffic file's order
CHECKS = ("", "short.")


def ssm_moe_config(config_file: dict, **overrides):
    """The published keys of a configuration file as the program's
    ``SsmMoeConfig``; what the program cannot express is refused. The file's
    ``n_routed_experts`` and ``vocab_size`` are what is HELD here
    (``experts_held``, ``vocab_held``); the router's width is the published
    count, and ``hybrid_override_pattern`` is as long as the file's depth."""
    from ray_tpu.models.ssm_moe import SsmMoeConfig

    c = {**config_file, **overrides}
    fixed = {"model_type": "nemotron_h", "mamba_hidden_act": "silu",
             "mlp_hidden_act": "relu2", "attention_bias": False,
             "mamba_proj_bias": False, "mlp_bias": False, "use_bias": False,
             "use_conv_bias": True, "tie_word_embeddings": False,
             "residual_in_fp32": False, "n_group": 1, "topk_group": 1,
             "sliding_window": None}
    for key, want in fixed.items():
        if c.get(key, want) != want:
            raise ValueError(f"models/ssm_moe.py has no {key}={c[key]!r}")
    pattern = c["hybrid_override_pattern"]
    if len(pattern) != c["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern is not num_hidden_layers long")
    if c["norm_eps"] != c["layer_norm_epsilon"]:
        raise ValueError("models/ssm_moe.py has one eps for every norm")
    held = tuple(c.get("experts_held") or (0, c["n_routed_experts"]))
    if held[1] - held[0] != c["n_routed_experts"]:
        raise ValueError("n_routed_experts is not the experts held here")
    published, vocab_held = c.get("published", {}), c.get("vocab_held")
    return SsmMoeConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], pattern=pattern,
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], mamba_heads=c["mamba_num_heads"],
        mamba_head_dim=c["mamba_head_dim"], n_groups=c["n_groups"],
        ssm_state=c["ssm_state_size"], conv_kernel=c["conv_kernel"],
        chunk_size=c["chunk_size"],
        n_experts=published.get("n_routed_experts", c["n_routed_experts"]),
        n_experts_per_tok=c["num_experts_per_tok"],
        d_expert=c["moe_intermediate_size"],
        n_shared_experts=c["n_shared_experts"],
        d_shared=c["moe_shared_expert_intermediate_size"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        norm_topk_prob=bool(c["norm_topk_prob"]),
        rms_norm_eps=float(c["layer_norm_epsilon"]),
        max_seq_len=c["max_position_embeddings"], dtype=c["torch_dtype"],
        experts_held=held, vocab_held=tuple(vocab_held) if vocab_held else None)


def deploy(cfg, engine_kw: dict, seed: int, callers: int):
    from ray_tpu import serve as rt_serve
    from benchmarks.lib.replica_ssm_moe import SsmMoeBenchServer, make_params_fn

    dep = rt_serve.deployment(SsmMoeBenchServer, name=DEPLOYMENT,
                              num_replicas=1, max_ongoing_requests=2 * callers,
                              ray_actor_options={"num_tpus": 1})
    app = dep.bind(cfg, None, make_params_fn(cfg, seed), **engine_kw)
    rt_serve.run(app, name=APP, timeout_s=1100)
    return rt_serve.get_deployment_handle(DEPLOYMENT, APP)


def setup(cell: dict, args, clock) -> dict:
    """``drivers/serve_sparse_moe.py``'s set-up with this family's config and
    replica: deploy, check the device, warm every reachable program, compare
    each checked request with the plain reference."""
    import ray_tpu

    cf, traffic = cell["config_file"], cell["traffic_file"]
    if args.allow_cpu:
        cf, traffic = {**cf, **cf["tiny"]}, {**traffic, **traffic["tiny"]}
    cfg = ssm_moe_config(cf)
    engine_kw = dict(cf["engine"])

    handle = deploy(cfg, engine_kw, args.seed, int(traffic["callers"]))
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

    ref, repeats = {}, True
    mode = getattr(args, "control_mode", None) or "float32"
    for i, (prefix, rc) in enumerate(zip(CHECKS, traffic["reference_check"])):
        one = ray_tpu.get(handle.reference_check.remote(
            args.seed, cfg, rc["prompt_len"], rc["max_tokens"], mode, None, i),
            timeout=900)
        repeats &= bool(one.pop("repeats"))
        ref.update({prefix + k: v for k, v in one.items()})
    ref["repeats"] = repeats
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
    # as ``drivers/serve_cohere2_moe.py`` ``run``: ``window`` asks
    # ``lib/traffic.py`` for the list by the seed and takes no other
    with mock.patch.object(T, "closed_list", lambda traffic, seed: pairs):
        return window(ctx, args.seed, float(args.seconds), bool(args.trace),
                      float(args.trace_seconds), clock)

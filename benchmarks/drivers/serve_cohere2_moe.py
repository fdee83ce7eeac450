"""Driver of the serving cells of the Cohere2 sparse-expert family: the loops,
the window and the hooks are ``drivers/serve.py``'s; what that file ties to
the Llama family by name (the program's config, the replica's weights and its
reference) is brought here, as ``drivers/serve_mla_moe.py`` brings the MLA
family's. ``correct_limits`` of a configuration are read under this driver's
name. One thing more differs: the closed loop's list keeps ONE order whatever
the seed (``even_list``), because a window reaches only the list's first two
fifths and a seeded order gave each seed another amount of work."""
from __future__ import annotations

from unittest import mock

from benchmarks.drivers.serve import APP, DEPLOYMENT, reachable_pads, say, window
from benchmarks.lib import traffic as T


def cohere2_moe_config(config_file: dict, **overrides):
    """The published keys of a configuration file as the program's
    ``Cohere2MoeConfig``; what the program cannot express is refused. The
    file's ``num_experts`` and ``vocab_size`` are what is HELD here
    (``experts_held``, ``vocab_held``); the router's width is the published
    count, and ``layer_types`` is cut to the file's depth."""
    from ray_tpu.models.cohere2_moe import Cohere2MoeConfig

    c = {**config_file, **overrides}
    fixed = {"model_type": "cohere2_moe", "expert_selection_fn": "sigmoid",
             "first_k_dense_replace": 0, "hidden_act": "silu",
             "attention_bias": False, "use_qk_norm": False,
             "use_parallel_block": True, "use_gated_activation": True,
             "tie_word_embeddings": True, "rotary_pct": 1,
             "position_embedding_type": "rope_gptj",
             "shared_expert_combination_strategy": "average",
             "order_of_interleaved_layers": "local_attn_first"}
    for key, want in fixed.items():
        if c.get(key, want) != want:
            raise ValueError(f"models/cohere2_moe.py has no {key}={c[key]!r}")
    depth = c["num_hidden_layers"]
    held = tuple(c.get("experts_held") or (0, c["num_experts"]))
    if held[1] - held[0] != c["num_experts"]:
        raise ValueError("num_experts is not the experts held here")
    published = c.get("published", {})
    vocab_held = c.get("vocab_held")
    return Cohere2MoeConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=depth,
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], layer_types=tuple(c["layer_types"][:depth]),
        sliding_window=c["sliding_window"],
        n_experts=published.get("num_experts", c["num_experts"]),
        n_experts_per_tok=c["num_experts_per_tok"],
        d_expert=c["intermediate_size"],
        n_shared_experts=c["num_shared_experts"],
        norm_topk_prob=bool(c["norm_topk_prob"]),
        logit_scale=float(c["logit_scale"]),
        layer_norm_eps=float(c["layer_norm_eps"]),
        max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), dtype=c["torch_dtype"],
        experts_held=held, vocab_held=tuple(vocab_held) if vocab_held else None)


def even_list(traffic: dict) -> list[tuple[int, int]]:
    """The closed loop's list in an order that the file alone fixes: the same
    multiset of lengths as ``lib/traffic.py``'s ``closed_list`` (the
    ``list_size`` quantiles of each distribution), entry ``i`` taking the
    prompt at quantile ``bit-reverse(i)`` (the van der Corput order) and the
    output at quantile ``i * stride`` (an odd stride near ``n`` over the
    golden ratio, so outputs spread evenly too and meet every prompt length).
    Every aligned run of 8 entries then holds one prompt of each eighth of
    the distribution, so any stretch of the list a window reaches offers the
    file's own mix, and every seed the same requests in the same order:
    ``--seed`` chooses the token ids and the weights."""
    n = int(traffic["list_size"])
    bits = n.bit_length() - 1
    if n != 1 << bits:
        raise ValueError(f"list_size {n} is not a power of two")
    prompts = T.quantile_lengths(traffic["prompt"], n)
    outputs = T.quantile_lengths(traffic["output"], n)
    if max(prompts) + max(outputs) > traffic["max_total"]:
        raise ValueError("the traffic file's maxima exceed its max_total")
    stride = round(n * 0.618) | 1
    return [(prompts[int(f"{i:0{bits}b}"[::-1], 2)], outputs[i * stride % n])
            for i in range(n)]


def deploy(cfg, engine_kw: dict, seed: int):
    from ray_tpu import serve as rt_serve
    from benchmarks.lib.replica_cohere2_moe import (
        Cohere2MoeBenchServer, make_params_fn)

    dep = rt_serve.deployment(Cohere2MoeBenchServer, name=DEPLOYMENT,
                              num_replicas=1, max_ongoing_requests=128,
                              ray_actor_options={"num_tpus": 1})
    app = dep.bind(cfg, None, make_params_fn(cfg, seed), **engine_kw)
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
    cfg = cohere2_moe_config(cf)
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
        getattr(args, "control_mode", None) or "float32"), timeout=900)
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

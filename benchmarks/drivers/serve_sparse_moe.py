"""Driver of the serving cells of the learned-sparse-attention expert family:
the loops, the window and the hooks are ``drivers/serve.py``'s; what that file
ties to the Llama family by name (the program's config, the replica's weights
and its reference) is brought here, as ``drivers/serve_cohere2_moe.py`` brings
that family's. ``correct_limits`` of a configuration are read under this
driver's name. Two things more: the closed loop's list keeps ONE order
whatever the seed (that driver's ``even_list``, for that driver's reason: a
window reaches the list's first third, and each reply is 1 % of its tokens),
and the traffic file's ``reference_check`` is a LIST of requests — one past
``topk`` and one under it — whose readings are reported side by side, the
later ones under a prefix."""
from __future__ import annotations

from unittest import mock

from benchmarks.drivers.serve import APP, DEPLOYMENT, say, window
from benchmarks.drivers.serve_cohere2_moe import even_list
from benchmarks.lib import traffic as T

# the checked requests' names, in the traffic file's order
CHECKS = ("", "short.")


def sparse_moe_config(config_file: dict, **overrides):
    """The published keys of a configuration file as the program's
    ``SparseMoeConfig``; what the program cannot express is refused. The
    file's ``num_experts`` and ``vocab_size`` are what is HELD here
    (``experts_held``, ``vocab_held``); the router's width is the published
    count (``num_local_experts``)."""
    from ray_tpu.models.sparse_moe import SparseMoeConfig

    c = {**config_file, **overrides}
    fixed = {"model_type": "KeyeVL2", "hidden_act": "silu",
             "attention_bias": False, "tie_word_embeddings": False,
             "decoder_sparse_step": 1, "mlp_only_layers": [],
             "use_sliding_window": False, "sliding_window": None}
    for key, want in fixed.items():
        if c.get(key, want) != want:
            raise ValueError(f"models/sparse_moe.py has no {key}={c[key]!r}")
    held = tuple(c.get("experts_held") or (0, c["num_experts"]))
    if held[1] - held[0] != c["num_experts"]:
        raise ValueError("num_experts is not the experts held here")
    sa, vocab_held = c["sa_config"], c.get("vocab_held")
    return SparseMoeConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        indexer_heads=sa["indexer_num_heads"],
        indexer_head_dim=sa["indexer_head_dim"],
        indexer_kv_heads=sa["indexer_num_kv_heads"], topk=sa["topk"],
        q_chunk=sa["q_chunk_size"], kv_chunk=sa["kv_chunk_size"],
        n_experts=c["num_local_experts"],
        n_experts_per_tok=c["num_experts_per_tok"],
        d_expert=c["moe_intermediate_size"],
        norm_topk_prob=bool(c["norm_topk_prob"]),
        rms_norm_eps=float(c["rms_norm_eps"]),
        max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), dtype=c["torch_dtype"],
        experts_held=held, vocab_held=tuple(vocab_held) if vocab_held else None)


def reachable_pads(traffic: dict, page_size: int) -> list[int]:
    """``drivers/serve.py``'s, over the traffic's prompt lengths alone: the
    checked requests' programs compile in set-up, where they run."""
    lengths = set(T.quantile_lengths(traffic["prompt"], 4096))
    return sorted({-(-n // page_size) * page_size for n in lengths})


def deploy(cfg, engine_kw: dict, seed: int):
    from ray_tpu import serve as rt_serve
    from benchmarks.lib.replica_sparse_moe import (
        SparseMoeBenchServer, make_params_fn)

    dep = rt_serve.deployment(SparseMoeBenchServer, name=DEPLOYMENT,
                              num_replicas=1, max_ongoing_requests=128,
                              ray_actor_options={"num_tpus": 1})
    app = dep.bind(cfg, None, make_params_fn(cfg, seed), **engine_kw)
    rt_serve.run(app, name=APP, timeout_s=1100)
    return rt_serve.get_deployment_handle(DEPLOYMENT, APP)


def setup(cell: dict, args, clock) -> dict:
    """``drivers/serve.py``'s set-up with this family's config and replica:
    deploy, check the device, warm every reachable program, compare each
    checked request with the plain reference."""
    import ray_tpu

    cf, traffic = cell["config_file"], cell["traffic_file"]
    if args.allow_cpu:
        cf, traffic = {**cf, **cf["tiny"]}, {**traffic, **traffic["tiny"]}
    cfg = sparse_moe_config(cf)
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

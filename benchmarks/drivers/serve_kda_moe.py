"""Driver of the serving cells of the delta-rule + latent-attention +
group-routed expert family: the loops, the window and the hooks are
``drivers/serve.py``'s; what that file ties to the Llama family by name (the
program's config, the replica's weights and its reference) is brought here,
as ``drivers/serve_ssm_moe.py`` brings that family's (a copy of it:
README_kda_moe.md). ``correct_limits`` of a
configuration are read under this driver's name. As there, the closed loop's
list keeps ONE order whatever the seed (``even_list``) and the traffic file's
``reference_check`` is a LIST of requests — one of whole chunks and pages, one
that fills neither a page, a chunk nor a pad — whose readings are reported
side by side, the later ones under a prefix. The deployment takes as many
calls at once as the file has callers: 104 on 96 slots, so that 8 wait in
the engine's queue and not in the replica's."""
from __future__ import annotations

import os
from unittest import mock

from benchmarks.drivers.serve import APP, DEPLOYMENT, say, window
from benchmarks.drivers.serve_cohere2_moe import even_list
from benchmarks.drivers.serve_sparse_moe import reachable_pads
from benchmarks.lib import traffic as T

# the checked requests' names, in the traffic file's order
CHECKS = ("", "short.")

# a tree from before the family (the parent of the PR that brought it, under
# this benchmark's files) fails here, at once and before any runtime starts;
# by the file, not by an import: this process stays off jax
if not os.path.exists(os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "ray_tpu", "models", "kda_moe.py")):
    raise SystemExit("this tree's program has no kda_moe family "
                     "(ray_tpu/models/kda_moe.py): it cannot run this cell")


def kda_moe_config(config_file: dict, **overrides):
    """The published keys of a configuration file as the program's
    ``KdaMoeConfig``; what the program cannot express is refused. The file's
    ``num_experts`` and ``vocab_size`` are what is HELD here (``experts_held``,
    ``vocab_held``); the router's width is the published count with its
    ``n_group`` groups, and the layers run are the first
    ``num_hidden_layers`` published ones."""
    from ray_tpu.models.kda_moe import KdaMoeConfig

    c = {**config_file, **overrides}
    fixed = {"q_lora_rank": None, "use_mla_nope": False,
             "score_function": "sigmoid", "moe_router_enable_expert_bias": True,
             "use_qk_norm": True, "linear_silu": True,
             "gated_attention_proj_granularity_type": "head_wise",
             "no_kda_lora": True, "use_kda_lora": False, "kda_safe_gate": True,
             "use_nGPT": False, "scale_router_input": False,
             "value_norm": False, "up_proj_norm": False, "group_norm_size": 1,
             "num_kv_heads_for_linear_attn": 0, "mtp_use_kda": False}
    for key, want in fixed.items():
        if c.get(key, want) != want:
            raise ValueError(f"models/kda_moe.py has no {key}={c[key]!r}")
    if c["rotary_dim"] != c["qk_rope_head_dim"] or (
            c["partial_rotary_factor"] * c["head_dim"] != c["rotary_dim"]):
        raise ValueError("the rotated lanes are the MLA layers' qk_rope_head_dim")
    if c["num_key_value_heads"] != c["num_attention_heads"]:
        raise ValueError("models/kda_moe.py has one head count")
    depth = c["num_hidden_layers"]
    for name in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if any(c.get(name, [])[:depth]):
            raise ValueError(f"models/kda_moe.py has no clamped SwiGLU "
                             f"({name} is not 0 in the first {depth} layers)")
    held = tuple(c.get("experts_held") or (0, c["num_experts"]))
    if held[1] - held[0] != c["num_experts"]:
        raise ValueError("num_experts is not the experts held here")
    published, vocab_held = c.get("published", {}), c.get("vocab_held")
    return KdaMoeConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=depth,
        layer_group_size=c["layer_group_size"],
        n_heads=c["num_attention_heads"], head_dim=c["head_dim"],
        conv_kernel=c["short_conv_kernel_size"],
        kda_lower_bound=float(c["kda_lower_bound"]),
        chunk_size=c["scan"]["chunk"], sub_chunk=c["scan"]["sub_block"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        d_ff=c["intermediate_size"],
        first_dense_layers=c["first_k_dense_replace"],
        n_experts=published.get("num_experts", c["num_experts"]),
        n_experts_per_tok=c["num_experts_per_tok"], n_group=c["n_group"],
        topk_group=c["topk_group"], d_expert=c["moe_intermediate_size"],
        d_shared=c["moe_shared_expert_intermediate_size"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        norm_topk_prob=bool(c["norm_topk_prob"]),
        rms_norm_eps=float(c["rms_norm_eps"]),
        max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), dtype=c["torch_dtype"],
        experts_held=held, vocab_held=tuple(vocab_held) if vocab_held else None)


def deploy(cfg, engine_kw: dict, seed: int, callers: int):
    from ray_tpu import serve as rt_serve
    from benchmarks.lib.replica_kda_moe import KdaMoeBenchServer, make_params_fn

    dep = rt_serve.deployment(KdaMoeBenchServer, name=DEPLOYMENT,
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
    cfg = kda_moe_config(cf)
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

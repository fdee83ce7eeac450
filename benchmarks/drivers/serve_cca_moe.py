"""Driver of the serving cells of the compressed-latent convolved attention +
top-1 expert family: the loops, the window and the hooks are
``drivers/serve.py``'s; what that file ties to the Llama family by name (the
program's config, the replica's weights and its reference) is brought here,
as ``drivers/serve_kda_moe.py`` brings that family's (a copy of it:
README_cca_moe.md). ``correct_limits`` of a configuration are read under this
driver's name. As there, the closed loop's list keeps ONE order whatever the
seed (``even_list``) and the traffic file's ``reference_check`` is a LIST of
requests — one of whole pages that opens another while decoding, one that
fills neither a page nor a pad — whose readings are reported side by side,
the later ones under a prefix. The deployment takes as many calls at once as
the file has callers: 88 on 80 slots, so that 8 wait in the engine's queue
and not in the replica's."""
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
            os.path.abspath(__file__)))), "ray_tpu", "models", "cca_moe.py")):
    raise SystemExit("this tree's program has no cca_moe family "
                     "(ray_tpu/models/cca_moe.py): it cannot run this cell")


def cca_moe_config(config_file: dict, **overrides):
    """The published keys of a configuration file as the program's
    ``CcaMoeConfig``; what the program cannot express is refused by key. The
    layers run are the first ``num_hidden_layers`` entries of
    ``layer_types``, which the file keeps whole."""
    from ray_tpu.models.cca_moe import CcaMoeConfig

    c = {**config_file, **overrides}
    fixed = {"model_type": "zaya", "attention_bias": False,
             "lm_head_bias": False, "hidden_act": "silu", "cca_time0": 2,
             "cca_time1": 2, "num_experts_per_tok": 1,
             "tie_word_embeddings": True, "sliding_window": None}
    for key, want in fixed.items():
        if c.get(key, want) != want:
            raise ValueError(f"models/cca_moe.py has no {key}={c[key]!r}")
    depth = c["num_hidden_layers"]
    kinds = c["layer_types"][:depth]
    if len(kinds) != depth or set(kinds) != {"hybrid"}:
        raise ValueError("models/cca_moe.py has hybrid layers alone (a CCA "
                         f"sublayer, then an expert sublayer), not {set(kinds)}")
    rope = c["rope_parameters"]["hybrid"]
    if rope["partial_rotary_factor"] != c["partial_rotary_factor"] or (
            rope.get("rope_type", "default") != "default"):
        raise ValueError("one partial_rotary_factor, the default rope")
    held = tuple(c.get("experts_held") or (0, c["num_experts"]))
    return CcaMoeConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=depth,
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"],
        rotary_dim=int(c["head_dim"] * c["partial_rotary_factor"]),
        rope_theta=float(rope["rope_theta"]),
        router_hidden=c["router_hidden_size"], n_experts=c["num_experts"],
        d_expert=c["moe_intermediate_size"],
        rms_norm_eps=float(c["rms_norm_eps"]),
        max_seq_len=c["max_position_embeddings"], dtype=c["torch_dtype"],
        experts_held=held)


def deploy(cfg, engine_kw: dict, seed: int, callers: int):
    from ray_tpu import serve as rt_serve
    from benchmarks.lib.replica_cca_moe import CcaMoeBenchServer, make_params_fn

    dep = rt_serve.deployment(CcaMoeBenchServer, name=DEPLOYMENT,
                              num_replicas=1, max_ongoing_requests=2 * callers,
                              ray_actor_options={"num_tpus": 1})
    app = dep.bind(cfg, None, make_params_fn(cfg, seed, engine_kw.get("eos_id")),
                   **engine_kw)
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
    cfg = cca_moe_config(cf)
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

"""Driver of the serving cells of the looped family: a copy of
``drivers/serve_sink_moe.py`` (README_looped.md says what differs) — the
loops, the window and the hooks are ``drivers/serve.py``'s; what that file
ties to the Llama family by name (the program's config, the replica's weights
and its reference) is brought here. ``correct_limits`` of a configuration are
read under this driver's name. The closed loop's list keeps ONE order
whatever the seed (``even_list``), and the reference is compared on EVERY
request of the traffic file's ``reference_check`` list (the worse reading of
each name stands). The deployment takes as many calls at once as the file has
callers: 32 on 24 slots, so that those the pages do not admit wait in the
engine's queue and not in the replica's."""
from __future__ import annotations

import os
from unittest import mock

from benchmarks.drivers.serve import APP, DEPLOYMENT, say, window
from benchmarks.drivers.serve_cohere2_moe import even_list
from benchmarks.drivers.serve_sink_moe import pads_of, worst_of
from benchmarks.lib import traffic as T

# a tree from before the family (the parent of the PR that brought it, under
# this benchmark's files) fails here, at once and before any runtime starts;
# by the file, not by an import: this process stays off jax
if not os.path.exists(os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "ray_tpu", "models", "looped.py")):
    raise SystemExit("this tree's program has no looped family "
                     "(ray_tpu/models/looped.py): it cannot run this cell")


def looped_config(config_file: dict, **overrides):
    """The published keys of a configuration file as the program's
    ``LoopedConfig``; what the program cannot express is refused by key."""
    from ray_tpu.models.looped import LoopedConfig

    c = {**config_file, **overrides}
    fixed = {"model_type": "ouro", "hidden_act": "silu", "rope_scaling": None,
             "sliding_window": None, "use_sliding_window": False,
             "tie_word_embeddings": False}
    for key, want in fixed.items():
        if c.get(key, want) != want:
            raise ValueError(f"models/looped.py has no {key}={c[key]!r}")
    depth = c["num_hidden_layers"]
    if c["layer_types"] != ["full_attention"] * depth or (
            c["max_window_layers"] != depth):
        raise ValueError("models/looped.py has full-attention layers alone")
    return LoopedConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=depth,
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], d_ff=c["intermediate_size"],
        n_passes=c["total_ut_steps"],
        exit_threshold=float(c["early_exit_threshold"]),
        rope_theta=float(c["rope_theta"]),
        rms_norm_eps=float(c["rms_norm_eps"]),
        max_seq_len=c["max_position_embeddings"], dtype=c["torch_dtype"])


def deploy(cfg, engine_kw: dict, seed: int, callers: int):
    from ray_tpu import serve as rt_serve
    from benchmarks.lib.replica_looped import LoopedBenchServer, make_params_fn

    dep = rt_serve.deployment(LoopedBenchServer, name=DEPLOYMENT,
                              num_replicas=1, max_ongoing_requests=2 * callers,
                              ray_actor_options={"num_tpus": 1})
    app = dep.bind(cfg, None, make_params_fn(cfg, seed, engine_kw.get("eos_id")),
                   **engine_kw)
    rt_serve.run(app, name=APP, timeout_s=1100)
    return rt_serve.get_deployment_handle(DEPLOYMENT, APP)


def setup(cell: dict, args, clock) -> dict:
    """``drivers/serve_sink_moe.py``'s set-up with this family's config and
    replica: deploy, check the device, warm every reachable program, compare
    with the plain reference on each checked request."""
    import ray_tpu

    cf, traffic = cell["config_file"], cell["traffic_file"]
    if args.allow_cpu:
        cf, traffic = {**cf, **cf["tiny"]}, {**traffic, **traffic["tiny"]}
    cfg = looped_config(cf)
    engine_kw = dict(cf["engine"])

    handle = deploy(cfg, engine_kw, args.seed, int(traffic["callers"]))
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
        args.seed, cfg, rc["prompt_len"], rc["max_tokens"], mode, None, i),
        timeout=900) for i, rc in enumerate(traffic["reference_check"])]
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
    # as ``drivers/serve_cohere2_moe.py`` ``run``: ``window`` asks
    # ``lib/traffic.py`` for the list by the seed and takes no other
    # the profiler's span is the traffic file's where that is shorter: a step
    # of this family is 192 layer applications, some 4,300 a second, and the
    # replica has ``seconds`` + 300 s to stop the profiler and reduce what it
    # wrote (README_looped.md, "The traced span")
    span = min(float(args.trace_seconds),
               float(ctx["traffic"].get("trace_seconds", args.trace_seconds)))
    with mock.patch.object(T, "closed_list", lambda traffic, seed: pairs):
        return window(ctx, args.seed, float(args.seconds), bool(args.trace),
                      span, clock)

"""Driver of the serving cells of the windowed exact + pooled-pair attention
family: the loops, the window and the hooks are ``drivers/serve.py``'s; what
that file ties to the Llama family by name (the program's config, the
replica's weights and its reference) is brought here, as
``drivers/serve_ssm_moe.py`` brings that family's. ``correct_limits`` of a
configuration are read under this driver's name. As there, the closed loop's
list keeps ONE order whatever the seed (``even_list``), the traffic file's
``reference_check`` is a LIST of requests — one that crosses a window's end
while it decodes, one under a window — whose readings are reported side by
side, the later ones under a prefix, and the deployment takes as many calls at
once as the file has callers (32 on 24 slots: the 8 that wait do so in the
engine's queue)."""
from __future__ import annotations

from unittest import mock

from benchmarks.drivers.serve import APP, DEPLOYMENT, say, window
from benchmarks.drivers.serve_cohere2_moe import even_list
from benchmarks.drivers.serve_sparse_moe import reachable_pads
from benchmarks.lib import traffic as T

# the checked requests' names, in the traffic file's order
CHECKS = ("", "short.")


def eva_config(config_file: dict, **overrides):
    """The published keys of a configuration file as the program's
    ``EvaConfig``; what the program cannot express is refused."""
    from ray_tpu.models.eva import EvaConfig

    c = {**config_file, **overrides}
    fixed = {"model_type": "evabyte", "attention_class": "eva",
             "hidden_act": "silu", "attention_bias": False,
             "tie_word_embeddings": False, "fp32_ln": False,
             "fp32_logits": True, "fp32_skip_add": True, "mixedp_attn": True,
             "norm_add_unit_offset": True, "rope_scaling": None,
             "num_chunks": None}
    for key, want in fixed.items():
        if c.get(key, want) != want:
            raise ValueError(f"models/eva.py has no {key}={c[key]!r}")
    heads = c["num_attention_heads"]
    if c["num_key_value_heads"] != heads:
        raise ValueError("models/eva.py has a KV head a query head")
    if c["max_seq_length"] != c["max_position_embeddings"]:
        raise ValueError("max_seq_length is not max_position_embeddings")
    return EvaConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=heads,
        head_dim=c["hidden_size"] // heads, d_ff=c["intermediate_size"],
        window_size=c["window_size"], chunk_size=c["chunk_size"],
        n_pred_heads=c["num_pred_heads"], rms_norm_eps=float(c["rms_norm_eps"]),
        max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), dtype=c["torch_dtype"])


def deploy(cfg, engine_kw: dict, seed: int, callers: int):
    from ray_tpu import serve as rt_serve
    from benchmarks.lib.replica_eva import EvaBenchServer, make_params_fn

    dep = rt_serve.deployment(EvaBenchServer, name=DEPLOYMENT,
                              num_replicas=1, max_ongoing_requests=2 * callers,
                              ray_actor_options={"num_tpus": 1})
    app = dep.bind(cfg, None, make_params_fn(cfg, seed, engine_kw["eos_id"]),
                   **engine_kw)
    rt_serve.run(app, name=APP, timeout_s=1100)
    return rt_serve.get_deployment_handle(DEPLOYMENT, APP)


def setup(cell: dict, args, clock) -> dict:
    """``drivers/serve_ssm_moe.py``'s set-up with this family's config and
    replica: deploy, check the device, warm every reachable program, compare
    each checked request with the plain reference."""
    import ray_tpu

    cf, traffic = cell["config_file"], cell["traffic_file"]
    if args.allow_cpu:
        cf, traffic = {**cf, **cf["tiny"]}, {**traffic, **traffic["tiny"]}
    cfg = eva_config(cf)
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

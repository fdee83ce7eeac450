"""Driver of the training cells: ``JaxTrainer(ScalingConfig(use_tpu=True))``
-> ``TrainWorker`` -> ``make_train_step``. The loop below runs in the worker
that was leased the chip and reports once, at its end."""
from __future__ import annotations

import collections
import os
import tempfile
import time

from benchmarks.lib.configs import llama_config


def reference_numbers(seed: int, cfg, params, rc: dict, reference: str,
                      control_mode: str | None = None) -> dict:
    """``correct`` for a training cell: the program's loss, gradient norm and
    the reference's picked gradient vectors on a seeded micro-batch
    (``llama_loss`` as ``make_train_step`` differentiates it, flash kernels
    and remat included) against the plain float32 reference's. The first
    layer's vectors pass back through every layer's attention backward. With
    ``control_mode`` the reference at that lower precision stands in the
    program's place."""
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import weights
    from benchmarks.lib.configs import load_module
    from ray_tpu.models.llama import llama_loss

    ref_mod = load_module("reference", reference)
    micro = jax.random.randint(
        jax.random.fold_in(weights.seed_key(seed), 1000),
        (rc["batch"], rc["seq_len"] + 1), 0, cfg.vocab_size, jnp.int32)

    @jax.jit
    def program_grads(params, batch):
        loss, g = jax.value_and_grad(
            lambda p: llama_loss(p, batch, cfg, attn_impl="auto"))(params)
        g = jax.tree.map(lambda x: x.astype(jnp.float32), g)
        sq = sum(jnp.sum(x ** 2) for x in jax.tree.leaves(g))
        vectors = {"final_norm": g["norm"]["scale"]}
        for name, (i, sub) in ref_mod.picked_vectors(cfg.n_layers).items():
            vectors[name] = g[f"layers_{i}"][sub][ref_mod.LAYER_VECTORS[sub]]
        return {"loss": loss, "grad_norm": jnp.sqrt(sq), "vectors": vectors}

    want = ref_mod.loss_and_grads(seed, cfg, micro)
    out = {"mode": control_mode or "program"}
    if control_mode:
        got = ref_mod.loss_and_grads(seed, cfg, micro, mode=control_mode)
    else:
        compiled = program_grads.lower(params, {"tokens": micro}).compile()
        out["kernel_in_check"] = "tpu_custom_call" in compiled.as_text()
        got = compiled(params, {"tokens": micro})

    def rel(a, b):
        return float(jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel()))

    out.update({
        "loss_rel_err": rel(got["loss"], want["loss"]),
        "grad_norm_rel_err": rel(got["grad_norm"], want["grad_norm"]),
        "loss": float(got["loss"]), "loss_reference": float(want["loss"])})
    for name, vec in want["vectors"].items():
        out[f"grad_vec_rel_err.{name}"] = rel(got["vectors"][name], vec)
    return out


def train_loop(config: dict) -> None:
    import jax
    import jax.numpy as jnp
    import optax

    from benchmarks.lib import weights
    from benchmarks.lib.xplane import reduce_trace
    from ray_tpu import train
    from ray_tpu.models.llama import make_train_step
    from ray_tpu.utils.device import device_report

    cfg, seed, tr = config["cfg"], config["seed"], config["traffic"]
    marks = {"worker": time.monotonic()}
    device = device_report()
    if device["platform"] != "tpu" and not config["allow_cpu"]:
        raise RuntimeError(f"the train worker runs on {device['platform']!r}: "
                           f"a CPU device is a failure, never a fallback")
    key = weights.seed_key(seed)
    params = weights.make_params(key, cfg)

    def tokens(i: int, batch: int, seq: int):
        return jax.random.randint(jax.random.fold_in(key, 1000 + i),
                                  (batch, seq + 1), 0, cfg.vocab_size, jnp.int32)

    reference = reference_numbers(seed, cfg, params, tr["reference_check"],
                                  config["reference"], config.get("control_mode"))
    marks["reference"] = time.monotonic()

    # ---- the job
    optimizer = optax.adamw(config["trainer"]["learning_rate"])
    opt_state = jax.jit(optimizer.init)(params)
    B, S = tr["batch"], tr["seq_len"]
    batches = [{"tokens": tokens(10 + i, B, S)} for i in range(tr["distinct_batches"])]
    step = make_train_step(cfg, optimizer, attn_impl="auto")
    compiled = step.lower(params, opt_state, batches[0]).compile()
    has_kernel = "tpu_custom_call" in compiled.as_text()
    losses = []
    for i in range(tr["lead_in_steps"]):
        params, opt_state, loss = compiled(params, opt_state,
                                           batches[i % len(batches)])
        losses.append(float(loss))  # the fence: a device -> host read
    marks["window"] = t0 = time.monotonic()

    # ``steps_ahead`` steps (some seconds of them) in flight ahead of the one
    # whose loss is read: the device works on while the host stands still, and
    # each step's end is seen at its own fence. When the window's time is up
    # nothing more is sent, all that was sent is waited for, and the last fence
    # closes the span: every step sent counts, over all of that time
    seconds, trace_dir = config["seconds"], config["trace_dir"]
    ahead = int(tr["steps_ahead"])
    ends, sent, i, trace, tracing = [], collections.deque(), 0, None, None

    def fence(keep: int) -> None:
        while len(sent) > keep:
            losses.append(float(sent.popleft()))
            ends.append(time.monotonic())

    while True:
        now = time.monotonic()
        if config["trace"] and tracing is None and len(ends) >= 2:
            jax.profiler.start_trace(trace_dir)
            tracing = (time.monotonic(), len(ends))
        if (tracing and trace is None
                and now - tracing[0] >= config["trace_seconds"]):
            fence(0)
            span = time.monotonic() - tracing[0]
            jax.profiler.stop_trace()
            trace = {**reduce_trace(trace_dir), "span_s": span,
                     "steps": len(ends) - tracing[1]}
        if now >= t0 + seconds:
            break
        params, opt_state, loss = compiled(params, opt_state,
                                           batches[i % len(batches)])
        i += 1
        sent.append(loss)
        fence(ahead)
    fence(0)
    marks["measured"] = time.monotonic()
    train.report({
        "device": device_report(), "marks": marks, "reference": reference,
        "has_kernel": has_kernel, "losses": losses, "step_ends": [e - t0 for e in ends],
        "steps": len(ends), "span_s": (ends[-1] - t0) if ends else 0.0,
        "tokens_per_step": B * S, "trace": trace,
    })


def run(cell: dict, args, clock) -> dict:
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    cf, traffic = cell["config_file"], cell["traffic_file"]
    if args.allow_cpu:
        cf, traffic = {**cf, **cf["tiny"]}, {**traffic, **traffic["tiny"]}
    cfg = llama_config(cf)
    tmp = tempfile.gettempdir()
    result = JaxTrainer(
        train_loop,
        train_loop_config={
            "cfg": cfg, "seed": args.seed, "traffic": traffic,
            "trainer": cf["trainer"], "reference": cf["reference"],
            "seconds": float(args.seconds), "trace": bool(args.trace),
            "trace_seconds": float(args.trace_seconds),
            "trace_dir": os.path.join(tmp, "bench_trace"),
            "allow_cpu": args.allow_cpu,
            "control_mode": getattr(args, "control_mode", None)},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
        run_config=RunConfig(name="bench_train",
                             storage_path=os.path.join(tmp, "bench_train")),
    ).fit()
    if result.error is not None:
        raise result.error
    m = result.metrics
    for name, t in m["marks"].items():
        clock.marks[name] = t  # one host, one monotonic clock
    import math

    finite = all(math.isfinite(x) for x in m["losses"])
    return {
        "device": m["device"], "seconds": float(args.seconds),
        "attempted": len(m["step_ends"]), "failed": 0 if finite else 1,
        "reference": m["reference"], "compiles_in_window": 0,
        "train": m, "trace": m["trace"],
        "trace_span_s": (m["trace"] or {}).get("span_s"),
        "cfg": cfg, "traffic": traffic,
    }

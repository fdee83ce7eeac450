#!/usr/bin/env python3
"""Compile a cell's programs at their real sizes for a *described* v5e chip
(no chip attached, no chip time) and print ``memory_analysis()``: how the
depth of a configuration is sized before any chip call. Nothing runs, so
this says nothing about results or speed.

    python benchmarks/sizing.py --config mistral-7b-v0.3 --layers 13 \
        --decode 64 --prefill 8x1792 --prefill 1x1536
    python benchmarks/sizing.py --config yi-6b --layers 4 --train 4x4096
"""
from __future__ import annotations

import argparse
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--layers", type=int, required=True)
    ap.add_argument("--decode", type=int, action="append", default=[],
                    help="fused steps of a paged_decode_multi to compile")
    ap.add_argument("--prefill", action="append", default=[],
                    help="WAVExPAD of a paged_prefill_batch to compile")
    ap.add_argument("--train", action="append", default=[],
                    help="BATCHxSEQ of a train step to compile")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.lib.configs import llama_config, load_json
    from ray_tpu.models.llama import llama_init, make_train_step

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def placed(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)

    def sd(shape, dtype):
        return placed(jax.ShapeDtypeStruct(shape, dtype))

    def report(name, lowered):
        t0 = time.monotonic()
        try:
            mem = lowered.compile().memory_analysis()
        except Exception as e:  # the compiler's refusal is the finding
            print(f"{name}: REFUSED {str(e)[:300]}", flush=True)
            return
        gb = 1e9
        print(f"{name}: arguments {mem.argument_size_in_bytes / gb:.2f} GB, "
              f"temporaries {mem.temp_size_in_bytes / gb:.2f} GB, outputs "
              f"{mem.output_size_in_bytes / gb:.2f} GB (aliased "
              f"{mem.alias_size_in_bytes / gb:.2f}); arguments + temporaries "
              f"{(mem.argument_size_in_bytes + mem.temp_size_in_bytes) / gb:.2f}"
              f" GB; compiled in {time.monotonic() - t0:.0f}s", flush=True)

    cf = load_json("configs", args.config + ".json")
    cfg = llama_config(cf, num_hidden_layers=args.layers)
    params = placed(jax.eval_shape(lambda: llama_init(jax.random.PRNGKey(0), cfg)))
    key = sd((2,), jnp.uint32)

    if args.decode or args.prefill:
        from ray_tpu.llm.engine import paged_decode_multi, paged_prefill_batch

        e = cf["engine"]
        B, PS = e["max_batch"], e["page_size"]
        pool = sd((cfg.n_layers, e["n_pages"], PS, cfg.n_kv_heads, cfg.head_dim),
                  jnp.dtype(cfg.dtype))
        i32 = sd((B,), jnp.int32)
        for k in args.decode:
            report(f"paged_decode_multi n_steps={k} layers={args.layers}",
                   paged_decode_multi.lower(
                       params, None, i32, i32, i32,
                       sd((B, e["max_seq_len"] // PS), jnp.int32), pool, pool,
                       sd((B,), jnp.bool_), sd((B,), jnp.float32), key,
                       cfg=cfg, n_steps=k))
        for spec in args.prefill:
            n, tp = (int(x) for x in spec.split("x"))
            report(f"paged_prefill_batch wave={n} pad={tp} layers={args.layers}",
                   paged_prefill_batch.lower(
                       params, None, sd((n,), jnp.int32), sd((n, tp), jnp.int32),
                       sd((n, tp // PS), jnp.int32), pool, pool,
                       sd((n,), jnp.int32), sd((n,), jnp.float32), key, cfg=cfg))
    for spec in args.train:
        import optax

        b, t = (int(x) for x in spec.split("x"))
        jax.default_backend = lambda: "tpu"  # attn_impl="auto" asks; see tests/test_chip_compile.py
        optimizer = optax.adamw(cf["trainer"]["learning_rate"])
        opt_state = placed(jax.eval_shape(optimizer.init, params))
        step = make_train_step(cfg, optimizer, attn_impl="auto")
        report(f"train_step batch={b}x{t} layers={args.layers}",
               step.lower(params, opt_state, {"tokens": sd((b, t + 1), jnp.int32)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

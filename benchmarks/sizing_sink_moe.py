#!/usr/bin/env python3
"""``sizing.py`` for the window-with-a-sink sparse-expert family (a copy of
``sizing_cohere2_moe.py``: README_sink_moe.md): compile the cell's two
programs at their real sizes for a *described* v5e chip and print
``memory_analysis()``; ``--layout`` prints how a K pool of 192-lane rows
would lie on the device beside the 256-lane rows the family keeps. Nothing
runs.

    python benchmarks/sizing_sink_moe.py --config mimo-v2-flash --layout \
        --decode 64 --prefill 1x16384 --prefill 2x8192 --prefill 8x2048
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
    ap.add_argument("--decode", type=int, action="append", default=[])
    ap.add_argument("--prefill", action="append", default=[])
    ap.add_argument("--max-batch", type=int)
    ap.add_argument("--pages", help="FULL,WINDOW pages to size instead of the file's")
    ap.add_argument("--layout", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.drivers.serve_sink_moe import sink_moe_config
    from benchmarks.lib.configs import load_json
    from ray_tpu.llm import sink_moe as programs
    from ray_tpu.models.sink_moe import sink_moe_init

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    programs._reads_in_place = lambda: True  # the chip's branch, compiled here
    jax.default_backend = lambda: "tpu"      # and the kernels compiled, not interpreted

    def placed(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)

    def sd(shape, dtype):
        return placed(jax.ShapeDtypeStruct(shape, dtype))

    cf = load_json("configs", args.config + ".json")
    cfg = sink_moe_config(cf)
    e = dict(cf["engine"])
    if args.max_batch:
        e["max_batch"] = args.max_batch
    if args.pages:
        full, win = (int(x) for x in args.pages.split(","))
        e["n_pages"] = {"full": full, "window": win}
    B, PS = e["max_batch"], e["page_size"]
    params = placed(jax.eval_shape(
        lambda: sink_moe_init(jax.random.PRNGKey(0), cfg)))
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    cache = placed(jax.eval_shape(
        lambda: programs.make_pools(cfg, PS, e["n_pages"], None)))
    pools = sum(x.size * x.dtype.itemsize for x in cache)
    kinds = programs.page_kinds(cfg, PS, e["max_seq_len"])
    print(f"weights {weights / 1e9:.3f} GB, pools {pools / 1e9:.3f} GB "
          f"({e['n_pages']}: {[tuple(c.shape) for c in cache]}), slots {B}, "
          f"tables {[k.table for k in kinds]}", flush=True)
    key = sd((2,), jnp.uint32)

    if args.layout:
        # how a pool of 192-lane rows would lie: the compiled identity's
        # argument layout and the bytes the chip gives it
        for lanes in (cfg.head_dim, programs.key_lanes(cfg)):
            pool = sd((1, 64, PS, cfg.swa_n_kv_heads, lanes), jnp.bfloat16)
            done = jax.jit(lambda x: x + 1).lower(pool).compile()
            mem = done.memory_analysis()
            print(f"K rows of {lanes} lanes: {pool.shape} bf16 is "
                  f"{pool.size * 2} B of numbers, {mem.argument_size_in_bytes} "
                  f"B on the device; layout {done.input_formats[0][0]}",
                  flush=True)

    def report(name, lowered):
        t0 = time.monotonic()
        try:
            mem = lowered.compile().memory_analysis()
        except Exception as ex:  # the compiler's refusal is the finding
            print(f"{name}: REFUSED {str(ex)[:400]}", flush=True)
            return
        gb = 1e9
        print(f"{name}: arguments {mem.argument_size_in_bytes / gb:.2f} GB, "
              f"temporaries {mem.temp_size_in_bytes / gb:.2f} GB; arguments + "
              f"temporaries {(mem.argument_size_in_bytes + mem.temp_size_in_bytes) / gb:.2f}"
              f" GB; compiled in {time.monotonic() - t0:.0f}s", flush=True)

    i32 = sd((B,), jnp.int32)
    tables = tuple(sd((B, k.table), jnp.int32) for k in kinds)
    for k in args.decode:
        report(f"sink_moe_decode_multi n_steps={k}",
               programs.sink_moe_decode_multi.lower(
                   params, None, i32, i32, i32, tables, *cache,
                   sd((B,), jnp.bool_), sd((B,), jnp.float32), key, cfg=cfg,
                   n_steps=k))
    for spec in args.prefill:
        n, tp = (int(x) for x in spec.split("x"))
        pages = tuple(sd((n, min(tp // PS, k.table)), jnp.int32) for k in kinds)
        report(f"sink_moe_prefill_batch wave={n} pad={tp}",
               programs.sink_moe_prefill_batch.lower(
                   params, None, sd((n,), jnp.int32), sd((n, tp), jnp.int32),
                   pages, *cache, sd((n,), jnp.int32), sd((n,), jnp.float32),
                   key, cfg=cfg))
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""``sizing.py`` for the looped family (``sizing.py`` names the Llama
programs; a copy of ``sizing_cca_moe.py`` with this family's one table and
two pools of a plane a pass a layer): compile the cell's two programs at
their real sizes for a *described* v5e chip and print ``memory_analysis()``
and, with ``--copies``, every instruction of the optimised program as large
as a whole pool, the embedding or the head (a copy of a pool on entry, on
exit or between two passes is 4.4 GB moved). Nothing runs.

    python benchmarks/sizing_looped.py --config ouro-2.6b \
        --decode 8 --prefill 4x384 --prefill 1x112 --copies
"""
from __future__ import annotations

import argparse
import os
import re
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
    ap.add_argument("--pages", type=int, help="K/V pages to size instead of the file's")
    ap.add_argument("--layers", type=int, help="a depth to size instead of the file's")
    ap.add_argument("--copies", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.drivers.serve_looped import looped_config
    from benchmarks.lib.configs import load_json
    from ray_tpu.llm import looped as programs
    from ray_tpu.models.looped import looped_init

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    # the chip's branches, compiled here: the paged and the blocked attention
    # kernels
    jax.default_backend = lambda: "tpu"

    def placed(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)

    def sd(shape, dtype):
        return placed(jax.ShapeDtypeStruct(shape, dtype))

    cf = load_json("configs", args.config + ".json")
    if args.layers:
        cf = {**cf, "num_hidden_layers": args.layers,
              "max_window_layers": args.layers,
              "layer_types": cf["layer_types"][:args.layers]}
    cfg = looped_config(cf)
    e = dict(cf["engine"])
    if args.max_batch:
        e["max_batch"] = args.max_batch
    if args.pages:
        e["n_pages"] = args.pages
    B, PS = e["max_batch"], e["page_size"]
    params = placed(jax.eval_shape(
        lambda: looped_init(jax.random.PRNGKey(0), cfg)))
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    cache = placed(jax.eval_shape(
        lambda: programs.make_pools(cfg, PS, e["n_pages"], None)))
    pools = sum(x.size * x.dtype.itemsize for x in cache)
    maxp = -(-e["max_seq_len"] // PS)
    print(f"{cfg.n_layers} layers x {cfg.n_passes} passes: weights {weights / 1e9:.3f} GB, "
          f"pools {pools / 1e9:.3f} GB ({e['n_pages']}), slots {B}, table {maxp}",
          flush=True)
    pool_shapes = {",".join(map(str, x.shape)) for x in cache} | {
        f"{cfg.vocab_size},{cfg.d_model}", f"{cfg.d_model},{cfg.vocab_size}"}
    key = sd((2,), jnp.uint32)

    def report(name, lowered):
        t0 = time.monotonic()
        try:
            compiled = lowered.compile()
            mem = compiled.memory_analysis()
        except Exception as ex:  # the compiler's refusal is the finding
            print(f"{name}: REFUSED {str(ex)[:400]}", flush=True)
            return
        gb = 1e9
        print(f"{name}: arguments {mem.argument_size_in_bytes / gb:.2f} GB, "
              f"temporaries {mem.temp_size_in_bytes / gb:.2f} GB; arguments + "
              f"temporaries {(mem.argument_size_in_bytes + mem.temp_size_in_bytes) / gb:.2f}"
              f" GB; compiled in {time.monotonic() - t0:.0f}s", flush=True)
        if args.copies:
            # an instruction whose result has a pool's shape and is no
            # parameter, scatter or in-place update of it
            for line in compiled.as_text().splitlines():
                m = re.match(r"\s*(?:ROOT )?(%\S+) = (\w+)\[([\d,]+)\]\S* (\S+?)\(", line)
                if m and m.group(3) in pool_shapes and m.group(4) in (
                        "copy", "transpose", "bitcast-convert", "convert",
                        "gather", "slice", "dynamic-slice", "pad", "fusion"):
                    print(f"    a pool's shape: {line.strip()[:220]}", flush=True)

    i32 = sd((B,), jnp.int32)
    tables = sd((B, maxp), jnp.int32)
    for k in args.decode:
        report(f"looped_decode_multi n_steps={k}",
               programs.looped_decode_multi.lower(
                   params, None, i32, i32, i32, tables, *cache,
                   sd((B,), jnp.bool_), sd((B,), jnp.float32), key, cfg=cfg,
                   n_steps=k))
    for spec in args.prefill:
        n, tp = (int(x) for x in spec.split("x"))
        pages = sd((n, tp // PS), jnp.int32)
        report(f"looped_prefill_batch wave={n} pad={tp}",
               programs.looped_prefill_batch.lower(
                   params, None, sd((n,), jnp.int32), sd((n, tp), jnp.int32),
                   pages, *cache, sd((n,), jnp.int32), sd((n,), jnp.float32),
                   key, cfg=cfg))
    return 0


if __name__ == "__main__":
    sys.exit(main())

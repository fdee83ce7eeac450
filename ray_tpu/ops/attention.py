"""Attention dispatch: one entry point, backend picked by mesh/hardware.

- plain exact attention (XLA fuses well at short T)
- pallas flash attention on TPU (ops/flash_attention.py) for long T
- ring attention over the sp mesh axis when sequence is sharded
- ulysses all-to-all variant for head-divisible meshes
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.parallel.ring_attention import reference_attention


def attention(q, k, v, *, causal: bool = True, sm_scale=None, mesh=None,
              seq_axis: str | None = None, impl: str = "auto"):
    """q/k/v: [B, T, H, D] (kv may have fewer heads — GQA broadcast here).
    The train path still repeats K and V to H heads (the serve programs do
    not: ``llm/generation.py`` ``_gqa_attn``): the flash kernels take equal
    head counts, and at Yi-6B's 2 x 4096 tokens the repeat writes 67 MB each a
    layer against a 0.48 s step — left for a PR of its own.

    impl: auto | plain | flash | ring | ulysses
    """
    if k.shape[2] != q.shape[2]:  # grouped-query: repeat kv heads
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    if impl == "auto":
        if mesh is not None and seq_axis and mesh.shape.get(seq_axis, 1) > 1:
            impl = "ring"
        else:
            impl = _default_local_impl(q)

    if impl == "ring":
        from ray_tpu.parallel.ring_attention import ring_attention

        return ring_attention(q, k, v, mesh, axis_name=seq_axis or "sp",
                              causal=causal, sm_scale=sm_scale)
    if impl == "ulysses":
        from ray_tpu.parallel.ulysses import ulysses_attention

        return ulysses_attention(q, k, v, mesh, axis_name=seq_axis or "sp",
                                 causal=causal, sm_scale=sm_scale)
    if impl == "flash":
        from ray_tpu.ops.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale)
    return reference_attention(q, k, v, causal=causal, sm_scale=sm_scale)


def _default_local_impl(q) -> str:
    B, T, H, D = q.shape
    if (jax.default_backend() == "tpu" and T >= 1024 and T % 512 == 0
            and D in (64, 128, 256)):
        return "flash"
    return "plain"

"""Ulysses-style sequence parallelism: all_to_all head/sequence re-sharding.

Absent from the reference (ref: SURVEY §5.7). The DeepSpeed-Ulysses recipe
mapped to XLA: attention inputs arrive sequence-sharded [B, T/n, H, D];
one ``lax.all_to_all`` re-shards to head-sharded full-sequence
[B, T, H/n, D]; exact attention runs locally per head group; a second
all_to_all restores sequence sharding. Two fabric transposes per attention
call, both ICI-resident under shard_map.
"""

from __future__ import annotations

import functools

from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel.ring_attention import reference_attention


def ulysses_attention_local(q, k, v, *, axis_name: str, causal: bool = True,
                            sm_scale: float | None = None):
    """Per-shard body (inside shard_map): q/k/v [B, t, H, D], H % n == 0."""

    def seq_to_heads(x):
        # [B, t, H, D] -> [B, T, H/n, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    out = reference_attention(qh, kh, vh, causal=causal, sm_scale=sm_scale)
    return heads_to_seq(out.astype(q.dtype))


def ulysses_attention(q, k, v, mesh, *, axis_name: str = "sp", causal: bool = True,
                      sm_scale: float | None = None):
    batch_axes = tuple(a for a in ("dp", "fsdp") if a in mesh.shape)
    spec = P(batch_axes or None, axis_name, None, None)
    fn = shard_map(
        functools.partial(
            ulysses_attention_local, axis_name=axis_name, causal=causal, sm_scale=sm_scale
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)

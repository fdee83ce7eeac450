"""Ring attention: exact attention over a sequence-sharded mesh axis.

Absent from the reference (ref: SURVEY §5.7 — no ring attention, no context
parallel in-tree; long sequences are handed to vLLM/torch). First-class
here: K/V chunks rotate around the ``sp`` mesh axis via
``lax.ppermute`` (ICI neighbor hops) while each device accumulates its
queries' output with the online-softmax (flash) recurrence, so peak memory
per chip is O(T/n) and the ring transfers overlap with compute blocks.

Layout convention: [batch, seq, heads, head_dim], sequence sharded on sp.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

_NEG_BIG = -1e30


def _ring_perm(n: int):
    return [(i, (i + 1) % n) for i in range(n)]


def ring_attention_local(q, k, v, *, axis_name: str, causal: bool = True,
                         sm_scale: float | None = None,
                         vary_axes: tuple = ()):
    """Per-shard body: call inside shard_map over ``axis_name``.

    q, k, v: [B, t, H, D] local chunks (t = T / ring_size).
    Returns [B, t, H, D].
    """
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    B, t, H, D = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    perm = _ring_perm(n)

    q_pos = my * t + jnp.arange(t)  # global positions of my queries

    def body(s, carry):
        k_cur, v_cur, m, l, o = carry
        src = (my - s) % n  # which shard this k/v chunk originated from
        # scores: [B, H, tq, tk]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_cur) * scale
        if causal:
            k_pos = src * t + jnp.arange(t)
            mask = q_pos[:, None] >= k_pos[None, :]  # [tq, tk]
            scores = jnp.where(mask[None, None], scores, _NEG_BIG)
        else:
            mask = None
        m_new = jnp.maximum(m, scores.max(axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        if causal:
            p = jnp.where(mask[None, None], p, 0.0)  # kill fully-masked rows
        correction = jnp.exp(m - m_new)
        l_new = l * correction + p.sum(axis=-1)
        o_new = o * correction[..., None].transpose(0, 2, 1, 3) + jnp.einsum(
            "bhqk,bkhd->bqhd", p, v_cur
        )
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return k_next, v_next, m_new, l_new, o_new

    # initial accumulators must be marked device-varying over the ring axis
    # or the scan carry types disagree (shard_map vma typing)
    axes = tuple(vary_axes) + (axis_name,) if axis_name not in vary_axes else tuple(vary_axes)

    def _vary(x):
        return lax.pcast(x, axes, to="varying")

    m0 = _vary(jnp.full((B, H, t), _NEG_BIG, dtype=jnp.float32))
    l0 = _vary(jnp.zeros((B, H, t), dtype=jnp.float32))
    o0 = _vary(jnp.zeros((B, t, H, D), dtype=jnp.float32))
    _, _, m, l, o = lax.fori_loop(0, n, body, (k, v, m0, l0, o0))
    denom = jnp.maximum(l, 1e-30)
    out = o / denom[..., None].transpose(0, 2, 1, 3)
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh, *, axis_name: str = "sp", causal: bool = True,
                   sm_scale: float | None = None):
    """Sharded entry point: q/k/v [B, T, H, D] with T sharded on ``axis_name``.
    Batch stays sharded over the data axes (dp/fsdp) so this composes with
    data parallelism inside one jitted step."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    batch_axes = tuple(a for a in ("dp", "fsdp") if a in mesh.shape)
    spec = P(batch_axes or None, axis_name, None, None)
    fn = shard_map(
        functools.partial(
            ring_attention_local, axis_name=axis_name, causal=causal,
            sm_scale=sm_scale, vary_axes=batch_axes,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)


def reference_attention(q, k, v, *, causal: bool = True, sm_scale: float | None = None):
    """Unsharded exact attention for testing parity."""
    B, T, H, D = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        mask = jnp.tril(jnp.ones((T, T), dtype=bool))
        scores = jnp.where(mask[None, None], scores, _NEG_BIG)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)

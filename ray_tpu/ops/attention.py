"""Attention dispatch: one entry point, backend picked by mesh/hardware.

- plain exact attention (XLA fuses well at short T)
- pallas flash attention on TPU (ops/flash_attention.py) for long T
- ring attention over the sp mesh axis when sequence is sharded
- ulysses all-to-all variant for head-divisible meshes

Beside it, the two plain forms the serving programs of more than one model
family share (XLA operations only): masked attention with the scores written
out, and a decode step's attention over a slot's gathered page table.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.parallel.ring_attention import reference_attention
from ray_tpu.utils import tracing


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


@tracing.part("attention")
def masked_attention(q, k, v, mask, sink=None):
    """Masked grouped-query attention with the scores written out: the plain
    form (a no-cache forward, and the serving programs off the TPU). q:
    [B, Tq, H, hd]; k: [B, Tk, KV, hd]; v: [B, Tk, KV, hv] (``hv`` need not
    be ``hd``); mask: [B, Tq, Tk]. ``sink`` [H]: one learned score a query
    head that stands in the softmax as one more column and is dropped — it
    takes mass and gives no value. Returns [B, Tq, H * hv]."""
    B, Tq, H, d = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, Tq, KV, H // KV, d)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
    s = jnp.where(mask[:, None, None], s / jnp.sqrt(jnp.float32(d)),
                  jnp.float32(-1e30))
    if sink is not None:
        col = jnp.broadcast_to(sink.astype(jnp.float32).reshape(
            1, KV, H // KV, 1, 1), (B, KV, H // KV, Tq, 1))
        s = jnp.concatenate([s, col], axis=-1)
    w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    if sink is not None:
        w = w[..., :-1]
    return jnp.einsum("bkgqs,bskd->bqkgd", w, v).reshape(
        B, Tq, H * v.shape[-1])


@tracing.part("attention")
def gathered_attention(q, kpool, vpool, table, pos, window: int | None = None,
                       sink=None):
    """The plain form of a decode step's walk over K and V pages: every entry
    of the slot's table gathered, each row masked by the position it holds.
    ``window`` None: entry e holds page e and a query attends back to
    position 0. A number: the table is a RING — entry e holds the latest page
    ``p <= pos // PS`` with ``p % entries == e`` — and a query attends the
    ``window`` positions up to its own. q: [B, 1, H, hd]; kpool: [P, PS, KV,
    hk] with ``hk >= hd`` (a row's lanes past the query's width are not
    read); vpool: [P, PS, KV, hv] (one layer each); table: [B, entries];
    pos: [B]; ``sink``: ``masked_attention``'s. Returns [B, 1, H * hv]."""
    B, entries = table.shape
    PS = kpool.shape[1]
    e = jnp.arange(entries)[None, :]
    last = (pos // PS)[:, None]
    page = jnp.broadcast_to(e, (B, entries)) if window is None else (
        last - (last - e) % entries)
    k_pos = (page[:, :, None] * PS + jnp.arange(PS)[None, None, :]
             ).reshape(B, entries * PS)
    held, q_pos = k_pos >= 0, pos[:, None]  # a ring's entry not yet written
    ok = q_pos >= k_pos
    if window is not None:
        ok &= q_pos - k_pos < window
    mask = held & ok

    def rows(pool):
        return pool[table].reshape(B, entries * PS, *pool.shape[2:]
                                   ).astype(q.dtype)

    return masked_attention(q, rows(kpool)[..., :q.shape[-1]], rows(vpool),
                            mask[:, None], sink)

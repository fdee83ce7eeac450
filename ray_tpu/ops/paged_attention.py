"""Pallas TPU paged decode attention — K and V read where they lie.

One query row a slot attends the slot's keys and values straight out of the
engine's page pools ``[L, P, PS, KV, hd]``: the pools stay in HBM, the kernel
walks the slot's page table and fetches only the pages that hold tokens, a
page of a layer being one contiguous ``[PS, KV, hd]`` run. Nothing is sliced
out of the pool, no ``[B, MAXP * PS, KV, hd]`` window is gathered, and no
position past a slot's length is contracted (what ``_kv_read`` +
``_gqa_attn`` do, and stay the plain reference for: ``llm/llama.py``).

One kernel invocation serves every slot: a work list of (slot, block) items,
a block being ``n_pages`` pages (256 tokens), runs through two VMEM buffers — the
next item's page copies are in flight while this one is used, across slot
boundaries too, so only the first block of a program waits for its pages.
Per block the fetched pages are read as ``[tokens * KV, hd]`` rows, as they
lie: the H query heads meet ALL rows in one matmul and a head mask keeps,
for query head h, the rows of KV head ``h // G`` (G = H // KV, read from the
shapes) — the G rows of a KV head against that head's keys, without a
strided load or a transpose of the block. The MXU's time is set by the K and
V tiles it has to hold, which are the same either way. Online softmax in
float32 (running maximum, sum, accumulator); the probabilities are cast to
the pool's dtype for w·V as ``_gqa_attn`` casts them.

The layer index, the page tables and the lengths are scalar-prefetched, and
the entry point is a jit of its own, so the call sites of a program's layers
share one traced and lowered kernel.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_BIG = -1e30
_BLOCK_TOKENS = 256  # tokens a compute block: 1 MB of K and V at 8 x 128 bf16


def _kernel(layer_ref, tables_ref, lengths_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, *, sm_scale: float, n_pages: int):
    B, H, hd = q_ref.shape
    _, _, PS, KV, _ = k_hbm.shape
    MAXP = tables_ref.shape[1]
    G = H // KV
    rows = n_pages * PS * KV  # rows of one block, token-major then KV head
    layer = layer_ref[0]

    def pages_of(b):
        return jnp.minimum(pl.cdiv(lengths_ref[b], PS), MAXP)

    def copies(b, i, buf):
        """The page copies of block ``i`` of slot ``b`` into buffer ``buf``,
        each with whether the page holds tokens (dead pages are not
        fetched; what the buffer held before stays there, masked)."""
        out = []
        live = pages_of(b)
        for j in range(n_pages):
            p = i * n_pages + j
            page = tables_ref[b, jnp.minimum(p, MAXP - 1)]
            for pool, dst, kv in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                out.append((p < live, pltpu.make_async_copy(
                    pool.at[layer, page], dst.at[buf, j], sems.at[kv, buf])))
        return out

    def start(b, i, buf):
        for cond, cp in copies(b, i, buf):
            pl.when(cond)(cp.start)

    def wait(b, i, buf):
        for cond, cp in copies(b, i, buf):
            pl.when(cond)(cp.wait)

    def next_slot(b):
        """The first slot at or after ``b`` that holds tokens, or B."""
        return jax.lax.while_loop(
            lambda s: jnp.logical_and(
                s < B, lengths_ref[jnp.minimum(s, B - 1)] <= 0),
            lambda s: s + 1, b)

    # dead pages of a block are masked, not fetched: whatever the buffers
    # hold there has to be finite for the w·V product
    kbuf[...] = jnp.zeros_like(kbuf)
    vbuf[...] = jnp.zeros_like(vbuf)

    first = next_slot(jnp.int32(0))

    @pl.when(first < B)
    def _():
        start(first, 0, 0)

    # which KV head a row of the block belongs to, against each query head's
    row = jax.lax.broadcasted_iota(jnp.int32, (H, rows), 1)
    row_tok = row // KV
    head_ok = row % KV == jax.lax.broadcasted_iota(jnp.int32, (H, rows), 0) // G

    def slot(b, buf):
        length = jnp.minimum(lengths_ref[b], MAXP * PS)
        n_blocks = pl.cdiv(pages_of(b), n_pages)
        q = q_ref[b]  # [H, hd]

        def block(i, carry):
            m, l, acc, buf = carry
            last = i + 1 == n_blocks
            nb = jnp.where(last, next_slot(b + 1), b)
            ni = jnp.where(last, 0, i + 1)

            @pl.when(nb < B)
            def _():
                start(nb, ni, 1 - buf)

            wait(b, i, buf)
            k = kbuf[buf].reshape(rows, hd)
            v = vbuf[buf].reshape(rows, hd)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale  # [H, rows]
            ok = jnp.logical_and(
                head_ok, i * (n_pages * PS) + row_tok < length)
            s = jnp.where(ok, s, _NEG_BIG)
            m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            fix = jnp.exp(m - m_new)
            l = l * fix + p.sum(axis=1, keepdims=True)
            acc = acc * fix + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l, acc, 1 - buf

        m, l, acc, buf = jax.lax.fori_loop(
            0, n_blocks, block,
            (jnp.full((H, 1), _NEG_BIG, jnp.float32),
             jnp.zeros((H, 1), jnp.float32),
             jnp.zeros((H, hd), jnp.float32), buf))
        # a slot with no tokens ran no block: zeros over 1e-30 are zeros
        o_ref[b] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        return buf

    jax.lax.fori_loop(0, B, slot, jnp.int32(0))


def paged_decode_attention(q, kpool, vpool, layer, page_tables, lengths, *,
                           interpret: bool | None = None):
    """Attention of one query row a slot over the slot's pages, in place.

    q: [B, H, hd]; kpool, vpool: [L, P, PS, KV, hd] (handed over whole; they
    stay in HBM); layer: int32 scalar, the pool layer to read; page_tables:
    [B, MAXP] int32 pool rows in position order (entries past a slot's live
    pages are never fetched); lengths: [B] int32 tokens to attend, the
    query's own position included — 0 for an inactive slot, which fetches
    nothing and gets zeros. A length past MAXP * PS attends the whole table.
    Returns [B, H, hd] in q's dtype. H // KV query heads share a KV head,
    read from the shapes (KV == H is plain multi-head attention). The
    kernel compiles for the TPU and is interpreted anywhere else."""
    H, KV = q.shape[1], kpool.shape[3]
    if H % KV:
        raise ValueError(f"{H} query heads do not group over {KV} KV heads")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _paged_decode_attention(
        q, kpool, vpool, jnp.asarray(layer, jnp.int32), page_tables, lengths,
        interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_decode_attention(q, kpool, vpool, layer, page_tables, lengths, *,
                            interpret: bool):
    """A jit of its own, with the layer a traced scalar: the layers of a
    program are then call sites of ONE traced and lowered kernel. Traced a
    layer each, a 13-layer decode program took 6-7 s to lower (about 14 s
    on the chip machine's host) before the compile cache was even asked:
    100 s of a replica's set-up over its 7 decode programs (PERF.md, PR 28)."""
    B, H, hd = q.shape
    L, P, PS, KV, _ = kpool.shape
    MAXP = page_tables.shape[1]
    n_pages = max(1, min(_BLOCK_TOKENS // PS, MAXP))
    kernel = functools.partial(
        _kernel, sm_scale=1.0 / math.sqrt(hd), n_pages=n_pages)
    buf = pltpu.VMEM((2, n_pages, PS, KV, hd), kpool.dtype)
    window = B * MAXP * PS * KV * hd * kpool.dtype.itemsize  # at most, K or V
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[
                pl.BlockSpec(q.shape, lambda i, *_: (0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(q.shape, lambda i, *_: (0, 0, 0)),
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2))],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=4 * B * H * MAXP * PS * hd, transcendentals=B * H * MAXP * PS,
            bytes_accessed=2 * window),
        interpret=interpret,
    )(layer.reshape(1),
      page_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q, kpool, vpool)

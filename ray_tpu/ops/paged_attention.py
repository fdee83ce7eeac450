"""Pallas TPU paged decode attention — the cache read where it lies.

One query row a slot attends the slot's cached positions straight out of the
engine's page pools: the pools stay in HBM, the kernel walks the slot's page
table and fetches only the pages that hold tokens. Nothing is sliced out of a
pool, no ``[B, MAXP * PS, ...]`` window is gathered, and no position past a
slot's length is contracted. Two callers, one walk:

* ``paged_decode_attention`` — a K pool ``[L, P, PS, KV, hk]`` and a V pool
  ``[.., hv]`` (``llm/llama.py``: what ``_kv_read`` + ``_gqa_attn`` do; V may be
  narrower and a K row wider than q: below). A page of a layer is one run.
* ``paged_latent_attention`` — ONE pool ``[L, P, PS, W]`` whose rows are the
  keys as they lie and whose first ``v_width`` lanes are the values (MLA's
  latent cache ``[c | k_rope]``, ``llm/mla_moe.py``; the window
  ``pool[i][page_tables]`` + ``mla_attend_window`` is the reference). A
  fetched page serves both products out of VMEM, so a live row crosses HBM
  once. The scale is the caller's: the model's head width is not a shape
  the kernel sees.

Which of the two a call is shows in the arguments: the number of pools, the
pool's rank (a 4-D pool is one KV head), the output's width. Two more forms of
the K and V walk, an argument each, that differ in what they MASK: ``starts``
(a window's: the table a ring, the walk from the first page within reach) and
``selected`` (a learned sparse attention's: every live page walked, the rows
the model did not pick masked). And one more way to give a walk out: as a
PART of a softmax that runs over more than one table
(``paged_attention_part``: the plain walk or the ring's, with its running
maximum and sum beside its output; ``merge_attention_parts`` joins such parts
exactly).

Keys may be wider than values (192 | 128 lanes, ``llm/sink_moe.py``):

* Each pool has a VMEM buffer as wide as its OWN rows (``_buffers``); the
  output is as wide as a value (``v_width`` from the V pool); the scores are
  over ``sqrt`` of the QUERY's width.
* A K row may be wider than the query — a 192-lane head kept in the 256 lanes
  the device's layout would pad it to anyway (``benchmarks/
  sizing_sink_moe.py --layout``), the lanes past the head's zeros — and the
  query is padded with zeros to meet it: a page is then whole lane tiles, one
  plain run, and a walk's copy of it one descriptor.
* A learned SINK, one score a query head that takes mass and gives no value,
  needs nothing of the kernel: it is the part ``(0, sink, 1)`` beside the
  walk's own ``paged_attention_part``, joined by ``merge_attention_parts``.

One kernel invocation serves every slot: a work list of (slot, block) items
runs through two VMEM buffers — the next item's page copies are in flight
while this one is used, across slot boundaries too, so only the first block
of a program waits for its pages. **Every walk over a K and a V pool fetches
and reads a block the same way**, whatever it masks: a block is as many pages
as make 1 MB of K and V (``kv_block``: 1,024 tokens at 2 KV heads of 128 bf16
lanes, 256 at 8, 64 at 32; the latent pool's is 512 tokens); where the
block's table entries — or those of a sub-run of 8 pages of it — lie one
after the other in the pool and all hold tokens, the block (sub-run) is ONE
copy a pool, with the page-by-page code out of its path (which entries follow
each other is the table's alone and found once a program, ``run_lengths``,
for a ring as for a table read from its start); the pages land as the rows
they are, ``[2, tokens * KV, width]`` with no page axis, and are read as the
32-bit words they lie in (``block_rows``). The latent pool's rows come with
padding (576 lanes in 640) and need the lane slice a page first: that walk
keeps a page axis and the page-by-page copies.
Per block the fetched pages are read as ``[tokens * KV, width]`` rows, as they
lie: the H query heads meet ALL rows in one matmul and a head mask keeps,
for query head h, the rows of KV head ``h // G`` (G = H // KV, read from the
shapes) — the G rows of a KV head against that head's keys, without a
strided load or a transpose of the block. The MXU's time is set by the K and
V tiles it has to hold, which are the same either way. Online softmax in
float32 (running maximum, sum, accumulator); the probabilities are cast to
the pool's dtype for w·V as ``_gqa_attn`` and ``mla_attend_window`` cast them.

The layer index, the page tables and the lengths are scalar-prefetched, and
each entry point is a jit of its own, so the call sites of a program's layers
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
# a compute block, which is also what is in flight while one is used: 1 MB of
# K and V whatever the number of KV heads (256 tokens at 8 x 128 bf16, 512 at
# 4: alone on the chip 2,103 us a call against 2,192 at 256, PERF.md, PR 33;
# 64 at 32: PR 40; 1,024 at 2: PR 41), 0.66 MB of 576-wide latent rows as they
# lie padded (at 256 tokens, 0.33 MB in flight kept the page copies at 60 % of
# the bandwidth with no arithmetic at all: PERF.md, PR 30)
_BLOCK_BYTES = 1 << 20
_LATENT_BLOCK_TOKENS = 512
# pages a sub-run: what a block that is not one run, or whose last pages hold
# no tokens yet, still fetches as one copy
_RUN_PAGES = 8


def run_lengths(page_tables):
    """How long the run of the pool that ends at each table entry is, less
    one: int32 [B, MAXP], entry e the number of entries just before it that
    lie one after the other in the pool up to it. Entries ``[e, e + n)`` are
    ONE copy where ``run_lengths[e + n - 1] >= n - 1``, wherever in the table
    a walk's block starts — a ring's starts anywhere. Plain XLA over the
    table alone, so a program makes it ONCE for all its steps and layers and
    the kernel scalar-prefetches it (``runs=``): what is left to the kernel
    is what moves with the step, whether those pages hold tokens yet."""
    t = page_tables.astype(jnp.int32)
    e = jnp.arange(t.shape[1], dtype=jnp.int32)[None, :]
    breaks = jnp.concatenate(
        [jnp.ones((t.shape[0], 1), bool), t[:, 1:] != t[:, :-1] + 1], axis=1)
    return e - jax.lax.cummax(jnp.where(breaks, e, 0), axis=1)


def kv_block(pool, MAXP: int, vpool=None):
    """(pages a block, pages a sub-run) of a walk over a K pool like ``pool``
    and a V pool like ``vpool`` (None: like ``pool``) under a table of ``MAXP``
    entries: ``_BLOCK_BYTES`` of K and V rows as they lie (``_block_pages``)."""
    PS, KV, v = pool.shape[2], pool.shape[3], pool if vpool is None else vpool
    token = KV * (_lanes(pool) + _lanes(v)) * pool.dtype.itemsize
    n_pages = _block_pages(_BLOCK_BYTES // token // PS, MAXP)
    return n_pages, _RUN_PAGES if n_pages % _RUN_PAGES == 0 else n_pages


def _lanes(pool) -> int:
    """The lanes a row of ``pool`` lies in: whole tiles of 128."""
    return -(-pool.shape[-1] // 128) * 128


def _block_pages(n_pages: int, MAXP: int) -> int:
    """``n_pages`` by the bytes, in whole sub-runs where that is more than
    one (rows of unequal width: 21 pages at 4 KV heads of 256 | 128 lanes are
    16; every power of two stays what it is), at least 1, at most the table."""
    if n_pages > _RUN_PAGES:
        n_pages -= n_pages % _RUN_PAGES
    return max(1, min(n_pages, MAXP))


def walk_copies(runs, unit: int, pages_live):
    """(units of ``unit`` pages a walk from the table's start fetched, those
    of them fetched as ONE copy or inside one) for slots holding
    ``pages_live`` [B] pages under tables whose ``run_lengths`` are ``runs``:
    the kernel's own rule — a unit is one copy where all its pages hold
    tokens and its entries lie one after the other in the pool."""
    pages_live = jnp.minimum(pages_live, runs.shape[1])
    last = jnp.arange(unit - 1, runs.shape[1], unit)  # a whole unit's last entry
    whole = last[None, :] < pages_live[:, None]
    return ((-(-pages_live // unit)).sum(),
            (whole & (runs[:, last] >= unit - 1)).sum())


def block_rows(buf, cur):
    """Block ``cur`` of a VMEM buffer [blocks, rows, lanes] as a value. Rows
    of 16 bits are read as the 32-bit words they lie in — two rows a word,
    a whole vector register's worth a load — and taken apart in registers:
    read as 16-bit rows Mosaic loads half a register a tile of 8 rows and
    shuffles two of them into one for the MXU, 160 operations a block of 512
    rows, which is what a walk then waits for (PERF.md, PR 34)."""
    if buf.dtype.itemsize != 2 or buf.shape[1] % 16:
        return buf[cur]
    return pltpu.bitcast(buf.bitcast(jnp.uint32)[cur], buf.dtype)


def _kernel(layer_ref, tables_ref, lengths_ref, *refs,
            sm_scale: float, n_pages: int, sub: int | None = None,
            ring: bool = False, select: bool = False, parts: bool = False):
    # refs: [the starts, where the table is a ring,] [the table's run
    # lengths, where a run of pages lands as one copy: ``sub`` pages a
    # sub-run,] [the positions picked, where the model picks them,] the
    # queries, the pools (HBM), the output [and, of a walk that is a PART of
    # a softmax, its running maximum and sum], a VMEM buffer a pool, the
    # semaphores
    if ring:
        starts_ref, *refs = refs
    if sub:
        runs_ref, *refs = refs
    if select:
        sel_ref, *refs = refs
    q_ref, *refs = refs
    n_outs = 3 if parts else 1
    n_pools = (len(refs) - 1 - n_outs) // 2
    pools, o_ref = refs[:n_pools], refs[n_pools]
    bufs, sems = refs[n_pools + n_outs:-1], refs[-1]
    B, H, lanes = q_ref.shape  # the rows' width, and what pads it in HBM
    PS, width = pools[0].shape[2], pools[0].shape[-1]
    KV = pools[0].shape[3] if len(pools[0].shape) == 5 else 1
    v_width = o_ref.shape[-1]
    MAXP = tables_ref.shape[1]
    G = H // KV
    page_rows = PS * KV
    rows = n_pages * page_rows  # of one block, token-major then KV head
    layer = layer_ref[0]

    # a page with its rows' padding, where they have any (_walk_pools)
    whole_rows = (() if lanes == width else
                  (slice(None),) * (len(pools[0].shape) - 3)
                  + (pl.ds(0, lanes),))

    def first_page(b):
        """The page the walk starts at: 0, or the one that holds the slot's
        first position within reach (a ring table)."""
        return starts_ref[b] // PS if ring else 0

    def pages_of(b):
        """How many pages the walk of slot ``b`` fetches."""
        if ring:  # from the first position within reach to the last
            return jnp.minimum(
                pl.cdiv(lengths_ref[b], PS) - first_page(b), MAXP)
        return jnp.minimum(pl.cdiv(lengths_ref[b], PS), MAXP)

    def landing(kv: int, buf, j, page, n: int = 1):
        """The copy of pages [page, page + n) of pool ``kv`` to pages [j,
        j + n) of block ``buf`` of its buffer: as the rows they are on both
        sides, or (the latent pool's, with their padding) one page under a
        page axis."""
        pool, dst, wide = pools[kv], bufs[kv], pools[kv].shape[-1]  # V's own
        if sub:
            src = pool.reshape(pool.shape[0], pool.shape[1] * page_rows, wide
                               ).at[layer, pl.ds(page * page_rows, n * page_rows)]
            to = dst.at[buf, pl.ds(j * page_rows, n * page_rows)]
        else:
            src, to = pool.at[(layer, page, *whole_rows)], dst.at[buf, j]
        return pltpu.make_async_copy(src, to, sems.at[kv, buf])

    def transfer(b, i, buf, how: str):
        """Start, or wait for, the copies of block ``i`` of slot ``b``: ONE
        a pool where its pages all hold tokens and lie one after the other
        in the pool; else one a sub-run of ``sub`` pages of which the same
        holds, and one a page that holds tokens for the rest (dead pages are
        not fetched; what the buffer held before stays there, masked). The
        page-by-page code — a table entry, a bound, a descriptor and a
        branch a page and pool — is what a walk waits for at 8 and 16 KB
        pages, not the bytes (PERF.md, PRs 33 and 41), and an allocator that
        draws from the front of a free list hands a slot its pages in runs;
        a run skips that code altogether."""
        live = pages_of(b) - i * n_pages  # of this block's pages hold tokens
        if ring:  # page p of the sequence lies at entry p mod the table
            e0 = (first_page(b) + i * n_pages) % MAXP

        def entry(j: int):
            """Where page ``j`` of the block lies in the table (a block is
            no longer than the table: a ring wraps at most once in it)."""
            if not ring:
                return jnp.minimum(i * n_pages + j, MAXP - 1)
            return jnp.where(e0 + j >= MAXP, e0 + j - MAXP, e0 + j)

        def by_page(lo: int, hi: int):
            for j in range(lo, hi):
                page = tables_ref[b, entry(j)]
                for kv in range(n_pools):
                    pl.when(j < live)(getattr(landing(kv, buf, j, page), how))

        if not sub:
            return by_page(0, n_pages)

        def is_run(j: int, n: int):
            """Pages [j, j + n) of the block all hold tokens, and their
            entries lie one after the other in the table (a ring's do not
            past its last) and, found once a program, in the pool."""
            e = entry(j)
            at = jnp.minimum(e + n - 1, MAXP - 1)
            ok = jnp.logical_and(live >= j + n, runs_ref[b, at] >= n - 1)
            return jnp.logical_and(ok, e + n <= MAXP) if ring else ok

        def run(j: int, n: int):
            first = tables_ref[b, entry(j)]
            for kv in range(n_pools):
                getattr(landing(kv, buf, j, first, n), how)()

        whole = is_run(0, n_pages)
        pl.when(whole)(functools.partial(run, 0, n_pages))

        @pl.when(jnp.logical_not(whole))
        def _():
            if sub == n_pages:
                return by_page(0, n_pages)
            for j in range(0, n_pages, sub):
                one = is_run(j, sub)
                pl.when(one)(functools.partial(run, j, sub))
                pl.when(jnp.logical_and(jnp.logical_not(one), live > j))(
                    functools.partial(by_page, j, j + sub))

    def start(b, i, buf):
        transfer(b, i, buf, "start")

    def wait(b, i, buf):
        transfer(b, i, buf, "wait")

    def next_slot(b):
        """The first slot at or after ``b`` that holds tokens, or B."""
        return jax.lax.while_loop(
            lambda s: jnp.logical_and(
                s < B, lengths_ref[jnp.minimum(s, B - 1)] <= 0),
            lambda s: s + 1, b)

    # dead pages of a block are masked, not fetched: whatever the buffers
    # hold there has to be finite for the w·V product
    for dst in bufs:
        dst[...] = jnp.zeros_like(dst)

    first = next_slot(jnp.int32(0))

    @pl.when(first < B)
    def _():
        start(first, 0, 0)

    # which KV head a row of the block belongs to, against each query head's
    row = jax.lax.broadcasted_iota(jnp.int32, (H, rows), 1)
    row_tok = row // KV
    head_ok = row % KV == jax.lax.broadcasted_iota(jnp.int32, (H, rows), 0) // G

    if lanes > width:  # the last lane tile, [lo, lanes), holds the padding
        lo = width // 128 * 128
        pad_ok = jax.lax.broadcasted_iota(
            jnp.int32, (rows, lanes - lo), 1) < width - lo

    def slot(b, buf):
        if ring:  # positions [reach_lo, length), counted from the first page
            base = first_page(b) * PS
            reach_lo, length = starts_ref[b] - base, lengths_ref[b] - base
        else:
            length = jnp.minimum(lengths_ref[b], MAXP * PS)
        n_blocks = pl.cdiv(pages_of(b), n_pages)
        q = q_ref[b]  # [H, lanes]

        def block(i, carry):
            m, l, acc, buf = carry
            last = i + 1 == n_blocks
            nb = jnp.where(last, next_slot(b + 1), b)
            ni = jnp.where(last, 0, i + 1)

            @pl.when(nb < B)
            def _():
                start(nb, ni, 1 - buf)

            wait(b, i, buf)
            k = (block_rows(bufs[0], buf) if sub
                 else bufs[0][buf].reshape(rows, lanes))
            if lanes > width:
                # the last lane tile came with the array's padding, which
                # may hold anything: zeros there, to meet q's zeros
                k = jnp.concatenate(
                    [k[:, :lo], jnp.where(pad_ok, k[:, lo:], 0)], axis=1)
            # one pool: the values are the leading lanes of the rows fetched
            v = (block_rows(bufs[1], buf) if sub
                 else bufs[1][buf].reshape(rows, lanes) if n_pools == 2
                 else k[:, :v_width])
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale  # [H, rows]
            ok = jnp.logical_and(
                head_ok, i * (n_pages * PS) + row_tok < length)
            if ring:
                ok = jnp.logical_and(
                    ok, i * (n_pages * PS) + row_tok >= reach_lo)
            if select:  # [1, rows]: this slot's picks, a row as the block's
                ok = jnp.logical_and(ok, sel_ref[pl.ds(b, 1), pl.ds(
                    pl.multiple_of(i * rows, rows), rows)] > 0.5)
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
             jnp.zeros((H, v_width), jnp.float32), buf))
        if parts:  # a lane tile each: what joins this walk to another
            m_ref, l_ref = refs[n_pools + 1:n_pools + 3]
            m_ref[b] = jnp.broadcast_to(m, m_ref.shape[1:])
            l_ref[b] = jnp.broadcast_to(l, l_ref.shape[1:])
        # a slot with no tokens ran no block: zeros over 1e-30 are zeros
        o_ref[b] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        return buf

    jax.lax.fori_loop(0, B, slot, jnp.int32(0))


def paged_decode_attention(q, kpool, vpool, layer, page_tables, lengths, *,
                           starts=None, selected=None, runs=None,
                           interpret: bool | None = None):
    """Attention of one query row a slot over the slot's pages, in place.

    q: [B, H, hd]; kpool: [L, P, PS, KV, hk >= hd], vpool: [L, P, PS, KV, hv]
    (whole, in HBM); layer: int32 scalar, the pool layer to read; page_tables:
    [B, MAXP] int32 pool rows in position order (entries past a slot's live
    pages are never fetched); lengths: [B] int32 tokens to attend, the
    query's own position included — 0 for an inactive slot, which fetches
    nothing and gets zeros. A length past MAXP * PS attends the whole table.
    Returns [B, H, hv] in q's dtype. H // KV query heads share a KV head,
    read from the shapes (KV == H is plain multi-head attention). The
    kernel compiles for the TPU and is interpreted anywhere else.

    ``runs`` is the table's ``run_lengths``, for a program that makes it once
    and calls this a layer a step; made here where it is not given.

    ``starts`` [B] int32 makes the call a WINDOW's: a slot attends positions
    ``[starts, lengths)`` and its table is a ring — the page of positions
    ``[p * PS, (p + 1) * PS)`` lies at entry ``p % MAXP``, so a table of
    ``window / PS + 1`` entries serves a sequence of any length. The walk
    begins at the page that holds ``starts``: nothing before it is fetched,
    and of that one page the positions before ``starts`` are masked.

    ``selected`` [B, MAXP * PS] bool makes the call a learned sparse
    attention's: a slot attends, of its ``lengths`` positions, those where
    ``selected`` — the softmax runs over them alone. The walk still fetches
    every live page (the picks of a scattered selection touch nearly all of
    them) and masks the rows not picked."""
    args, static = _kv_call(q, kpool, vpool, layer, page_tables, lengths,
                            runs, interpret)
    if starts is not None:
        return _paged_window_attention(*args, starts, **static)
    if selected is not None:
        return _paged_selected_attention(*args, selected, **static)
    return _paged_decode_attention(*args, **static)


def _kv_call(q, kpool, vpool, layer, page_tables, lengths, runs, interpret):
    """The arguments every K and V entry hands its jit, and the static ones:
    the block follows from the shapes, here, where a test can see it move."""
    H, KV = q.shape[1], kpool.shape[3]
    if H % KV:
        raise ValueError(f"{H} query heads do not group over {KV} KV heads")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if runs is None:
        runs = run_lengths(page_tables)
    return ((q, kpool, vpool, jnp.asarray(layer, jnp.int32), page_tables,
             lengths, runs),
            {"block": kv_block(kpool, page_tables.shape[1], vpool),
             "interpret": bool(interpret)})


def _kv_walk(q, kpool, vpool, layer, page_tables, lengths, runs, block,
             interpret, **form):
    hd, hv = q.shape[-1], vpool.shape[-1]  # the scores' width, the output's
    return _walk_pools(q, (kpool, vpool), layer, page_tables, lengths,
                       v_width=hv, sm_scale=1.0 / math.sqrt(hd), block=block,
                       interpret=interpret, runs=runs, **form)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _paged_decode_attention(q, kpool, vpool, layer, page_tables, lengths,
                            runs, *, block, interpret: bool):
    """A jit of its own, with the layer a traced scalar: the layers of a
    program are then call sites of ONE traced and lowered kernel. Traced a
    layer each, a 13-layer decode program took 6-7 s to lower (about 14 s
    on the chip machine's host) before the compile cache was even asked:
    100 s of a replica's set-up over its 7 decode programs (PERF.md, PR 28)."""
    return _kv_walk(q, kpool, vpool, layer, page_tables, lengths, runs,
                    block, interpret)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _paged_window_attention(q, kpool, vpool, layer, page_tables, lengths,
                            runs, starts, *, block, interpret: bool):
    """A jit of its own for the reason ``_paged_decode_attention`` is one."""
    return _kv_walk(q, kpool, vpool, layer, page_tables, lengths, runs,
                    block, interpret, starts=starts)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _paged_selected_attention(q, kpool, vpool, layer, page_tables, lengths,
                              runs, selected, *, block, interpret: bool):
    """A jit of its own for the reason ``_paged_decode_attention`` is one."""
    return _kv_walk(q, kpool, vpool, layer, page_tables, lengths, runs,
                    block, interpret, selected=selected)


def paged_latent_attention(q, pool, layer, page_tables, lengths, *,
                           v_width: int, sm_scale: float,
                           interpret: bool | None = None):
    """``paged_decode_attention`` over ONE pool whose rows are keys and, in
    their first ``v_width`` lanes, values: MLA's absorbed decode attention
    over the latent cache.

    q: [B, H, W], the query carried into the rows' space; pool: [L, P, PS, W]
    (whole, in HBM), one KV head that all H query heads attend; ``sm_scale``
    multiplies the scores (the model's, not ``1 / sqrt(W)``); layer,
    page_tables, lengths as there. Returns the probabilities' sum of the
    rows' values, [B, H, v_width] in q's dtype; zeros for an inactive slot."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _paged_latent_attention(
        q, pool, jnp.asarray(layer, jnp.int32), page_tables, lengths,
        v_width=int(v_width), sm_scale=float(sm_scale),
        interpret=bool(interpret))


@functools.partial(jax.jit,
                   static_argnames=("v_width", "sm_scale", "interpret"))
def _paged_latent_attention(q, pool, layer, page_tables, lengths, *,
                            v_width: int, sm_scale: float, interpret: bool):
    """A jit of its own for the reason ``_paged_decode_attention`` is one."""
    n_pages = max(1, min(_LATENT_BLOCK_TOKENS // pool.shape[2],
                         page_tables.shape[1]))
    return _walk_pools(q, (pool,), layer, page_tables, lengths,
                       v_width=v_width, sm_scale=sm_scale,
                       block=(n_pages, None), interpret=interpret)


def _walk_pools(q, pools, layer, page_tables, lengths, *, v_width: int,
                sm_scale: float, block, interpret: bool,
                starts=None, selected=None, runs=None, parts: bool = False):
    """The one ``pallas_call`` every entry makes: the pools stay where they
    are (``pl.ANY``), a VMEM buffer of two blocks a pool. ``block`` is
    (pages a block, pages a sub-run: ``kv_block``; none for the latent
    pool, whose rows come with padding). ``starts`` is one more
    scalar-prefetched array, and a ring table (``_kernel``); so is ``runs``,
    the table's ``run_lengths``.
    ``selected`` is one more input in VMEM: float 0 / 1 a ROW of the blocks
    (a position's pick repeated over its KV heads here, in XLA: a repeat
    that interleaves lanes is no vector operation of the kernel's), whole
    blocks a slot — 8 MB at 32 slots of 16,384 positions of 4 KV heads.
    ``parts``: the output in float32 and, beside it, the walk's running
    maximum and sum ``[B, H, 128]`` (a lane tile each, every lane the same)."""
    B, H, _ = q.shape
    PS, page = pools[0].shape[2], pools[0].shape[2:]
    MAXP, width = page_tables.shape[1], page[-1]  # the rows': at least q's
    # Rows whose width is not whole lane tiles (MLA's 576 = 4.5 x 128) lie in
    # HBM padded to whole ones, and Mosaic takes no slice of a tiled axis that
    # is not whole tiles, the whole axis included ("Slice shape along
    # dimension 3 must be aligned to tiling (128), but is 576"). So the
    # compiled kernel copies a page's rows WITH their padding — the same run
    # of HBM — and masks it; q gets zeros there. The interpreter has no
    # padding to fetch and none to mask.
    lanes = width if interpret else -(-width // 128) * 128
    if lanes > q.shape[-1]:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, lanes - q.shape[-1])))
    n_pages, sub = block
    kernel = functools.partial(_kernel, sm_scale=sm_scale, n_pages=n_pages,
                               sub=sub, ring=starts is not None,
                               select=selected is not None, parts=parts)
    prefetch = [a.astype(jnp.int32) for a in (
        starts, runs if sub else None) if a is not None]
    picks = ()
    if selected is not None:
        n_blocks, KV = -(-MAXP // n_pages), math.prod(page[1:-1])
        picks = (jnp.repeat(jnp.pad(selected.astype(jnp.float32), (
            (0, 0), (0, n_blocks * n_pages * PS - selected.shape[1]))),
            KV, axis=1),)
    # two blocks a pool: of rows, or of pages of rows with their padding (a
    # pool narrower than the first, values under wider keys, as its own rows
    # are: ``_buffers``); and, at most, over the pools: every slot's table
    bufs, window = _buffers(pools, n_pages, lanes, bool(sub))
    window *= B * MAXP
    out = jax.ShapeDtypeStruct((B, H, v_width), q.dtype)
    outs = out
    if parts:
        out = jax.ShapeDtypeStruct((B, H, v_width), jnp.float32)
        outs = [out] + [jax.ShapeDtypeStruct((B, H, 128), jnp.float32)] * 2
    # what the kernel holds in VMEM beside its blocks: over the default
    # limit's room with a long table's picks
    held = sum(p.size * p.dtype.itemsize for p in picks)
    return pl.pallas_call(
        kernel,
        out_shape=outs,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3 + len(prefetch),
            grid=(1,),
            in_specs=[pl.BlockSpec(p.shape, lambda i, *_: (0, 0)) for p in picks]
            + [pl.BlockSpec(q.shape, lambda i, *_: (0, 0, 0))]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=pl.BlockSpec(out.shape, lambda i, *_: (0, 0, 0))
            if not parts else
            [pl.BlockSpec(o.shape, lambda i, *_: (0, 0, 0)) for o in outs],
            scratch_shapes=bufs
            + [pltpu.SemaphoreType.DMA((len(pools), 2))],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            **({"vmem_limit_bytes": 64 * 1024 * 1024}
               if held > 4 * 1024 * 1024 else {})),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * H * MAXP * PS * (width + v_width),
            transcendentals=B * H * MAXP * PS,
            bytes_accessed=window),
        interpret=interpret,
        name=None if not parts else
        "paged_attention_part" if starts is None else "paged_window_part",
    )(layer.reshape(1),
      page_tables.astype(jnp.int32), lengths.astype(jnp.int32), *prefetch,
      *picks, q, *pools)


def _buffers(pools, n_pages: int, lanes: int, sub: bool):
    """(a VMEM buffer of two blocks a pool, the bytes of ONE table entry's
    page over the pools): ``lanes`` wide for the first pool and every pool
    like it, as wide as its own rows for a narrower one."""
    width = pools[0].shape[-1]
    bufs = []
    for pool in pools:
        page = pool.shape[2:]
        w = lanes if page[-1] == width else page[-1]
        bufs.append(pltpu.VMEM(
            (2, n_pages * math.prod(page[:-1]), w) if sub
            else (2, n_pages, *page[:-1], w), pool.dtype))
    return bufs, sum(math.prod(p.shape[2:]) * p.dtype.itemsize for p in pools)


def paged_attention_part(q, kpool, vpool, layer, page_tables, lengths, *,
                         starts=None, runs=None,
                         interpret: bool | None = None):
    """``paged_decode_attention`` as ONE PART of a softmax that runs over
    more than one table: the same walk (plain, or a ring's with ``starts``;
    ``runs`` as there), given out with what joins it to another — ``(o [B,
    H, hv] float32, the walk's own normalised output; m [B, H] float32, its
    largest score; l [B, H] float32, the sum of ``exp(score - m)`` over its
    rows)``. A slot with no rows gives ``l = 0``. ``merge_attention_parts``
    joins them exactly."""
    args, static = _kv_call(q, kpool, vpool, layer, page_tables, lengths,
                            runs, interpret)
    if starts is None:
        o, m, l = _paged_attention_part(*args, **static)
    else:
        o, m, l = _paged_window_part(*args, starts, **static)
    return o, m[..., 0], l[..., 0]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _paged_attention_part(q, kpool, vpool, layer, page_tables, lengths, runs,
                          *, block, interpret: bool):
    """A jit of its own for the reason ``_paged_decode_attention`` is one."""
    return _kv_walk(q, kpool, vpool, layer, page_tables, lengths, runs,
                    block, interpret, parts=True)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _paged_window_part(q, kpool, vpool, layer, page_tables, lengths, runs,
                       starts, *, block, interpret: bool):
    """A jit of its own for the reason ``_paged_decode_attention`` is one."""
    return _kv_walk(q, kpool, vpool, layer, page_tables, lengths, runs,
                    block, interpret, starts=starts, parts=True)


def merge_attention_parts(*parts):
    """One softmax over the union of the rows that several walks ran over:
    each ``(o, m, l)`` of ``paged_attention_part``. Exact — part i's rows
    weigh ``l_i exp(m_i - max m)`` of the whole. Returns [B, H, hd] float32;
    zeros where no part had a row."""
    m = functools.reduce(jnp.maximum, (p[1] for p in parts))
    w = [p[2] * jnp.exp(p[1] - m) for p in parts]
    total = jnp.maximum(sum(w), 1e-30)
    return sum(p[0] * (wi / total)[..., None] for p, wi in zip(parts, w))

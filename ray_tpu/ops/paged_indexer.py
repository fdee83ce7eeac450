"""Pallas TPU paged indexer scores — a learned sparse attention's first half,
over the cache where it lies.

A decode step's query scores EVERY cached position of its slot before it
attends any: ``I[s] = sum_j w[j] . relu(qI[j] . kI[s])`` over the indexer's J
heads and its one cached key a position (``models/sparse_moe.py``). The keys
live in a page pool of their own; this kernel walks a slot's page table as
``ops/paged_attention.py`` does — the pool stays in HBM, only pages that hold
tokens are fetched, a ring of VMEM buffers so that the next blocks' copies
(half a megabyte of keys) fly while this one is scored — and writes the scores
of all slots as one float32 array for the selection (``ops/select.py``) to
read. Where a block's pages lie one after the other in the pool it is ONE
copy (a sub-run of it, one), and where that is was found from the table
before the kernel ran (``index_runs``: once a program, not once a call).

**The pool is packed.** An indexer key is ``dk`` = 64 lanes, half a lane
tile: rows of 64 would lie in HBM padded to 128 (or the device would turn the
page axis minor-most and every program would convert the pool on entry, as the
576-wide latent pool taught: PERF.md, PR 30). So a page's ``PS`` keys are
stored ``128 // dk`` to a row: ``[L, P, PS . dk // 128, 128]``, the key of
in-page offset ``o`` at row ``o % rows`` in lanes ``[(o // rows) . dk, +dk)``
(``pack_keys`` / ``unpack_keys``). One page is one contiguous 2 KB run. The
kernel scores a row's keys with ONE matmul: the J queries are laid out
block-diagonally, ``[128 // dk . J, 128]``, so that sublanes ``[h . J, +J)``
of ``q2 . rows^T`` are the J heads against the keys in lane group h.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.paged_attention import block_rows

_LANES = 128
# pages a block (64 pages of 16 are 1,024 positions, 128 KB), blocks in VMEM (one
# scored, the others in flight: 512 KB) and pages a sub-run of a block that is
# not one run
_BLOCK_PAGES = 64
_RING = 5
_RUN_PAGES = 8


def table_runs(page_tables, n_pages: int, sub: int | None = None):
    """Which blocks of a page table are runs of the pool: int32 [B, blocks of
    ``n_pages`` entries]. Bit 0: the block's entries lie one after the other
    in the pool, so the block is ONE copy where all its pages hold tokens;
    bit 1 + c: so do the ``sub`` entries of its sub-run c. Plain XLA over the
    table alone, so a program makes it ONCE for all its steps and layers and
    the kernels scalar-prefetch it: what is left to a kernel is the one
    compare that moves with the step, whether the pages hold tokens yet."""
    B, MAXP = page_tables.shape
    sub = sub or n_pages
    if n_pages % sub or n_pages // sub > 30:
        raise ValueError(f"{n_pages} pages a block do not split into at most "
                         f"30 sub-runs of {sub}")
    n_blocks = -(-MAXP // n_pages)
    # entries past the table break every run that reaches them
    t = jnp.pad(page_tables.astype(jnp.int32),
                ((0, 0), (0, n_blocks * n_pages - MAXP)), constant_values=-1)
    follows = jnp.concatenate(
        [jnp.zeros((B, 1), bool), t[:, 1:] == t[:, :-1] + 1], axis=1
    ).reshape(B, n_blocks, n_pages // sub, sub)
    inner = follows[..., 1:].all(-1)  # a sub-run's entries follow each other
    whole = jnp.logical_and(inner.all(-1), follows[..., 1:, 0].all(-1))
    bits = (inner.astype(jnp.int32) << (1 + jnp.arange(n_pages // sub))).sum(-1)
    return bits + whole.astype(jnp.int32)


def keys_per_row(dk: int, page_size: int) -> int:
    """How many keys of ``dk`` lanes share a row of the packed pool."""
    per = _LANES // dk if _LANES % dk == 0 else 1
    if page_size % per:
        raise ValueError(f"keys of {dk} lanes do not pack into rows of "
                         f"{_LANES} over pages of {page_size}")
    return per


def pack_keys(keys, page_size: int):
    """[..., n_pages * PS, dk] keys in position order -> [..., n_pages, rows,
    per * dk] pages as the pool holds them."""
    *lead, n, dk = keys.shape
    per = keys_per_row(dk, page_size)
    rows = page_size // per
    x = keys.reshape(*lead, n // page_size, per, rows, dk)
    return jnp.swapaxes(x, -3, -2).reshape(*lead, n // page_size, rows,
                                           per * dk)


def unpack_keys(pages, dk: int):
    """The inverse of ``pack_keys``: [..., n_pages, rows, per * dk] ->
    [..., n_pages * PS, dk]."""
    *lead, n, rows, lanes = pages.shape
    per = lanes // dk
    x = pages.reshape(*lead, n, rows, per, dk)
    return jnp.swapaxes(x, -3, -2).reshape(*lead, n * rows * per, dk)


def _kernel(layer_ref, tables_ref, lengths_ref, runs_ref, q_ref, w_ref, pool,
            o_ref, buf, sems, *, n_pages: int, sub: int, per: int):
    B, JJ, _ = q_ref.shape
    J = JJ // per
    ring, R, lanes = buf.shape  # R: the rows of one block
    L, P, rows, _ = pool.shape
    PS, MAXP = rows * per, tables_ref.shape[1]
    layer = layer_ref[0]
    # pages as the rows they are: a run of n pages is n * rows rows of this,
    # and lands in a buffer of rows (no page axis to fold away once there)
    rows_of = pool.reshape(L, P * rows, lanes)

    def pages_of(b):
        return jnp.minimum(pl.cdiv(lengths_ref[jnp.minimum(b, B - 1)], PS),
                           MAXP)

    def transfer(b, i, slot, how: str):
        """Start, or wait for, the copies of block ``i`` of slot ``b``: ONE
        where its pages all hold tokens and lie one after the other in the
        pool; else one a sub-run of ``sub`` pages of which the same holds,
        and one a page that holds tokens for the rest. The page-by-page code
        (a table entry, a bound, a descriptor and a branch a page) costs as
        much for a 2 KB page as for a 16 KB one, so it is what a walk waits
        for; a run skips it (PERF.md, PR 33). Where the runs are is the
        table's alone and comes found (``runs_ref``); what moves with the
        step is how many of the block's pages hold tokens."""
        live = pages_of(b) - i * n_pages
        bits = runs_ref[b, i]

        def copy(entry: int, n: int):
            first = tables_ref[b, i * n_pages + entry]
            getattr(pltpu.make_async_copy(
                rows_of.at[layer, pl.ds(first * rows, n * rows)],
                buf.at[slot, pl.ds(entry * rows, n * rows)],
                sems.at[slot]), how)()

        whole = jnp.logical_and(bits & 1 == 1, live >= n_pages)
        pl.when(whole)(lambda: copy(0, n_pages))

        @pl.when(jnp.logical_not(whole))
        def _():
            for c in range(n_pages // sub):
                run = jnp.logical_and((bits >> (1 + c)) & 1 == 1,
                                      live >= (c + 1) * sub)
                pl.when(run)(functools.partial(copy, c * sub, sub))

                @pl.when(jnp.logical_and(jnp.logical_not(run), live > c * sub))
                def _():
                    for p in range(c * sub, (c + 1) * sub):
                        pl.when(p < live)(functools.partial(copy, p, 1))

    def next_slot(b):
        return jax.lax.while_loop(
            lambda s: jnp.logical_and(
                s < B, lengths_ref[jnp.minimum(s, B - 1)] <= 0),
            lambda s: s + 1, b)

    def after(b, i):
        """The work item after block ``i`` of slot ``b``; slot B: none."""
        last = i + 1 >= pl.cdiv(pages_of(b), n_pages)
        return (jnp.where(last, next_slot(b + 1), b),
                jnp.where(last, 0, i + 1))

    # pages that hold no tokens are not fetched, blocks past a slot's last
    # are not scored: what lies there must be finite for the selection's mask
    buf[...] = jnp.zeros_like(buf)
    o_ref[...] = jnp.zeros_like(o_ref)

    def prime(k, ahead):
        pl.when(ahead[0] < B)(lambda: transfer(*ahead, k, "start"))
        return after(*ahead)

    first = (next_slot(jnp.int32(0)), jnp.int32(0))
    ahead = jax.lax.fori_loop(0, ring - 1, prime, first)

    def item(carry):
        """Score one block while the ``ring - 1`` after it are on their way:
        the copies of the last of them start into the buffer the item before
        this one was scored from."""
        b, i, ab, ai, cur = carry
        pl.when(ab < B)(lambda: transfer(
            ab, ai, jnp.where(cur == 0, ring - 1, cur - 1), "start"))
        transfer(b, i, cur, "wait")
        k = block_rows(buf, cur)
        s = jax.lax.dot_general(
            q_ref[b], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [per * J, R]
        s = jnp.maximum(s, 0.0) * w_ref[b]
        at = pl.ds(pl.multiple_of(i * R, R), R)
        for h in range(per):
            o_ref[h, pl.ds(b, 1), at] = s[h * J:(h + 1) * J].sum(
                axis=0, keepdims=True)
        return (*after(b, i), *after(ab, ai),
                jnp.where(cur == ring - 1, 0, cur + 1))

    jax.lax.while_loop(lambda c: c[0] < B, item, (*first, *ahead, jnp.int32(0)))


def index_runs(page_tables):
    """``table_runs`` at this walk's block and sub-run, [B, blocks] int32,
    and the pages a block."""
    n_pages, sub = _block(page_tables.shape[1])
    return table_runs(page_tables, n_pages, sub), n_pages


def _block(MAXP: int):
    """Pages a block and a sub-run of a table of ``MAXP`` entries."""
    n_pages = max(1, min(_BLOCK_PAGES, MAXP))
    return n_pages, _RUN_PAGES if n_pages % _RUN_PAGES == 0 else n_pages


def paged_index_scores(qi, w, pool, layer, page_tables, lengths, *,
                       runs=None, interpret: bool | None = None):
    """The indexer's score of every cached position of every slot.

    qi: [B, J, dk] the step's indexer queries; w: [B, J] float32 the heads'
    weights; pool: [L, P, rows, per * dk] the packed keys (whole, in HBM);
    layer: int32 scalar; page_tables: [B, MAXP]; lengths: [B] positions to
    score, the query's own included (0: an inactive slot, nothing fetched);
    runs: the table's ``index_runs``, for a program that makes it once and
    calls this a layer a step (made here where it is not given).
    Returns [B, MAXP * PS] float32, position order; entries at or past a
    slot's length are finite and mean nothing."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if runs is None:
        runs, _ = index_runs(page_tables)
    return _paged_index_scores(qi, w, pool, jnp.asarray(layer, jnp.int32),
                               page_tables, lengths, runs,
                               interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_index_scores(qi, w, pool, layer, page_tables, lengths, runs, *,
                        interpret: bool):
    """A jit of its own: the layers of a program are call sites of one
    traced and lowered kernel (``ops/paged_attention.py``)."""
    B, J, dk = qi.shape
    rows, lanes = pool.shape[2], pool.shape[3]
    per = lanes // dk
    MAXP = page_tables.shape[1]
    n_pages, sub = _block(MAXP)
    n_blocks = -(-MAXP // n_pages)
    R = n_pages * rows
    # the J queries block-diagonally: sublanes [h * J, +J) meet lane group h
    q2 = jnp.zeros((B, per, J, per, dk), qi.dtype)
    for h in range(per):
        q2 = q2.at[:, h, :, h].set(qi)
    q2 = q2.reshape(B, per * J, lanes).astype(pool.dtype)
    w2 = jnp.tile(w.astype(jnp.float32), (1, per))[..., None]
    out = pl.pallas_call(
        functools.partial(_kernel, n_pages=n_pages, sub=sub, per=per),
        out_shape=jax.ShapeDtypeStruct((per, B, n_blocks * R), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(1,),
            in_specs=[pl.BlockSpec(q2.shape, lambda i, *_: (0, 0, 0)),
                      pl.BlockSpec(w2.shape, lambda i, *_: (0, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((per, B, n_blocks * R),
                                   lambda i, *_: (0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((_RING, R, lanes), pool.dtype),
                pltpu.SemaphoreType.DMA((_RING,))],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * J * dk * MAXP * rows * per, transcendentals=0,
            bytes_accessed=B * MAXP * rows * lanes * pool.dtype.itemsize),
        interpret=interpret,
        name="paged_index_scores",
    )(layer.reshape(1), page_tables.astype(jnp.int32),
      lengths.astype(jnp.int32), runs.astype(jnp.int32), q2, w2, pool)
    # [lane group, B, page, row] -> position order: page, lane group, row
    out = out.reshape(per, B, n_blocks * n_pages, rows)
    return jnp.moveaxis(out, 0, 2).reshape(B, -1)[:, :MAXP * rows * per]

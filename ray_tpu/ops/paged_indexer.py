"""Pallas TPU paged indexer scores — a learned sparse attention's first half,
over the cache where it lies.

A decode step's query scores EVERY cached position of its slot before it
attends any: ``I[s] = sum_j w[j] . relu(qI[j] . kI[s])`` over the indexer's J
heads and its one cached key a position (``models/sparse_moe.py``). The keys
live in a page pool of their own; this kernel walks a slot's page table as
``ops/paged_attention.py`` does — the pool stays in HBM, only pages that hold
tokens are fetched, two VMEM buffers so that the next block's copies fly while
this one is scored — and writes the scores of all slots as one float32 array
for the selection (``ops/select.py``) to read.

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

from ray_tpu.ops.paged_attention import block_run

_LANES = 128
# pages a block: 64 pages of 16 are 1,024 positions, 128 KB in flight
_BLOCK_PAGES = 64


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


def _kernel(layer_ref, tables_ref, lengths_ref, q_ref, w_ref, pool, o_ref,
            buf, sems, *, n_pages: int, per: int):
    B, JJ, _ = q_ref.shape
    J = JJ // per
    rows, lanes = pool.shape[2], pool.shape[3]
    PS, MAXP = rows * per, tables_ref.shape[1]
    R = n_pages * rows  # rows of one block
    layer = layer_ref[0]

    def pages_of(b):
        return jnp.minimum(pl.cdiv(lengths_ref[b], PS), MAXP)

    def transfer(b, i, slot, how: str):
        """Start, or wait for, the copies of block ``i`` of slot ``b``: ONE
        where its pages all hold tokens and lie one after the other in the
        pool, else one a page that holds tokens. The page-by-page code (a
        table entry, a bound, a descriptor and a branch a page) costs as
        much for a 2 KB page as for a 16 KB one, so it is what this walk
        waits for; a block that is one run skips it (PERF.md, PR 33)."""
        live = pages_of(b)
        run, first = block_run(tables_ref, b, i, n_pages, live)

        @pl.when(run)
        def _():
            getattr(pltpu.make_async_copy(
                pool.at[layer, pl.ds(first, n_pages)], buf.at[slot],
                sems.at[slot]), how)()

        @pl.when(jnp.logical_not(run))
        def _():
            for j in range(n_pages):
                p = i * n_pages + j
                page = tables_ref[b, jnp.minimum(p, MAXP - 1)]
                pl.when(p < live)(getattr(pltpu.make_async_copy(
                    pool.at[layer, page], buf.at[slot, j], sems.at[slot]), how))

    def start(b, i, slot):
        transfer(b, i, slot, "start")

    def wait(b, i, slot):
        transfer(b, i, slot, "wait")

    def next_slot(b):
        return jax.lax.while_loop(
            lambda s: jnp.logical_and(
                s < B, lengths_ref[jnp.minimum(s, B - 1)] <= 0),
            lambda s: s + 1, b)

    # pages that hold no tokens are not fetched, blocks past a slot's last
    # are not scored: what lies there must be finite for the selection's mask
    buf[...] = jnp.zeros_like(buf)
    o_ref[...] = jnp.zeros_like(o_ref)

    first = next_slot(jnp.int32(0))

    @pl.when(first < B)
    def _():
        start(first, 0, 0)

    def slot(b, cur):
        n_blocks = pl.cdiv(pages_of(b), n_pages)
        q, w = q_ref[b], w_ref[b]  # [per * J, lanes], [per * J, 1]

        def block(i, cur):
            last = i + 1 == n_blocks
            nb = jnp.where(last, next_slot(b + 1), b)
            ni = jnp.where(last, 0, i + 1)

            @pl.when(nb < B)
            def _():
                start(nb, ni, 1 - cur)

            wait(b, i, cur)
            k = buf[cur].reshape(R, lanes)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [per * J, R]
            s = jnp.maximum(s, 0.0) * w
            at = pl.ds(pl.multiple_of(i * R, R), R)
            for h in range(per):
                o_ref[h, pl.ds(b, 1), at] = s[h * J:(h + 1) * J].sum(
                    axis=0, keepdims=True)
            return 1 - cur

        return jax.lax.fori_loop(0, n_blocks, block, cur)

    jax.lax.fori_loop(0, B, slot, jnp.int32(0))


def paged_index_scores(qi, w, pool, layer, page_tables, lengths, *,
                       interpret: bool | None = None):
    """The indexer's score of every cached position of every slot.

    qi: [B, J, dk] the step's indexer queries; w: [B, J] float32 the heads'
    weights; pool: [L, P, rows, per * dk] the packed keys (whole, in HBM);
    layer: int32 scalar; page_tables: [B, MAXP]; lengths: [B] positions to
    score, the query's own included (0: an inactive slot, nothing fetched).
    Returns [B, MAXP * PS] float32, position order; entries at or past a
    slot's length are finite and mean nothing."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _paged_index_scores(qi, w, pool, jnp.asarray(layer, jnp.int32),
                               page_tables, lengths,
                               interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_index_scores(qi, w, pool, layer, page_tables, lengths, *,
                        interpret: bool):
    """A jit of its own: the layers of a program are call sites of one
    traced and lowered kernel (``ops/paged_attention.py``)."""
    B, J, dk = qi.shape
    rows, lanes = pool.shape[2], pool.shape[3]
    per = lanes // dk
    MAXP = page_tables.shape[1]
    n_pages = max(1, min(_BLOCK_PAGES, MAXP))
    n_blocks = -(-MAXP // n_pages)
    R = n_pages * rows
    # the J queries block-diagonally: sublanes [h * J, +J) meet lane group h
    q2 = jnp.zeros((B, per, J, per, dk), qi.dtype)
    for h in range(per):
        q2 = q2.at[:, h, :, h].set(qi)
    q2 = q2.reshape(B, per * J, lanes).astype(pool.dtype)
    w2 = jnp.tile(w.astype(jnp.float32), (1, per))[..., None]
    out = pl.pallas_call(
        functools.partial(_kernel, n_pages=n_pages, per=per),
        out_shape=jax.ShapeDtypeStruct((per, B, n_blocks * R), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[pl.BlockSpec(q2.shape, lambda i, *_: (0, 0, 0)),
                      pl.BlockSpec(w2.shape, lambda i, *_: (0, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((per, B, n_blocks * R),
                                   lambda i, *_: (0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, n_pages, rows, lanes), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * J * dk * MAXP * rows * per, transcendentals=0,
            bytes_accessed=B * MAXP * rows * lanes * pool.dtype.itemsize),
        interpret=interpret,
        name="paged_index_scores",
    )(layer.reshape(1), page_tables.astype(jnp.int32),
      lengths.astype(jnp.int32), q2, w2, pool)
    # [lane group, B, page, row] -> position order: page, lane group, row
    out = out.reshape(per, B, n_blocks * n_pages, rows)
    return jnp.moveaxis(out, 0, 2).reshape(B, -1)[:, :MAXP * rows * per]

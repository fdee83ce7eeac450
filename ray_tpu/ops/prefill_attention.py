"""Pallas TPU prefill attention for grouped heads and a window — forward
only, no ``[T, T]`` array anywhere.

What ``ops/flash_attention.py`` is to the train path this is to a serving
prefill over thousands of positions: online softmax over blocks of keys, with
two things that kernel has not.

* **The heads stay grouped.** q is ``[N, T, H * hd]`` and k, v are ``[N, T,
  KV * hd]`` as the projections leave them. A grid cell is one KV head and
  one block of queries: it takes the ``G = H // KV`` query heads of that KV
  head as lane slices of one ``[block_q, G * hd]`` block and runs them, one
  after the other, against the SAME fetched block of keys and values — K and
  V cross HBM once a block of queries, not once a query head, and are never
  written out to H heads.
* **A lower bound.** ``window=W`` lets position i attend j where ``0 <= i -
  j < W``. A block of queries then visits only the blocks of keys that hold
  such j: the innermost grid axis counts from the first of them, so neither
  the blocks the window has slid past nor the ones above the diagonal are
  fetched (their index repeats the last one needed, which copies nothing)
  or computed.
* **A pick a query.** ``picked`` [N, T, T] int8 lets position i attend j only
  where ``picked[n, i, j]`` (a learned sparse attention's selected set,
  ``models/sparse_moe.py``): the mask comes a block beside the keys, one byte
  a pair, and no float ``[T, T]`` array exists. Every causal block is still
  visited: the picks of a scattered selection touch nearly all of them.
* **Keys wider than values.** k may be ``KV`` heads of ``hd`` lanes beside
  v's ``KV`` heads of ``hv`` (192 | 128, ``models/sink_moe.py``): the output
  is as wide as the values. A head that is not whole lane tiles is padded
  with zeros to them before the kernel (a 192-lane head costs the MXU its
  256 either way, and a block of one head must be whole tiles), the scores
  still over ``sqrt(hd)`` (``gqa_sink_attention``).
* **A sink.** ``sink`` [H] float is one learned score a query head that
  joins every softmax as a column with no value: the running maximum and
  sum of a block of queries START at ``(sink, 1)`` where they start at
  ``(-inf, 0)``, and nothing else of the kernel knows it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_BIG = -1e30
_LANES = 128
BLOCK_Q, BLOCK_K = 256, 512


def _key_blocks(i, block_q: int, block_k: int, window: int | None):
    """First and last block of keys that the queries of block ``i`` attend."""
    first = 0 if window is None else (
        jnp.maximum(i * block_q - (window - 1), 0) // block_k)
    return first, (i * block_q + block_q - 1) // block_k


def _kernel(q_ref, k_ref, v_ref, *refs, sm_scale: float, window: int | None,
            block_q: int, block_k: int, G: int, hd: int, picks: bool = False,
            hv: int = 0, sink: bool = False):
    # hv: a value head's lanes, the output's; the sink: [1, G, 128], a lane
    # tile a query head
    picked_ref, hv = refs[0] if picks else None, hv or hd
    sink_ref = refs[int(picks)] if sink else None
    o_ref, m_scr, l_scr, acc_scr = refs[int(picks) + int(sink):]
    i, j = pl.program_id(2), pl.program_id(3)
    first, last = _key_blocks(i, block_q, block_k, window)
    kj = first + j

    @pl.when(j == 0)
    def _init():
        m_scr[...] = (_sink_rows(sink_ref, m_scr) if sink
                      else jnp.full_like(m_scr, _NEG_BIG))
        l_scr[...] = jnp.zeros_like(l_scr) if not sink else jnp.ones_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(kj <= last)
    def _compute():
        k, v = k_ref[0], v_ref[0]  # [block_k, hd]
        rows = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        ok = rows >= cols
        if window is not None:
            ok = jnp.logical_and(ok, rows - cols < window)
        if picks:
            ok = jnp.logical_and(ok, picked_ref[0].astype(jnp.int32) != 0)
        for g in range(G):
            q = q_ref[0, :, g * hd:(g + 1) * hd]  # [block_q, hd]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            s = jnp.where(ok, s, _NEG_BIG)
            m_prev = m_scr[g, :, 0]
            m_new = jnp.maximum(m_prev, s.max(axis=1))
            p = jnp.where(ok, jnp.exp(s - m_new[:, None]), 0.0)
            fix = jnp.exp(m_prev - m_new)
            l_new = l_scr[g, :, 0] * fix + p.sum(axis=1)
            acc_scr[g] = acc_scr[g] * fix[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[g] = jnp.broadcast_to(m_new[:, None], (block_q, _LANES))
            l_scr[g] = jnp.broadcast_to(l_new[:, None], (block_q, _LANES))

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        for g in range(G):
            denom = jnp.maximum(l_scr[g, :, 0], 1e-30)
            o_ref[0, :, g * hv:(g + 1) * hv] = (
                acc_scr[g] / denom[:, None]).astype(o_ref.dtype)


def _sink_rows(sink_ref, m_scr):
    """Where a block of queries' running maximum starts under a sink: each
    query head's own score, on every row."""
    return jnp.stack([jnp.broadcast_to(sink_ref[0, g:g + 1, :], m_scr.shape[1:])
                      for g in range(m_scr.shape[0])])


def blocks_for(T: int) -> tuple[int, int] | None:
    """The (queries, keys) block sizes for ``T`` positions, or None where
    ``T`` is not whole blocks of at least 128 (the caller's plain form
    then)."""
    bq = next((b for b in (BLOCK_Q, 128) if T % b == 0), None)
    bk = next((b for b in (BLOCK_K, 256, 128) if T % b == 0), None)
    return None if bq is None or bk is None else (bq, bk)


def gqa_prefill_attention(q, k, v, *, n_kv_heads: int, window: int | None = None,
                          picked=None, interpret: bool | None = None, sink=None):
    """Causal attention of every position of a prompt over the prompt, the heads
    grouped, optionally within a window. q: [N, T, H * hd]; k: [N, T, KV * hd], v:
    [N, T, KV * hv], ``KV = n_kv_heads`` (hv a multiple of 128; hd too unless ``hv !=
    hd`` or a ``sink`` [H] is given: ``gqa_sink_attention``); query head h reads
    KV head ``h // (H // KV)``. ``window``: position i attends j where ``0 <= i - j <
    window`` (None: every ``j <= i``). ``picked``: [N, T, T] int8 (or bool), i attends
    ``j <= i`` only where set. T whole blocks (``blocks_for``). Returns [N, T, H * hv]
    in q's dtype. Compiled for the TPU, interpreted anywhere else."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if sink is not None or k.shape[-1] != v.shape[-1]:
        return gqa_sink_attention(q, k, v, sink, n_kv_heads, window, interpret, picked)
    if picked is not None:
        return _gqa_picked_attention(q, k, v, picked.astype(jnp.int8),
                                     n_kv_heads=int(n_kv_heads),
                                     interpret=bool(interpret))
    return _gqa_prefill_attention(q, k, v, n_kv_heads=int(n_kv_heads),
                                  window=None if window is None else int(window),
                                  interpret=bool(interpret))


@functools.partial(jax.jit,
                   static_argnames=("n_kv_heads", "window", "interpret"))
def _gqa_prefill_attention(q, k, v, *, n_kv_heads: int, window: int | None,
                           interpret: bool):
    """A jit of its own: the layers of a program are call sites of one
    traced and lowered kernel a kind (``ops/paged_attention.py``)."""
    return _blocked(q, k, v, None, n_kv_heads, window, interpret)


@functools.partial(jax.jit, static_argnames=("n_kv_heads", "interpret"))
def _gqa_picked_attention(q, k, v, picked, *, n_kv_heads: int,
                          interpret: bool):
    """A jit of its own for the reason ``_gqa_prefill_attention`` is one."""
    return _blocked(q, k, v, picked, n_kv_heads, None, interpret)


def gqa_sink_attention(q, k, v, sink, n_kv_heads: int, window, interpret: bool,
                       picked=None):
    """``gqa_prefill_attention`` where k's heads are wider than v's, or a
    ``sink`` [H] stands in every softmax (or both): q: [N, T, H * hd]; k: [N,
    T, KV * hd]; v: [N, T, KV * hv], any ``hd``. Returns [N, T, H * hv]."""
    if picked is not None:
        raise ValueError("picks beside a sink or unequal widths: no such form")
    return _gqa_sink_attention(
        q, k, v, sink, n_kv_heads=int(n_kv_heads),
        window=None if window is None else int(window),
        interpret=bool(interpret))


@functools.partial(jax.jit,
                   static_argnames=("n_kv_heads", "window", "interpret"))
def _gqa_sink_attention(q, k, v, sink, *, n_kv_heads: int,
                        window: int | None, interpret: bool):
    """A jit of its own for the reason ``_gqa_prefill_attention`` is one.
    Heads of q and k that are not whole lane tiles are padded to them here."""
    N, T, _ = q.shape
    hd = k.shape[-1] // n_kv_heads
    short = -hd % _LANES
    if short:
        q, k = (jnp.pad(a.reshape(N, T, -1, hd), ((0, 0),) * 3 + ((0, short),)
                        ).reshape(N, T, -1) for a in (q, k))
    return _blocked(q, k, v, None, n_kv_heads, window, interpret, sink=sink,
                    sm_scale=1.0 / math.sqrt(hd))


def _blocked(q, k, v, picked, n_kv_heads: int, window: int | None,
             interpret: bool, sink=None, sm_scale: float | None = None):
    """The one ``pallas_call`` every entry makes (``sink``, ``sm_scale``:
    ``_gqa_sink_attention``'s)."""
    N, T, HD = q.shape
    KV = n_kv_heads
    hd, hv = k.shape[-1] // KV, v.shape[-1] // KV
    G = HD // hd // KV
    bq, bk = blocks_for(T)
    # the most blocks of keys any block of queries visits
    visits = max(
        (i * bq + bq - 1) // bk
        - (0 if window is None else max(i * bq - (window - 1), 0) // bk) + 1
        for i in range(T // bq))

    def key_block(n, kv, i, j):
        first, last = _key_blocks(i, bq, bk, window)
        return n, jnp.minimum(first + j, last), kv

    kernel = functools.partial(
        _kernel, sm_scale=sm_scale or 1.0 / math.sqrt(hd), window=window, block_q=bq,
        block_k=bk, G=G, hd=hd, picks=picked is not None, hv=hv, sink=sink is not None)
    masks = (() if picked is None else (picked,)) + _sink_tiles(sink, KV, G)
    pairs = T * (T + 1) // 2 if window is None or window >= T else (
        window * (window + 1) // 2 + (T - window) * window)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((N, T, KV * G * hv), q.dtype),
        grid=(N, KV, T // bq, visits),
        in_specs=[
            pl.BlockSpec((1, bq, G * hd), lambda n, kv, i, j: (n, i, kv)),
            pl.BlockSpec((1, bk, hd), key_block),
            pl.BlockSpec((1, bk, hv), key_block),
        ] + [pl.BlockSpec((1, bq, bk), lambda n, kv, i, j: (
            n, i, key_block(n, kv, i, j)[1]))
             for _ in masks[:picked is not None]] + _sink_specs(sink, G),
        out_specs=pl.BlockSpec((1, bq, G * hv), lambda n, kv, i, j: (n, i, kv)),
        scratch_shapes=[
            pltpu.VMEM((G, bq, _LANES), jnp.float32),
            pltpu.VMEM((G, bq, _LANES), jnp.float32),
            pltpu.VMEM((G, bq, hv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        cost_estimate=pl.CostEstimate(
            flops=2 * N * KV * G * (hd + hv) * pairs,
            transcendentals=N * KV * G * pairs,
            bytes_accessed=(q.size + N * T * KV * G * hv) * q.dtype.itemsize
            + (k.size + v.size) * k.dtype.itemsize * (T // bq) * visits * bk // T),
        interpret=interpret,
        name="gqa_sink_prefill_attention" if sink is not None else
        "gqa_prefill_attention" if picked is None else "gqa_picked_attention",
    )(q, k, v, *masks)


def _sink_tiles(sink, KV: int, G: int) -> tuple:
    """The sinks as the kernel takes them, [KV, G, 128] float32 — a lane tile
    a query head, every lane the same — or nothing."""
    if sink is None:
        return ()
    return (jnp.broadcast_to(sink.astype(jnp.float32).reshape(KV, G, 1),
                             (KV, G, _LANES)),)


def _sink_specs(sink, G: int) -> list:
    """The block of ``_sink_tiles`` a grid cell sees: its KV head's."""
    return [] if sink is None else [
        pl.BlockSpec((1, G, _LANES), lambda n, kv, i, j: (kv, 0, 0))]


# ------------------------------------------------- exact window + pooled pairs
def eva_blocks_for(T: int, window: int) -> tuple[int, int] | None:
    """``blocks_for`` where a block of queries must lie in ONE aligned window
    and a window must be whole blocks of keys; None where it cannot."""
    bq = next((b for b in (BLOCK_Q, 128) if T % b == 0 and window % b == 0), None)
    bk = next((b for b in (BLOCK_K, 256, 128)
               if T % b == 0 and window % b == 0), None)
    return None if bq is None or bk is None else (bq, bk)


def _eva_visits(i, block_q: int, block_k: int, window: int, pairs: int):
    """Of the queries of block ``i``: the first and last block of exact keys
    (from their window's start to the diagonal), and how many blocks of
    ``_LANES`` pooled pairs — those of every earlier window — follow."""
    w = i * block_q // window
    return (w * (window // block_k), (i * block_q + block_q - 1) // block_k,
            (w * pairs + _LANES - 1) // _LANES)


def _eva_kernel(q_ref, k_ref, v_ref, kh_ref, vh_ref, o_ref, m_scr, l_scr,
                acc_scr, *, sm_scale: float, window: int, pairs: int,
                block_q: int, block_k: int):
    i, j = pl.program_id(2), pl.program_id(3)
    first, last, n_sum = _eva_visits(i, block_q, block_k, window, pairs)
    n_win = last - first + 1

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def update(k, v, ok):
        s = jax.lax.dot_general(
            q_ref[0], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where(ok, s, _NEG_BIG)
        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.where(ok, jnp.exp(s - m_new[:, None]), 0.0)
        fix = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, 0] * fix + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * fix[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    @pl.when(j < n_win)
    def _exact():  # the window starts a block of keys: nothing before it comes
        rows = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = (first + j) * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        update(k_ref[0], v_ref[0], rows >= cols)

    @pl.when(jnp.logical_and(j >= n_win, j - n_win < n_sum))
    def _pooled():
        c = (j - n_win) * _LANES + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, _LANES), 1)
        update(kh_ref[0], vh_ref[0], c < i * block_q // window * pairs)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[:, 0], 1e-30)
        o_ref[0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def eva_prefill_attention(q, k, v, kh, vh, *, n_heads: int, window: int,
                          chunk: int, interpret: bool | None = None):
    """Attention of every position of a prompt over the exact keys of its own
    ALIGNED window (``window * (i // window) <= j <= i``) and the pooled pairs
    of every chunk of every earlier window (``c < (window / chunk) * (i //
    window)``), under one softmax; multi-head (a KV head a query head).

    q, k, v: [N, T, H * hd]; kh, vh: [N, T // chunk, H * hd], chunk c's pair
    at row c (pairs of chunks no query may see — the last window's, a pad's —
    are never scored); hd a multiple of 128, T and the window whole blocks
    (``eva_blocks_for``). Returns [N, T, H * hd] in q's dtype. A block of
    queries visits the blocks of keys from its window's start to the
    diagonal, then the blocks of 128 pairs it may see: nothing of an earlier
    window's keys is fetched and no ``[T, T]`` array exists."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _eva_prefill_attention(
        q, k, v, kh, vh, n_heads=int(n_heads), window=int(window),
        chunk=int(chunk), interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "window", "chunk", "interpret"))
def _eva_prefill_attention(q, k, v, kh, vh, *, n_heads: int, window: int,
                           chunk: int, interpret: bool):
    """A jit of its own for the reason ``_gqa_prefill_attention`` is one."""
    N, T, HD = q.shape
    H, hd, pairs = n_heads, HD // n_heads, window // chunk
    bq, bk = eva_blocks_for(T, window)
    # whole blocks of pairs: the rows added are never scored
    short = -kh.shape[1] % _LANES
    kh, vh = (jnp.pad(a, ((0, 0), (0, short), (0, 0))) for a in (kh, vh))
    n_q = T // bq

    # blocks of exact keys and of pairs that each block of queries visits
    visits = [_eva_visits(i, bq, bk, window, pairs) for i in range(n_q)]
    visits = [(last - first + 1, n_sum) for first, last, n_sum in visits]

    def key_block(n, h, i, j):
        first, last, _ = _eva_visits(i, bq, bk, window, pairs)
        return n, jnp.minimum(first + j, last), h

    def pair_block(n, h, i, j):
        first, last, n_sum = _eva_visits(i, bq, bk, window, pairs)
        return n, jnp.clip(j - (last - first + 1), 0,
                           jnp.maximum(n_sum - 1, 0)), h

    scored = sum((t % window + 1) + t // window * pairs for t in range(T))
    return pl.pallas_call(
        functools.partial(_eva_kernel, sm_scale=1.0 / math.sqrt(hd),
                          window=window, pairs=pairs, block_q=bq, block_k=bk),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(N, H, n_q, max(a + b for a, b in visits)),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda n, h, i, j: (n, i, h)),
            pl.BlockSpec((1, bk, hd), key_block),
            pl.BlockSpec((1, bk, hd), key_block),
            pl.BlockSpec((1, _LANES, hd), pair_block),
            pl.BlockSpec((1, _LANES, hd), pair_block),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda n, h, i, j: (n, i, h)),
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        cost_estimate=pl.CostEstimate(
            flops=4 * N * H * hd * scored, transcendentals=N * H * scored,
            bytes_accessed=2 * q.size * q.dtype.itemsize
            + 2 * k.dtype.itemsize * N * H * hd * sum(
                a * bk + b * _LANES for a, b in visits)),
        interpret=interpret,
        name="eva_prefill_attention",
    )(q, k, v, kh, vh)

"""Exact top-k selection as a mask, for a k too large to sort for.

``lax.top_k`` over 14,336 scores a row for the 2,048 largest is a full sort a
row on the TPU; a learned sparse attention needs it for every query of a
prompt in every layer. What the attention needs is not the sorted picks but
WHICH positions are among them, and that is the k-th largest value and a
comparison: the k-th largest is found by bisection on the scores' BITS (a
float's bits, sign folded, order as the float does: 32 compare-and-count
passes, one a bit), and the picks are everything above it plus as many of the
entries equal to it, from the lowest position up, as make k. That is
``lax.top_k``'s set bit for bit, ties included (equal scores go to the lower
position), with no sort and nothing approximate.

Two forms of one rule. ``topk_mask`` is plain XLA operations over any mask of
candidates: the reference, and what runs off the TPU. ``topk_prefix_mask`` is
what the serving programs call — a row's candidates are its entries up to a
limit (a query's own position) — and on a TPU it is ONE Pallas kernel a call:
a tile of rows stays in VMEM through the 32 passes over the bits and, for the
ties, 14 more over the positions (which of the equal entries are the lowest:
a bisection again, no prefix sum), where XLA ran each pass as operations of
its own — a hundred a call, which is also what a profiler trace of a decode
step then holds (PERF.md, PR 33).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.utils import tracing

_INT_MIN = -(1 << 31)


def _ordered_bits(scores):
    """float32 -> uint32 whose unsigned order is the floats' order, every
    finite score above 0 (which is kept for "not a candidate"). -0.0 counts
    as +0.0, as a comparison of floats has it. NaN is not a score."""
    s = scores.astype(jnp.float32)
    s = jnp.where(s == 0, jnp.float32(0), s)
    bits = jax.lax.bitcast_convert_type(s, jnp.int32)
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return jax.lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(1 << 31)


@tracing.part("select")
def topk_mask(scores, valid, k: int):
    """Which entries of the last axis are among the ``k`` largest of those
    where ``valid``: every valid one where there are at most ``k``. Equal
    scores go to the lower index. scores: [..., S] float; valid: [..., S]
    bool. Returns [..., S] bool with ``min(k, valid.sum(-1))`` set a row."""
    u = jnp.where(valid, _ordered_bits(scores), jnp.uint32(0))

    def bit(i, prefix):
        # the largest x with at least k entries >= x, a bit at a time
        cand = prefix | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = (u >= cand[..., None]).sum(-1, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, prefix)

    kth = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros(u.shape[:-1], jnp.uint32))[..., None]
    above, equal = u > kth, u == kth
    left = k - above.sum(-1, keepdims=True, dtype=jnp.int32)
    rank = jnp.cumsum(equal, axis=-1, dtype=jnp.int32)
    return valid & (above | (equal & (rank <= left)))


def _selects_in_kernel(width: int) -> bool:
    """Which form ``topk_prefix_mask`` runs, decided by what the code can see:
    the kernel on a TPU for rows of whole lane tiles, XLA operations anywhere
    else (where the kernel would be interpreted) — which thereby stay the
    kernel's reference and what the CPU tests run."""
    return jax.default_backend() == "tpu" and width % 128 == 0


def topk_prefix_mask(scores, limit, k: int, dtype=jnp.float32):
    """``topk_mask`` where a row's candidates are its entries ``0 .. limit``
    (none where ``limit`` < 0), as 0 / 1 of ``dtype``. scores: [..., S]
    float; limit: [...] int. Returns [..., S]."""
    S = scores.shape[-1]
    if _selects_in_kernel(S):
        out = _topk_prefix_mask(scores.reshape(-1, S).astype(jnp.float32),
                                limit.reshape(-1).astype(jnp.int32), k=int(k),
                                dtype=jnp.dtype(dtype), interpret=False)
        return out.reshape(scores.shape)
    valid = jnp.arange(S) <= limit[..., None]
    return topk_mask(scores, valid, k).astype(dtype)


def _kernel(s_ref, limit_ref, o_ref, *, k: int):
    s = s_ref[...]
    rows, S = s.shape
    s = jnp.where(s == 0, jnp.float32(0), s)
    bits = pltpu.bitcast(s, jnp.int32)
    # int32 whose SIGNED order is the floats' order; every float above the
    # lowest int, which is kept for "not a candidate"
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, S), 1)
    valid = col <= limit_ref[...]
    key = jnp.where(valid, key, jnp.int32(_INT_MIN))

    def count(mask):
        return jnp.sum(mask.astype(jnp.int32), axis=1, keepdims=True)

    def bit(i, prefix):
        # ``prefix``: the answer's leading bits in the order-preserving
        # UNSIGNED form (signed key with its top bit flipped)
        cand = prefix | (jnp.int32(1) << (31 - i))
        enough = count(key >= (cand ^ jnp.int32(_INT_MIN))) >= k
        return jnp.where(enough, cand, prefix)

    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros((rows, 1), jnp.int32)
                            ) ^ jnp.int32(_INT_MIN)
    above, equal = key > kth, key == kth
    left = k - count(above)
    # of the entries equal to the k-th, the ``left`` lowest positions: the
    # largest position ``last`` with fewer than ``left`` of them at or under
    # it, found a bit at a time, then everything up to ``last + 1``
    n_bits = max(1, (S - 1).bit_length())

    def place(i, last):
        step = jnp.int32(1) << (n_bits - 1 - i)
        few = count(jnp.logical_and(equal, col <= last + step)) < left
        return jnp.where(few, last + step, last)

    last = jax.lax.fori_loop(0, n_bits, place,
                             jnp.full((rows, 1), -1, jnp.int32))
    picked = jnp.logical_and(valid, jnp.logical_or(
        above, jnp.logical_and(equal, col <= last + 1)))
    o_ref[...] = jnp.where(picked, 1, 0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k", "dtype", "interpret"))
def _topk_prefix_mask(scores, limit, *, k: int, dtype, interpret: bool):
    """A jit of its own: the layers of a program are call sites of one traced
    and lowered kernel (``ops/paged_attention.py``). scores: [R, S] float32;
    limit: [R] int32."""
    R, S = scores.shape
    # rows a tile: a byte mask packs 32 rows a tile, a float one 8; a tile
    # of float32 scores stays under 2 MB of VMEM
    tile = 32 if jnp.dtype(dtype).itemsize == 1 else 8
    pad = -R % tile
    if pad:
        scores = jnp.pad(scores, ((0, pad), (0, 0)))
        limit = jnp.pad(limit, (0, pad), constant_values=-1)
    out = pl.pallas_call(
        functools.partial(_kernel, k=k),
        out_shape=jax.ShapeDtypeStruct(scores.shape, dtype),
        grid=(scores.shape[0] // tile,),
        in_specs=[pl.BlockSpec((tile, S), lambda i: (i, 0)),
                  pl.BlockSpec((tile, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile, S), lambda i: (i, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="topk_prefix_mask",
    )(scores, limit[:, None])
    return out[:R]

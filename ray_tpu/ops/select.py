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
what a decode step calls — a row's candidates are its entries up to a limit
(a query's own position) — and on a TPU it is ONE Pallas kernel a call: a
tile of rows stays in VMEM through the 32 passes over the bits and, for the
ties, up to 14 more over the positions (which of the equal entries are the
lowest: a bisection again, no prefix sum), where XLA ran each pass as
operations of its own (PERF.md, PR 33).

**The passes walk what the rows can see** (PR 37). A row picks from ``0 ..
limit``, so nothing past a tile's largest limit can be a pick: the kernel
keeps the tile's scores as order-preserving integer keys in VMEM, made once,
in column blocks, and every pass is a loop over the blocks ``0 ..
walk_blocks(largest limit)`` — a decode step's slots whose longest holds
9,000 live positions walk 9,216 columns of a 16,384-wide table, not all of
it; what lies past is never read (it may be anything) and is written 0. The
position passes run only where some row of the tile has more entries equal
to its k-th value than places left for them; otherwise every equal entry is
a pick and there is nothing to order. ``pick_walked`` is that selection over
keys already in VMEM: ``ops/prefill_picks.py`` scores into the same scratch
and calls it, so a prompt's float scores never exist in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.utils import tracing

_INT_MIN = -(1 << 31)
_LANES = 128
# columns a block of the kernel's walk: the largest that divides the width
_BLOCKS = (1024, 512, 256, 128)
# rows a tile of the kernel, whatever the mask's type (a tile of bytes is 32
# rows; a float mask's 8-row tiles took 57 us a decode step's call where 32
# rows take 40: PERF.md, PR 37), and the cells of a pass's partial sums
_TILE = 32
_COUNT_CELLS = 16 * 8 * _LANES


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
    return jax.default_backend() == "tpu" and width % _LANES == 0


def _block_for(width: int) -> int:
    return next(b for b in _BLOCKS if width % b == 0)


def walk_blocks(last, block: int, floor: int = 0):
    """Column blocks the passes of a tile of rows walk, the tile's largest
    candidate position being ``last``: those that hold ``0 .. last``; none
    where ``last`` < ``floor`` (no candidate at -1; in a prompt, a tile whose
    queries all see at most k keys picks them all unscored). The ONE rule the
    kernels' trip counts and the counters' hand counts share: ints or
    arrays."""
    if isinstance(last, int):
        return 0 if last < floor else last // block + 1
    return jnp.where(last < floor, 0, last // block + 1)


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


def _tile_tops(limit):
    """The largest limit of each tile of rows (rows past the last: -1)."""
    return jnp.pad(limit, (0, -limit.shape[0] % _TILE), constant_values=-1
                   ).reshape(-1, _TILE).max(axis=1)


def prefix_walked(limit, width: int):
    """Columns ``topk_prefix_mask``'s passes walk for rows of these limits,
    summed over the rows: a row walks what its tile's largest limit asks for
    in the kernel, the whole width in the plain form. limit: [R] int."""
    R = limit.shape[0]
    if not _selects_in_kernel(width):
        return jnp.asarray(R * width)
    block = _block_for(width)
    blocks = walk_blocks(_tile_tops(limit), block)
    return (jnp.repeat(blocks, _TILE)[:R] * block).sum()


def ordered_key(s):
    """In a kernel: float32 -> int32 whose SIGNED order is the floats' order;
    every float above the lowest int, which is kept for "not a candidate"."""
    s = jnp.where(s == 0, jnp.float32(0), s)
    bits = pltpu.bitcast(s, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def put_keys(key_ref, b, scores, limit, block: int):
    """In a kernel: column block ``b`` of a tile's keys from its scores
    [rows, block]: ``ordered_key`` where a row's candidates are (columns up
    to its ``limit`` [rows, 1]), ``_INT_MIN`` elsewhere."""
    col = b * block + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    key_ref[:, pl.ds(pl.multiple_of(b * block, block), block)] = jnp.where(
        col <= limit, ordered_key(scores), jnp.int32(_INT_MIN))


def pick_walked(key_ref, o_ref, limit, n_blocks, *, k: int, block: int):
    """In a kernel: the selection of a tile of rows whose keys lie in VMEM.
    key_ref: [rows, S] int32, of which column blocks ``0 .. n_blocks`` hold
    ``ordered_key`` of the candidates' scores and ``_INT_MIN`` elsewhere (the
    rest is never read); limit: [rows, 1] the rows' last candidate position.
    Writes o_ref [rows, S] whole: 1 at a pick, 0 elsewhere."""
    rows, S = key_ref.shape
    # a pass counts into 16 registers of partial sums, so that its adds are
    # 16 chains and not one
    lanes = min(block, max(_LANES, _COUNT_CELLS // rows))
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1)

    def at(b):
        return pl.ds(pl.multiple_of(b * block, block), block)

    def wide(x):  # [rows, 1] -> the width of the partial sums, once a pass
        return jnp.broadcast_to(x, (rows, lanes))

    def count(hit):
        """Entries a row over the walked blocks where ``hit(a run of keys,
        its first column)``."""
        def body(b, acc):
            key = key_ref[:, at(b)]
            for t in range(block // lanes):
                acc = acc + hit(key[:, t * lanes:(t + 1) * lanes],
                                b * block + t * lanes).astype(jnp.int32)
            return acc

        acc = jax.lax.fori_loop(0, n_blocks, body,
                                jnp.zeros((rows, lanes), jnp.int32))
        return jnp.sum(acc, axis=1, keepdims=True)

    def bit(i, prefix):
        # ``prefix``: the answer's leading bits in the order-preserving
        # UNSIGNED form (signed key with its top bit flipped)
        cand = prefix | (jnp.int32(1) << (31 - i))
        floor = wide(cand ^ jnp.int32(_INT_MIN))
        enough = count(lambda key, _: key >= floor) >= k
        return jnp.where(enough, cand, prefix)

    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros((rows, 1), jnp.int32)
                            ) ^ jnp.int32(_INT_MIN)
    kth_wide = wide(kth)
    left = k - count(lambda key, _: key > kth_wide)
    n_equal = count(lambda key, _: key == kth_wide)
    # of the entries equal to the k-th, the ``left`` lowest positions: the
    # largest position ``last`` with fewer than ``left`` of them at or under
    # it, found a bit at a time, then everything up to ``last + 1``. Where no
    # row has more of them than places (a row short of k candidates has none
    # to order: its k-th is no score) every one is a pick.
    n_bits = max(1, (S - 1).bit_length())

    def place(i, last):
        step = jnp.int32(1) << (n_bits - 1 - i)
        upto = wide(last + step)
        few = count(lambda key, col: jnp.logical_and(
            key == kth_wide, lane + col <= upto)) < left
        return jnp.where(few, last + step, last)

    crowded = jnp.max(jnp.where(kth == _INT_MIN, 0, n_equal - left)) > 0
    last = jax.lax.cond(
        crowded,
        lambda: jax.lax.fori_loop(0, n_bits, place,
                                  jnp.full((rows, 1), -1, jnp.int32)),
        lambda: jnp.full((rows, 1), S, jnp.int32))

    def write(b, _):
        key = key_ref[:, at(b)]
        col = b * block + jax.lax.broadcasted_iota(jnp.int32, key.shape, 1)
        picked = jnp.logical_and(col <= limit, jnp.logical_or(
            key > kth, jnp.logical_and(key == kth, col <= last + 1)))
        o_ref[:, at(b)] = jnp.where(picked, 1, 0).astype(o_ref.dtype)

    def blank(b, _):
        o_ref[:, at(b)] = jnp.zeros((rows, block), o_ref.dtype)

    jax.lax.fori_loop(0, n_blocks, write, None)
    jax.lax.fori_loop(n_blocks, S // block, blank, None)


def _kernel(top_ref, s_ref, limit_ref, o_ref, key_ref, *, k: int, block: int):
    limit = limit_ref[...]
    n_blocks = walk_blocks(top_ref[pl.program_id(0)], block)

    def keys(b, _):
        at = pl.ds(pl.multiple_of(b * block, block), block)
        put_keys(key_ref, b, s_ref[:, at], limit, block)

    jax.lax.fori_loop(0, n_blocks, keys, None)
    pick_walked(key_ref, o_ref, limit, n_blocks, k=k, block=block)


@functools.partial(jax.jit, static_argnames=("k", "dtype", "interpret"))
def _topk_prefix_mask(scores, limit, *, k: int, dtype, interpret: bool):
    """A jit of its own: the layers of a program are call sites of one traced
    and lowered kernel (``ops/paged_attention.py``). scores: [R, S] float32;
    limit: [R] int32."""
    R, S = scores.shape
    tile, block = _TILE, _block_for(S)
    pad = -R % tile
    scores = jnp.pad(scores, ((0, pad), (0, 0)))
    limit = jnp.pad(limit, (0, pad), constant_values=-1)
    out = pl.pallas_call(
        functools.partial(_kernel, k=k, block=block),
        out_shape=jax.ShapeDtypeStruct(scores.shape, dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # a tile's largest limit: its trip count
            grid=(scores.shape[0] // tile,),
            in_specs=[pl.BlockSpec((tile, S), lambda i, _: (i, 0)),
                      pl.BlockSpec((tile, 1), lambda i, _: (i, 0))],
            out_specs=pl.BlockSpec((tile, S), lambda i, _: (i, 0)),
            scratch_shapes=[pltpu.VMEM((tile, S), jnp.int32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="topk_prefix_mask",
    )(_tile_tops(limit), scores, limit[:, None])
    return out[:R]

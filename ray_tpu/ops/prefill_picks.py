"""Pallas TPU prefill picks — a learned sparse attention's selected sets over
a whole prompt, scored and selected over the TRIANGLE, in one kernel.

Query t of a prompt picks the ``k`` positions ``s <= t`` with the largest
indexer score ``I[t, s] = sum_j w[t, j] . relu(qI[t, j] . kI[s])``
(``models/sparse_moe.py``). A tile of ``ROWS`` queries ending at position
``e`` can see keys ``0 .. e`` and nothing else, so that is all the kernel
touches for it:

* ``e < k``: every query of the tile picks all it sees. No score, no pass:
  the tile's bytes are ``s <= t``.
* otherwise, for the column blocks ``0 .. e // BLOCK`` only (``ops/select.py``
  ``walk_blocks``: the trip count of every loop below): the scores of a block
  — the tile's J heads as ONE ``[J . ROWS, dk] x [dk, BLOCK]`` product on the
  MXU, float32 from the inputs as they are, relu, times the heads' weights,
  summed over the heads — go straight into VMEM as the order-preserving
  integer keys the selection bisects on; then ``pick_walked``: the 32 passes
  over the bits, the passes over positions where ties ask for them, and the
  bytes. The selected set is ``topk_mask``'s bit for bit on the scores made;
  only the order of the float32 sum over the heads is this kernel's own.

What leaves the kernel is ``[N, T, T]`` int8, written once, 0 past a tile's
bound — the array ``gqa_prefill_attention(picked=)`` takes. The float scores,
their ``[.., J, T]`` intermediate and a stack of blocks of bytes to turn
around never exist in HBM. A prompt's keys sit whole in VMEM (1.8 MB at
14,336), a tile's integer keys are ``ROWS x T x 4`` bytes beside them (7.3
MB). 128 queries a tile and 512 keys a block are the chip's answer (PERF.md,
PR 37: 32 queries a tile took 8.1 ms a layer at 14,336 where 128 take 5.7 —
the passes' loops run a quarter as often over four times the registers —
and blocks of 1,024 or 2,048 keys were within 2 % either way).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.select import pick_walked, put_keys, walk_blocks

ROWS = 128           # queries a tile: four tiles of bytes, one of the MXU's
_BLOCKS = (512, 256, 128)


def picks_block(T: int) -> int | None:
    """Columns a block for a prompt of ``T`` positions, or None where ``T``
    is not whole tiles (the caller's plain form then)."""
    if T % ROWS:
        return None
    return next((b for b in _BLOCKS if T % b == 0), None)


def columns_walked(T: int, k: int, rows: int = ROWS,
                   block: int | None = None) -> int:
    """Key columns the kernel scores and selects over for one prompt of ``T``
    positions, summed over its queries: a function of shapes alone (the
    square would be ``T . T``)."""
    block = block or picks_block(T)
    return sum(rows * block * walk_blocks(i * rows + rows - 1, block, k)
               for i in range(T // rows))


def _kernel(q_ref, w_ref, kt_ref, o_ref, key_ref, *, k: int, block: int):
    J, rows, dk = q_ref.shape[1:]
    T = o_ref.shape[2]
    first = pl.program_id(1) * rows
    pos = first + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    n_blocks = walk_blocks(first + rows - 1, block, k)

    @pl.when(n_blocks == 0)
    def _all_it_sees():
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, T), 1)
        o_ref[0] = jnp.where(col <= pos, 1, 0).astype(o_ref.dtype)

    @pl.when(n_blocks > 0)
    def _scored():
        q = q_ref[0].reshape(J * rows, dk)  # head-major: rows of head j
        w = w_ref[0]                        # [rows, J] float32

        def score(b, _):
            at = pl.ds(pl.multiple_of(b * block, block), block)
            s = jnp.dot(q, kt_ref[0, :, at],
                        preferred_element_type=jnp.float32)
            total = jnp.zeros((rows, block), jnp.float32)
            for j in range(J):
                total = total + (jnp.maximum(s[j * rows:(j + 1) * rows], 0.0)
                                 * w[:, j:j + 1])
            put_keys(key_ref, b, total, pos, block)

        jax.lax.fori_loop(0, n_blocks, score, None)
        pick_walked(key_ref, o_ref.at[0], pos, n_blocks, k=k, block=block)


def prefill_picks(qi, w, ki, k: int, *, interpret: bool | None = None):
    """Every query's selected set over its own prompt, one byte a pair.

    qi: [N, T, J, dk] the indexer's queries; w: [N, T, J] float32 the heads'
    weights; ki: [N, T, dk] its keys; ``T`` whole tiles (``picks_block``).
    Returns [N, T, T] int8: 1 where key s is among query t's ``k`` best of
    ``0 .. t`` (ties to the lower position; all of them while t < k).
    Compiled for the TPU, interpreted anywhere else."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _prefill_picks(qi, w.astype(jnp.float32), ki.astype(qi.dtype),
                          k=int(k), block=picks_block(ki.shape[1]),
                          interpret=bool(interpret))


@functools.partial(jax.jit,
                   static_argnames=("k", "block", "interpret", "rows"))
def _prefill_picks(qi, w, ki, *, k: int, block: int, interpret: bool,
                   rows: int = ROWS):
    """A jit of its own: the layers of a program are call sites of one traced
    and lowered kernel (``ops/paged_attention.py``)."""
    N, T, J, dk = qi.shape
    # the product's two sides as the MXU takes them: a tile's heads stacked
    # head-major, the keys with the positions along the lanes
    q = jnp.moveaxis(qi, 2, 1)
    kt = jnp.moveaxis(ki, 1, 2)
    walked = columns_walked(T, k, rows, block)
    return pl.pallas_call(
        functools.partial(_kernel, k=k, block=block),
        out_shape=jax.ShapeDtypeStruct((N, T, T), jnp.int8),
        grid=(N, T // rows),
        in_specs=[pl.BlockSpec((1, J, rows, dk), lambda n, i: (n, 0, i, 0)),
                  pl.BlockSpec((1, rows, J), lambda n, i: (n, i, 0)),
                  pl.BlockSpec((1, dk, T), lambda n, i: (n, 0, 0))],
        out_specs=pl.BlockSpec((1, rows, T), lambda n, i: (n, i, 0)),
        scratch_shapes=[pltpu.VMEM((rows, T), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=64 * 1024 * 1024),
        cost_estimate=pl.CostEstimate(
            flops=2 * N * J * dk * walked, transcendentals=0,
            bytes_accessed=N * T * T + qi.size * qi.dtype.itemsize),
        interpret=interpret,
        name="prefill_picks",
    )(q, w, kt)

"""Pallas TPU one-pass delta-rule decode step over a state pool where it lies.

``ops/kda.py`` ``kda_step`` is the plain form: a decay of the block's pool,
a reduction over it for ``u = S'^T k``, the rank-1 update and a second
reduction for ``o = S^T q`` — as XLA schedules them, the pool crosses HBM more
than once each way. Here a row crosses once each way, as a Mamba-2 row does in
``ops/ssm_pool.py``, whose frame this is: a grid over the rows, the block's
index prefetched for the index maps, the pool aliased from input to output so
that the other blocks of the pool and nothing else of it move. A program
fetches row ``r`` of block ``j`` (``[H, dk, dv]`` float32, 2 MB at the
published widths) and, a head at a time,

    S' = a . S          (each key lane's row of S by its own decay)
    u  = sum_k S'[k, :] . k[k]
    S  = S' + (b k) (x) (v - u)
    o  = sum_k S[k, :] . q[k]

while the next row's copy is in flight. Key lanes are the second-minor axis
and value lanes the lanes, so both reductions are sums DOWN the sublanes —
plain adds of whole registers, no cross-lane reduction and no tile turned
(Mamba-2's read-out sums over lanes and had to turn its tile) — and ``u`` and
``o`` are rows of lanes as they come.

The mathematics is ``kda_step``'s at its precision: float32 on the vector
unit, nothing through the MXU. A row whose decays are 1 and whose ``b k`` is 0
(the junk row, a row no live slot owns) is multiplied by 1 and has 0 added: it
leaves bit for bit as it entered.

What the kernel needs of a row beside its state is small (84 KB): the decays,
``k``, ``b . k`` and ``q`` as COLUMNS — laid out ``[4, dk, H]`` by plain XLA
operations in the wrapper, so that a head's ``[dk]`` values broadcast along
the state's lanes — and ``v`` as rows ``[H, dv]``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32


def _kernel(j_ref, s_ref, c_ref, v_ref, o_ref, y_ref):
    # j_ref: the block (in the index maps alone); s_ref / o_ref: [H, dk, dv]
    # one row in, the same row out; c_ref: [4, dk, H] the columns (decay, k,
    # b . k, q); v_ref, y_ref: [H, dv]
    del j_ref
    cols = [c_ref[i] for i in range(4)]
    for h in range(s_ref.shape[0]):
        a, k, bk, q = (c[:, h:h + 1] for c in cols)
        s = a * s_ref[h]
        u = jnp.sum(s * k, axis=0, keepdims=True)
        s = s + bk * (v_ref[h:h + 1, :] - u)
        o_ref[h] = s
        y_ref[h:h + 1, :] = jnp.sum(s * q, axis=0, keepdims=True)


def kda_pool_step(pool, j, q, k, v, g, beta, *, interpret: bool | None = None):
    """One position of the delta rule for every row of block ``j`` of a state
    pool, in place. pool: [L, R, H, dk, dv] float32; j: the block (int32
    scalar, traced or not); q, k: [R, H, dk]; v: [R, H, dv]; g: [R, H, dk]
    float32 (the log of the decay; 0 for a row that must not move); beta:
    [R, H] float32 (0 for such a row). Returns (pool with block j advanced,
    o [R, H, dv] float32): what ``kda_step(pool[j], ...)`` and
    ``pool.at[j].set`` give. The kernel compiles for the TPU and is
    interpreted anywhere else."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _pool_step(pool, jnp.asarray(j, jnp.int32).reshape(1), q, k, v, g,
                      beta, interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pool_step(pool, j, q, k, v, g, beta, *, interpret: bool):
    L, R, H, dk, dv = pool.shape
    q, k = q.astype(_F32), k.astype(_F32)
    cols = jnp.swapaxes(jnp.stack(
        [jnp.exp(g), k, beta[..., None] * k, q], axis=1), 2, 3)  # [R, 4, dk, H]

    def row(*tail):   # one row's whole block of a per-row input or output
        return pl.BlockSpec((None,) + tail,
                            lambda r, j: (r,) + (0,) * len(tail))

    state = pl.BlockSpec((None, None, H, dk, dv),
                         lambda r, j: (j[0], r, 0, 0, 0))
    pool, o = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R,),
            in_specs=[state, row(4, dk, H), row(H, dv)],
            out_specs=[state, row(H, dv)]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((R, H, dv), _F32)],
        # operand 1 (after the prefetched scalar) is the pool
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="kda_pool_step",
    )(j, pool, cols, v.astype(_F32))
    return pool, o

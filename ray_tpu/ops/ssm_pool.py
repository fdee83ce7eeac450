"""Pallas TPU one-pass Mamba-2 decode step over a state pool where it lies.

``ops/ssm.py`` ``ssm_step`` is the plain form: XLA makes of it an in-place
update of the block's pool (every row read and written) and then a second
pass that reads the new state again for ``y = S . C``. Here a row of the pool
crosses HBM once each way: a program fetches row ``r`` of block ``j``
(``[H, P, N]`` float32, 2 MB at the published widths), decays it, adds the
step's input, reads it out against ``C`` and writes it back, while the next
row's copy is in flight. The pool is aliased from input to output, so the
other blocks of the pool and nothing else of it move.

The mathematics is ``ssm_step``'s at its precision: the decay
``exp(dt . A)``, ``dt . x``, both products and the sum over ``N`` in float32
on the vector unit, nothing through the MXU. A row whose ``dt`` is 0 (the
junk row, a row no live slot owns) is multiplied by 1 and has 0 added: it
leaves as it entered.

What the kernel needs of a row beside its state is small (37 KB) and is laid
out for it by plain XLA operations in the wrapper: the decays as scalars, and
``dt . x`` with the HEAD axis minor — a head's ``[P]`` values are then a
column that broadcasts along the state's lanes. The read-out is a sum over
lanes; taken a register at a time its cross-lane reductions and the stores of
their one-lane results, not the copies, set the pace (0.90 ms a block of 129
rows at the published widths inside the decode program, where a kernel that
only copies the rows takes 0.83: PERF.md section 6, PR 39). So the products
``S . C`` of as many heads as fill the lanes (two of 64) are turned as one
tile and summed down the sublanes, plain adds, and ``y`` leaves as whole rows
of lanes in the order ``[H, P]`` has: 0.83 ms, the copies' own time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32


def _heads_a_tile(H: int, P: int) -> int:
    """How many heads' read-outs fill a row of 128 lanes: the largest
    divisor of H at or under 128 / P (1 where a head is wider)."""
    return max(n for n in range(1, max(1, 128 // P) + 1) if H % n == 0)


def _kernel(j_ref, a_ref, s_ref, u_ref, b_ref, c_ref, o_ref, y_ref, *,
            rep: int, per: int):
    # j_ref: the block (in the index maps alone); a_ref: [R, H] decays
    # (SMEM); s_ref / o_ref: [H, P, N] one row in, the same row out; u_ref:
    # [P, H] dt . x; b_ref, c_ref: [G, N]; y_ref: [H / per, per . P]
    del j_ref
    r = pl.program_id(0)
    H = s_ref.shape[0]
    u = u_ref[...]
    for h0 in range(0, H, per):
        read = []
        for h in range(h0, h0 + per):
            g = h // rep
            s = a_ref[r, h] * s_ref[h] + u[:, h:h + 1] * b_ref[g:g + 1, :]
            o_ref[h] = s
            read.append(s * c_ref[g:g + 1, :])
        tile = jnp.concatenate(read, axis=0)          # [per . P, N]
        y_ref[h0 // per:h0 // per + 1, :] = jnp.sum(
            tile.T, axis=0, keepdims=True)


def ssm_pool_step(pool, j, x, dt, A, Bm, Cm, D, *,
                  interpret: bool | None = None):
    """One position of the recurrence for every row of block ``j`` of a state
    pool, in place. pool: [L, R, H, P, N] float32; j: the block (int32
    scalar, traced or not); x: [R, H, P]; dt: [R, H] float32 (after its
    softplus; 0 for a row that must not move); A, D: [H] float32; Bm, Cm:
    [R, G, N]. Returns (pool with block j advanced, y [R, H, P] float32):
    what ``ssm_step(pool[j], ...)`` and ``pool.at[j].set`` give. The kernel
    compiles for the TPU and is interpreted anywhere else."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _pool_step(pool, jnp.asarray(j, jnp.int32).reshape(1), x, dt, A,
                      Bm, Cm, D, interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pool_step(pool, j, x, dt, A, Bm, Cm, D, *, interpret: bool):
    L, R, H, P, N = pool.shape
    rep, per = H // Bm.shape[1], _heads_a_tile(H, P)
    x = x.astype(_F32)
    decay = jnp.exp(dt * A)
    u = jnp.swapaxes(dt[..., None] * x, 1, 2)  # [R, P, H]

    def row(*tail):   # one row's whole block of a per-row input or output
        return pl.BlockSpec((None,) + tail,
                            lambda r, j, a: (r,) + (0,) * len(tail))

    state = pl.BlockSpec((None, None, H, P, N),
                         lambda r, j, a: (j[0], r, 0, 0, 0))
    pool, y = pl.pallas_call(
        functools.partial(_kernel, rep=rep, per=per),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(R,),
            in_specs=[state, row(P, H), row(*Bm.shape[1:]),
                      row(*Cm.shape[1:])],
            out_specs=[state, row(H // per, per * P)]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((R, H // per, per * P), _F32)],
        # operand 2 (after the two prefetched scalars) is the pool
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ssm_pool_step",
    )(j, decay, pool, u,
      Bm.astype(_F32), Cm.astype(_F32))
    return pool, y.reshape(R, H, P) + D[None, :, None] * x

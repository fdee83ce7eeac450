"""Pallas TPU grouped SwiGLU for few rows a group — the touched experts
streamed once.

A decode step routes ``T * k`` rows (192, 384) over the held experts: a few
rows an expert against three matrices of megabytes each, so the step is the
time it takes to read the TOUCHED experts' weights out of HBM.
``jax.lax.ragged_dot``'s kernel is built for prefill's thousands of rows a
group; called three times (gate, up, down) at three rows a group it reads
them at 57-62 % of the bandwidth, this kernel at 84-90 % (PERF.md, PR 32).
It makes one pass: for every held expert that has rows,

    ys[rows of e] = (silu(x . W_gate[e]) * (x . W_up[e])) . W_down[e]

* **The parameter tree as it is**: ``w_gate``, ``w_up`` ``[n, D, F]`` and
  ``w_down`` ``[n, F, D]`` are three operands whose blocks the grid's index
  maps pick; no copy of a weight is made anywhere.
* **A grid over the touched experts.** The caller's ``load`` becomes the
  compacted list of experts with rows, each with its group's offset into the
  sorted rows, scalar-prefetched. The list's tail repeats the last touched
  expert's last tile: a padded step asks for the block that is there
  already, so it fetches nothing, and its load of 0 computes nothing. An
  expert with no rows costs no bytes. (With no row at all the list is expert
  0's last tile, fetched once and not used.)
* **The hidden width tiled, the product fused.** A step is (expert, tile of
  ``F``): ``hid = silu(x . Wg[:, f]) * (x . Wu[:, f])`` in float32, rounded
  to the rows' dtype once, ``out += hid . Wd[f, :]`` in float32. The tile is
  the widest whose three double-buffered blocks fit ``_WEIGHT_VMEM``
  (``f_tile``): a whole expert at 2048 x 768, 1024 columns at 4096 x 4096.
  While a step computes, the pipeline copies the next step's blocks, across
  an expert boundary the next expert's.
* **Each matrix enters the MXU once.** The rows and the float32 output stay
  in VMEM for the whole call. A group's rows ``[off, off + load)`` are taken
  as chunks of ``ROW_CHUNK`` rows from the sublane tile that holds ``off``;
  rows of the chunk that are a neighbour's are zeroed in ``hid`` and add
  nothing. A load up to ``ROW_CHUNK - ROW_ALIGN + 1`` is one chunk wherever
  it lies, and ``expert_passes`` counts the chunks. HBM bounds a step, not
  the MXU's weight loads: alone on a v5e, 39 chunks over 16 experts of
  4096 x 4096 took what 16 took (2,190 and 2,185 us; chunks of 16 to 128
  rows within 1 %: PERF.md, PR 32).

The entry is a jit of its own, so the expert layers of a program are call
sites of one traced and lowered kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# rows of a chunk start at a multiple of this: the sublane tile of bf16
# (two of float32's), which a dynamic slice of the rows has to respect
ROW_ALIGN = 16
# rows the MXU takes against one load of an expert's weight tiles
ROW_CHUNK = 64
# the three weight blocks of a step, double-buffered, may take this much of
# a core's 128 MiB of VMEM (a grid step costs about 0.8 us beside its bytes,
# so the fewest steps an expert that fit: 25 MB in flight read 2 % faster
# than 12, PERF.md, PR 32); the rows and the float32 output (twice each: 22
# MiB at 384 x 4096) and a chunk's temporaries take the rest of the limit
_WEIGHT_VMEM = 48 * 2**20
_VMEM_LIMIT = 100 * 2**20


def _chunks(off, load, chunk: int):
    """Row chunks of a group of ``load`` rows from row ``off``, counted from
    the sublane tile that holds ``off``; none for an expert with no rows."""
    return jnp.where(load > 0, (off % ROW_ALIGN + load + chunk - 1) // chunk, 0)


def expert_passes(load, chunk: int = ROW_CHUNK):
    """How often the kernel puts an expert's matrices through the MXU for
    these loads: a touched expert's chunks, summed. load: [..., n] rows a
    held expert, sorted rows in expert order along the last axis."""
    return _chunks(jnp.cumsum(load, axis=-1) - load, load, chunk).sum()


def f_tile(D: int, F: int, itemsize: int) -> int:
    """Columns of the hidden width a step takes: all of ``F`` if an expert's
    three matrices fit ``_WEIGHT_VMEM`` twice over, else the largest divisor
    of ``F`` that is whole lane tiles and fits."""
    fits = _WEIGHT_VMEM // (2 * 3 * D * itemsize)
    if F <= fits:
        return F
    tiles = [t for t in range(128, F, 128) if F % t == 0 and t <= fits]
    if not tiles:
        raise ValueError(f"no tile of the hidden width {F} fits VMEM at D={D}")
    return tiles[-1]


def _kernel(nt_ref, ids_ref, offs_ref, loads_ref, x_ref, wg_ref, wu_ref,
            wd_ref, o_ref, *, chunk: int):
    e, f = pl.program_id(0), pl.program_id(1)

    @pl.when(jnp.logical_and(e == 0, f == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    off, load = offs_ref[e], loads_ref[e]
    base = off // ROW_ALIGN * ROW_ALIGN

    def one_chunk(c, carry):
        r0 = pl.multiple_of(base + c * chunk, ROW_ALIGN)
        x = x_ref[pl.ds(r0, chunk), :]
        row = r0 + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
        mine = jnp.logical_and(row >= off, row < off + load)
        gate = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        hid = jnp.where(mine, gate * jax.nn.sigmoid(gate) * up, 0.0)
        o_ref[pl.ds(r0, chunk), :] += jnp.dot(
            hid.astype(x.dtype), wd_ref[0], preferred_element_type=jnp.float32)
        return carry

    # a padded step (load 0) makes no chunk
    jax.lax.fori_loop(0, _chunks(off, load, chunk), one_chunk, 0)


def grouped_swiglu(xs, w_gate, w_up, w_down, load, *,
                   interpret: bool | None = None):
    """``swiglu_e`` of each group's rows, the groups given by ``load``.

    xs: [R, D] rows sorted by expert, group ``e`` at ``[sum(load[:e]),
    sum(load[:e + 1]))``; w_gate, w_up: [n, D, F]; w_down: [n, F, D] (handed
    over whole; only the experts with rows are read); load: [n] int32.
    Returns [R, D] in xs's dtype; rows past the last group are zeros. The
    kernel compiles for the TPU and is interpreted anywhere else."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    D, F = w_gate.shape[1:]
    return _grouped_swiglu(
        xs, w_gate, w_up, w_down, load,
        tile=f_tile(D, F, w_gate.dtype.itemsize), chunk=ROW_CHUNK,
        interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("tile", "chunk", "interpret"))
def _grouped_swiglu(xs, w_gate, w_up, w_down, load, *, tile: int, chunk: int,
                    interpret: bool):
    """A jit of its own for the reason ``ops/paged_attention.py``'s entries
    are: a program's expert layers share one lowered kernel."""
    R, D = xs.shape
    n, _, F = w_gate.shape
    nf = F // tile
    # the last chunk of the last group may reach past the rows: zeros there
    padded = -(-R // ROW_ALIGN) * ROW_ALIGN + chunk
    xs = jnp.pad(xs, ((0, padded - R), (0, 0)))

    # the experts with rows, in order, then the last of them again and again
    load = load.astype(jnp.int32)
    touched = load > 0
    nt = touched.sum().astype(jnp.int32)
    place = jnp.cumsum(touched) - 1  # a touched expert's place in the list
    at = jnp.arange(n, dtype=jnp.int32)
    ids = jnp.sum(jnp.where(
        jnp.logical_and(touched[None, :], place[None, :] == at[:, None]),
        at[None, :], 0), axis=1)
    ids = jnp.where(at < nt, ids, ids[jnp.maximum(nt - 1, 0)])
    offs = (jnp.cumsum(load) - load)[ids]
    loads = jnp.where(at < nt, load[ids], 0)

    def tile_of(e, f, nt_ref):  # a padded step stays on the last tile
        return jnp.where(e < nt_ref[0], f, nf - 1)

    def wide(e, f, nt_ref, ids_ref, *_):   # w_gate, w_up: [:, :, f]
        return ids_ref[e], 0, tile_of(e, f, nt_ref)

    def tall(e, f, nt_ref, ids_ref, *_):   # w_down: [:, f, :]
        return ids_ref[e], tile_of(e, f, nt_ref), 0

    out = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        out_shape=jax.ShapeDtypeStruct((padded, D), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n, nf),
            in_specs=[pl.BlockSpec((padded, D), lambda e, f, *_: (0, 0)),
                      pl.BlockSpec((1, D, tile), wide),
                      pl.BlockSpec((1, D, tile), wide),
                      pl.BlockSpec((1, tile, D), tall)],
            out_specs=pl.BlockSpec((padded, D), lambda e, f, *_: (0, 0)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * 3 * padded * D * F, transcendentals=padded * F,
            bytes_accessed=3 * min(n, R) * D * F * w_gate.dtype.itemsize),
        name="ragged-dot-swiglu",
        interpret=interpret,
    )(nt.reshape(1), ids, offs, loads, xs, w_gate, w_up, w_down)
    return out[:R].astype(xs.dtype)

"""Pallas TPU chunked delta rule: a prefill's whole scan of one KDA layer as
ONE call, a chunk's working set and the running state in VMEM.

``ops/kda.py`` ``kda_chunked`` is the plain form and this kernel's reference:
the same mathematics (its docstring derives it), as XLA schedules it — four
float32 copies of the keys, a dozen more arrays of the keys' size and a
``lax.scan`` of three small matmuls a chunk, every one of them through HBM.
Here a program of the grid is (sequence, eight heads, a run of chunks), the
chunk axis last and sequential: it reads its chunks' q, k, v, g and beta,
writes their o, and nothing else of a chunk leaves VMEM. The operands stay
``[N, T, H, lanes]`` as the model makes them — a block is ``[positions, 8
heads, lanes]``, a head's rows a sublane of each position's tile — so no
layout changes hands as float32 around the call. A head's state lives in a
scratch across the chunk axis — zeroed at the first chunk, written out after
the last — TRANSPOSED, ``[dv, dk]``: the decay of a chunk's end is then a row
of lanes times the state's lanes, and the reads of the state contract the
last axes.

Per chunk of ``C`` positions in sub-blocks of ``sub``, for each head:

    G      = the running sum of g down the chunk
    down_t = exp(G_t - r_i + 40),  r_i = G before sub-block i   (t in i)
    keys_i = b k . exp(min(r_i - G - 40, 40))           the key side, a row sub-block each
    A, Aq  = (k . down)_i keys_i^T, (q . down)_i keys_i^T   under their masks (s < t, s <= t)
    T      = (I + A)^-1                                 blockwise, below
    W      = T (V - (K . exp(G)) S)                     ( = T V - T (K . exp(G)) S )
    O      = (Q . exp(G)) S + Aq W
    S      = Diag(exp(G_C)) S + (b K . exp(G_C - G))^T W

``beta`` is folded into the key side's rows (``kda_chunked`` scales ``A``'s
columns: the same product, rounded elsewhere), and the keys of the sub-blocks
after ``i`` — whose columns lie under the masks — are zero rows of ``keys_i``
and not computed. ``T``: the ``sub x sub`` blocks on the diagonal by
elimination — pivot ``s`` takes its column times row ``s`` off the rows
below, ``sub - 1`` steps on every block at once, on the vector unit — then
the blocks under the diagonal by block substitution, two neighbours at a
time (``-T_b A_ba T_a``; sub-blocks to pairs to the chunk), two products a
doubling over the rows that change.

The mathematics is ``kda_chunked``'s at its precision: every operand
float32, every product at ``Precision.HIGHEST``, the state float32 from the
first chunk to the last. A position whose ``g`` is 0 and whose ``beta`` is 0
moves no state (its key row is 0, its decay 1).

What bounds it is the vector unit, not the MXU: with every product taken out
the body still ran 82 % of its time (my chip runs, PR 45) — the three-way
split of each float32 operand, the elimination, the running sum. So the body
counts registers: operands of half the rows where half are zero, tiles the
pivot has passed left alone, beta a column as it comes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.kda import _CAP, _EXACT, _F32

# heads a program (a tile of sublanes; their chains overlap: ``_chunk``) and
# chunks a program at most (a program's fixed cost against a chunk's 9 us of
# work; the blocks of 8 heads and 2 chunks are 4.5 MB, twice over, and 4
# chunks pass the 16 MB a kernel may scope)
_HEADS = 8
_CHUNKS = 2


def fits(dk: int, dv: int, chunk: int, sub: int) -> bool:
    """Whether the kernel takes these shapes: key and value lanes whole lane
    tiles, sub-blocks whole sublane tiles, a chunk a power of two of them."""
    nb = chunk // sub if sub else 0
    return (dk % 128 == 0 and dv % 128 == 0 and sub % 8 == 0
            and nb * sub == chunk and nb & (nb - 1) == 0 and nb > 0)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_EXACT,
                               preferred_element_type=_F32)


def _nn(a, b):      # [m, k] [k, n]
    return _dot(a, b, ((1,), (0,)))


def _nt(a, b):      # [m, k] [n, k]
    return _dot(a, b, ((1,), (1,)))


def _tn(a, b):      # [k, m] [k, n]
    return _dot(a, b, ((0,), (0,)))


def _running_sum(g):
    """The sum down the rows through each row. g: [C, lanes], C whole tiles
    of 8 rows: inside a tile three shifted adds (the only turns of a
    register), then each tile takes the last row of the one before."""
    at = jax.lax.broadcasted_iota(jnp.int32, (8, g.shape[1]), 0)
    tiles, carry = [], None
    for lo in range(0, g.shape[0], 8):
        t = g[lo:lo + 8]
        for reach in (1, 2, 4):
            t = t + jnp.where(at >= reach, pltpu.roll(t, reach, 0), 0.0)
        if carry is not None:
            t = t + carry
        tiles.append(t)
        carry = t[7:8]
    return jnp.concatenate(tiles, axis=0)


def _odd(a, width: int):
    """The rows of every second block of ``width`` rows (the odd ones), one
    under the other: [C / 2, ...]. A doubling changes these rows alone."""
    return jnp.concatenate([a[lo:lo + width]
                            for lo in range(width, a.shape[0], 2 * width)],
                           axis=0)


def _at_odd(a, width: int):
    """``_odd``'s rows back at their places, zeros between: [C, ...]."""
    zeros = jnp.zeros((width,) + a.shape[1:], a.dtype)
    return jnp.concatenate(
        [x for lo in range(0, a.shape[0], width)
         for x in (zeros, a[lo:lo + width])], axis=0)


def _chunk(q, k, v, g, b, St, sub: int):
    """One chunk of a few heads; every argument a list over the heads. q, k,
    g: [C, dk]; v: [C, dv]; b: [C, 1]; St: [dv, dk], the state before the
    chunk, transposed. Returns (o [C, dv], the state after it), lists too.

    Written a STAGE at a time over the heads, not a head at a time: a head's
    chunk is one chain of dependent products, the compiler keeps the order
    it is given, and only chains that lie side by side overlap (an earlier
    form of this body a head at a time 1.29 us a token a layer, two 0.89,
    four 0.80, eight 0.77: my chip runs, PR 45)."""
    n = range(len(q))
    C, dk = k[0].shape
    nb = C // sub
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    G = [_running_sum(g[h]) for h in n]                  # through position t
    # r[h][i]: the running sum before sub-block i, [1, dk]
    r = [[(G[h] - g[h])[i * sub:i * sub + 1] for i in range(nb)] for h in n]
    # from the sub-block's start, both sides moved half the span
    down = [jnp.exp(G[h] - jnp.concatenate(
        [jnp.broadcast_to(x - _CAP / 2, (sub, dk)) for x in r[h]], axis=0))
        for h in n]
    kd = [k[h] * down[h] for h in n]
    qd = [q[h] * down[h] for h in n]
    kb = [k[h] * b[h] for h in n]
    both = [[] for h in n]   # sub-block i's rows of A over those of Aq
    for i in range(nb):
        rows, live = slice(i * sub, (i + 1) * sub), (i + 1) * sub
        for h in n:
            # the keys up to the sub-block's end; the later ones' columns are
            # under the masks, and zero here
            keys = kb[h][:live] * jnp.exp(jnp.minimum(
                (r[h][i] - _CAP / 2) - G[h][:live], _CAP / 2))
            if live < C:
                keys = jnp.concatenate(
                    [keys, jnp.zeros((C - live, dk), _F32)], axis=0)
            both[h].append(_nt(
                jnp.concatenate([kd[h][rows], qd[h][rows]], axis=0), keys))
    A = [jnp.where(row > col, jnp.concatenate(
        [x[:sub] for x in both[h]], axis=0), 0.0) for h in n]
    Aq = [jnp.where(row >= col, jnp.concatenate(
        [x[sub:] for x in both[h]], axis=0), 0.0) for h in n]
    # the diagonal blocks' inverses: pivot s takes its column times row s off
    # the rows below, every block of every head a step. A block is tiles of
    # 8 rows, and a tile whose rows the pivot has passed changes no more.
    rows_i = jax.lax.broadcasted_iota(jnp.int32, (8, C), 0)
    cols_i = jax.lax.broadcasted_iota(jnp.int32, (8, C), 1)
    blocks = [(h, i) for h in n for i in range(nb)]
    tiles = range(sub // 8)
    unit = {(i, t): (cols_i == rows_i + i * sub + t * 8).astype(_F32)
            for i in range(nb) for t in tiles}
    X = {(h, i, t): unit[i, t] for h, i in blocks for t in tiles}
    # (only a block's own columns of its rows of A are ever picked)
    mine = {(h, i, t): A[h][i * sub + t * 8:i * sub + t * 8 + 8]
            for h, i in blocks for t in tiles}
    for s in range(sub - 1):
        for h, i in blocks:
            pivot = i * sub + s
            at = X[h, i, s // 8][s % 8:s % 8 + 1]
            for t in tiles[(s + 1) // 8:]:
                X[h, i, t] = X[h, i, t] - mine[h, i, t][:, pivot:pivot + 1] * at
    Tm = [jnp.concatenate([X[h, i, t] for i in range(nb) for t in tiles],
                          axis=0) for h in n]
    # the blocks under the diagonal, two neighbours at a time
    width = sub
    while width < C:
        left = col // width == row // width - 1   # the neighbour's columns
        inner = [_nn(_odd(jnp.where(left, A[h], 0.0), width), Tm[h])
                 for h in n]                             # A_ba T_a
        low = [_nn(_odd(Tm[h], width), _at_odd(inner[h], width))
               for h in n]                               # T_b (A_ba T_a)
        Tm = [Tm[h] - _at_odd(low[h], width) for h in n]
        width *= 2
    gamma = [jnp.exp(G[h]) for h in n]
    read = [_nt(jnp.concatenate([k[h] * gamma[h], q[h] * gamma[h]], axis=0),
                St[h]) for h in n]                       # (k Gamma) S over q_in S
    W = [_nn(Tm[h], v[h] - read[h][:C]) for h in n]      # T (V - (K Gamma) S)
    o = [read[h][C:] + _nn(Aq[h], W[h]) for h in n]
    last = [G[h][C - 1:C] for h in n]
    St = [St[h] * jnp.exp(last[h])
          + _tn(W[h], kb[h] * jnp.exp(last[h] - G[h])) for h in n]
    return o, St


def _kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, s_ref, st_ref, *,
            chunk: int, sub: int):
    # q_ref, k_ref, g_ref: [m . C, heads, dk]; v_ref, o_ref: [m . C, heads,
    # dv] (a head's rows are a sublane of each position's tile); b_ref: [m .
    # C, heads]; s_ref: [heads, dk, dv] out; st_ref: [heads, dv, dk] the
    # running states
    C = chunk
    js = range(st_ref.shape[0])
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    def one(i, carry):
        rows = pl.ds(pl.multiple_of(i * C, C), C)
        betas = b_ref[rows, :]
        o, St = _chunk(
            [q_ref[rows, j, :].astype(_F32) for j in js],
            [k_ref[rows, j, :].astype(_F32) for j in js],
            [v_ref[rows, j, :].astype(_F32) for j in js],
            [g_ref[rows, j, :] for j in js],
            [betas[:, j:j + 1] for j in js],
            [st_ref[j] for j in js], sub)
        for j in js:
            o_ref[rows, j, :] = o[j]
            st_ref[j] = St[j]
        return carry

    jax.lax.fori_loop(0, q_ref.shape[0] // C, one, 0)

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        for j in js:
            s_ref[j] = st_ref[j].T


def kda_chunk_scan(q, k, v, g, beta, chunk: int = 64, sub: int = 16, *,
                   interpret: bool | None = None):
    """``ops/kda.py`` ``kda_chunked`` as one kernel: the delta rule over whole
    sequences from a zero state. q, k: [N, T, H, dk]; v: [N, T, H, dv]; g:
    [N, T, H, dk] float32, at least ``-80 / sub`` a position (0 where a
    position must not move the state); beta: [N, T, H] float32 (0 there
    too). Returns (o [N, T, H, dv] float32, the state after the last
    position [N, H, dk, dv] float32). T is padded to whole chunks here with
    ``g`` = 0 and ``beta`` = 0, as ``kda_chunked`` pads it. The shapes must
    be the kernel's (``fits``). It compiles for the TPU and is interpreted
    anywhere else."""
    dk, dv = k.shape[-1], v.shape[-1]
    if not fits(dk, dv, chunk, sub):
        raise ValueError(f"not the kernel's shapes: keys of {dk} lanes, "
                         f"values of {dv}, chunks of {chunk} in {sub}s")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _scan(q, k, v, g, beta, chunk=chunk, sub=sub,
                 interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("chunk", "sub", "interpret"))
def _scan(q, k, v, g, beta, *, chunk: int, sub: int, interpret: bool):
    N, T, H, dk = k.shape
    dv = v.shape[-1]
    pad = -T % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    Tp = T + pad
    # a block takes whole tiles of heads (8 sublanes) or all of them
    heads = _HEADS if H % _HEADS == 0 else H
    m = max(d for d in range(1, _CHUNKS + 1) if Tp // chunk % d == 0)
    rows = m * chunk

    def lanes(width):  # a program's chunks of its heads
        return pl.BlockSpec((None, rows, heads, width),
                            lambda n, h, c: (n, c, h, 0))

    o, S = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, sub=sub),
        grid=(N, H // heads, Tp // rows),
        in_specs=[lanes(dk), lanes(dk), lanes(dv), lanes(dk),
                  pl.BlockSpec((None, None, rows, heads),
                               lambda n, h, c: (n, h, c, 0))],
        out_specs=[lanes(dv),
                   pl.BlockSpec((None, heads, dk, dv),
                                lambda n, h, c: (n, h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((N, Tp, H, dv), _F32),
                   jax.ShapeDtypeStruct((N, H, dk, dv), _F32)],
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="kda_chunk_scan",
    )(q, k, v, g.astype(_F32),
      # a program's heads' beta as columns: [N, H / heads, T, heads]
      jnp.moveaxis(beta.astype(_F32).reshape(N, Tp, H // heads, heads), 2, 1))
    return o[:, :T], S

"""The Mamba-2 mixer's two stateful pieces, each in the form a decode step
takes (one position, the state read and written) and in the form a prefill
takes (a whole prompt): the causal depthwise convolution and the selective
state-space recurrence

    S_t = exp(dt_t . A) . S_{t-1} + dt_t . x_t (x) B_t,    y_t = S_t . C_t + D . x_t

with one scalar ``A`` and ``D`` a head and ``B``, ``C`` shared by the heads of
a group. Plain XLA operations, and exact: the chunked form (within a chunk a
masked ``[Q, Q]`` product, between chunks the carried state) is the one-step
recurrence regrouped, with the cumulative sums of ``dt . A``, the
exponentials of their differences and the state in float32 and every
float32 product at the highest precision. Nothing is dropped below a
threshold and no history is cut. A position whose ``dt`` is 0 decays nothing
and adds nothing: that is how a caller keeps padding out of a state.
``ssm_step`` is also the reference of ``ops/ssm_pool.py``'s kernel, which is
what a decode step on a TPU runs in its place: the same step over a whole
pool's block, each row read once and written once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_EXACT = jax.lax.Precision.HIGHEST


def causal_conv(u, w, b):
    """``silu(b + sum_j w[j] . u[t - K + 1 + j])`` with zeros before position
    0, accumulated in float32. u: [N, T, C]; w: [K, C]; b: [C]. Returns
    [N, T, C] in u's dtype."""
    K, T = w.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    acc = b.astype(_F32)
    for j in range(K):
        acc = acc + w[j].astype(_F32) * padded[:, j:j + T].astype(_F32)
    return jax.nn.silu(acc).astype(u.dtype)


def conv_tail(u, lens, K: int):
    """What a decode step's window needs of a prompt: the ``K - 1`` inputs
    before position ``lens[n]``, oldest first, zeros where the prompt is
    shorter. u: [N, T, C]; lens: [N] int32. Returns [N, K - 1, C]. Read out
    of ``u`` itself: a zero-padded copy of the whole sequence kept for three
    rows of it stayed in the chip's fast memory through the blocks that
    followed, and one prefill program of twelve never came back (PERF.md
    section 6, PR 38)."""
    at = lens[:, None] - (K - 1) + jnp.arange(K - 1)[None, :]
    rows = jnp.take_along_axis(u, jnp.maximum(at, 0)[:, :, None], axis=1)
    return jnp.where((at >= 0)[:, :, None], rows, jnp.zeros((), u.dtype))


def conv_step(window, w, b):
    """One position of ``causal_conv``: window [B, K, C] holds the K - 1
    inputs before this one and this one, oldest first. Returns [B, C]."""
    acc = b.astype(_F32) + (
        w.astype(_F32)[None] * window.astype(_F32)).sum(axis=1)
    return jax.nn.silu(acc).astype(window.dtype)


def _heads(a, rep: int):
    """[..., G, N] of a group -> [..., G * rep, N] of its heads, float32."""
    return jnp.repeat(a.astype(_F32), rep, axis=-2)


def ssm_step(S, x, dt, A, Bm, Cm, D):
    """One position of the recurrence for every row. S: [B, H, P, N] float32;
    x: [B, H, P]; dt: [B, H] float32 (after its softplus); A, D: [H] float32;
    Bm, Cm: [B, G, N]. Returns (S' [B, H, P, N] float32, y [B, H, P]
    float32). Elementwise and a reduction: float32 throughout."""
    rep = x.shape[1] // Bm.shape[1]
    x = x.astype(_F32)
    S = jnp.exp(dt * A)[..., None, None] * S + (
        (dt[..., None] * x)[..., None] * _heads(Bm, rep)[:, :, None, :])
    y = (S * _heads(Cm, rep)[:, :, None, :]).sum(axis=-1)
    return S, y + D[None, :, None] * x


def ssm_chunked(x, dt, A, Bm, Cm, D, chunk: int):
    """The recurrence over whole sequences from a zero state, ``chunk``
    positions at a time. x: [N, T, H, P]; dt: [N, T, H] float32 (0 where a
    position must not advance the state); A, D: [H] float32; Bm, Cm:
    [N, T, G, S]. Returns (y [N, T, H, P] float32, the state after the last
    position [N, H, P, S] float32). T is padded to whole chunks here with
    ``dt`` = 0; the tiling changes no result."""
    N, T, H, P = x.shape
    G, St = Bm.shape[2:]
    Q, rep = chunk, H // G
    pad = -T % Q
    if pad:
        x, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                         for a in (x, dt, Bm, Cm))
    c = (T + pad) // Q
    xs = x.reshape(N, c, Q, G, rep, P).astype(_F32)
    dts = dt.reshape(N, c, Q, G, rep)
    Bc, Cc = Bm.reshape(N, c, Q, G, St), Cm.reshape(N, c, Q, G, St)
    # cum[l]: the log of the decay from the chunk's start through position l
    cum = jnp.cumsum(dts * A.reshape(G, rep), axis=2)       # [N, c, Q, G, r]
    # within a chunk: y[l] += sum_{m <= l} (C_l . B_m) exp(cum_l - cum_m) dt_m x_m
    cb = jnp.einsum("nclgs,ncmgs->ncglm", Cc, Bc, precision=_EXACT,
                    preferred_element_type=_F32)             # [N, c, G, Q, Q]
    cum_h = jnp.moveaxis(cum, 2, -1)                         # [N, c, G, r, Q]
    seg = cum_h[..., :, None] - cum_h[..., None, :]          # [.., l, m]
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    w = jnp.exp(jnp.where(causal, seg, -jnp.inf)) * (
        jnp.moveaxis(dts, 2, -1)[..., None, :])              # [N, c, G, r, l, m]
    y = jnp.einsum("ncgrlm,ncmgrp->nclgrp", cb[:, :, :, None] * w, xs,
                   precision=_EXACT)
    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cum[:, :, -1:] - cum) * dts             # [N, c, Q, G, r]
    own = jnp.einsum("ncmgrp,ncmgs->ncgrps", xs * to_end[..., None],
                     Bc.astype(_F32), precision=_EXACT)      # [N, c, G, r, P, S]
    whole = jnp.exp(cum[:, :, -1])                           # [N, c, G, r]

    def carry(S, chunk_in):
        mine, decay = chunk_in
        return decay[..., None, None] * S + mine, S

    S, before = jax.lax.scan(
        carry, jnp.zeros((N, G, rep, P, St), _F32),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(whole, 1, 0)))
    # what the state at the chunk's start adds: exp(cum_l) . C_l . S
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "nclgs,cngrps->nclgrp", Cc.astype(_F32), before, precision=_EXACT)
    y = y + D.reshape(G, rep)[..., None] * xs
    return (y.reshape(N, c * Q, H, P)[:, :T], S.reshape(N, H, P, St))

"""The gated delta rule of Kimi Delta Attention (KDA), in the form a decode
step takes (one position, the state read and written) and in the form a
prefill takes (a whole prompt, chunked). A head keeps a state ``S`` of key
lanes x value lanes; a position decays every KEY LANE by its own factor, takes
out what the state already holds along the new key, and adds the new pair:

    S' = Diag(a_t) S_{t-1}
    S_t = S' + b_t k_t (v_t - S'^T k_t)^T        ( = (I - b k k^T) Diag(a) S + b k v^T )
    o_t = S_t^T q_t

``a_t = exp(g_t)`` in (0, 1] a lane (``kda_gate``: ``g = lower_bound .
sigmoid(exp(A_log) . a)``, so ``g`` lies in ``(lower_bound, 0)``), ``b_t`` in
[0, 1] a head. Against ``ops/ssm.py``'s recurrence (one decay a head, a rank-1
add) the new part is the reduction over the state BEFORE the update, inside
the same pass. Plain XLA operations, and exact: ``g``, ``a``, ``b``, the
state and every product in float32 at the highest precision. A position
whose ``b`` is 0 and whose ``g`` is 0 changes nothing: that is how a caller
keeps padding out of a state. ``kda_step`` is also the reference of
``ops/kda_pool.py``'s kernel, which a decode step on a TPU runs
in its place.

**The chunked form** (``kda_chunked``). Inside a chunk of ``C`` positions that
starts from ``S_0``, with ``G_t`` the running sum of ``g`` from the chunk's
start through ``t`` (``Gamma_t = exp(G_t)`` a lane) and ``w_t = v_t - S'_t^T
k_t`` the part of ``v_t`` the state did not hold:

    S'_t = Diag(Gamma_t) S_0 + sum_{s<t} Diag(Gamma_t / Gamma_s) b_s k_s w_s^T
    (I + A) W = V - (K . Gamma) S_0,   A[t, s] = b_s sum_l k_t[l] k_s[l] exp(G_t[l] - G_s[l])  (s < t)
    O = (Q . Gamma) S_0 + Aq W,        Aq[t, s] = b_s sum_l q_t[l] k_s[l] exp(G_t[l] - G_s[l]) (s <= t)
    S_C = Diag(Gamma_C) S_0 + (b K . Gamma_C / Gamma)^T W

so the delta rule inside a chunk is ONE triangular solve, ``T = (I + A)^-1``
(unit lower triangular), found for every chunk at once; ``T V`` and ``T (K .
Gamma)`` likewise; what runs one chunk after another is ``W = T V - T (K .
Gamma) S_0`` and the two products that follow, three small matmuls a chunk.

**Why a sub-block is 16 positions.** ``A`` as a matmul needs the two sides
apart, ``(K . Gamma)(K / Gamma)^T``, and ``1 / Gamma`` overflows float32 once
a lane's running ``g`` passes -88. With ``g >= -5`` a position
(``kda_lower_bound``) 16 positions reach at most -80: so a chunk of 64 is
four sub-blocks of 16, the rows of sub-block ``i`` are taken relative to the
running sum at ITS start ``r_i`` — ``k_t . exp(G_t - r_i)``, a factor in
[e^-80, 1] — and the key side relative to the same point, ``k_s . exp(r_i -
G_s)``: at most ``e^80`` for a key inside the row's own sub-block, under 1
for every earlier one, and masked (the exponent capped) for the later ones.
Each side is moved by ``e^40`` towards the other, so both stay inside
[e^-40, e^40] where it matters: a small lane times ``e^-80`` would be a
subnormal number, which a TPU flushes to zero. ``T`` is then found
blockwise: a sub-block's own 16 x 16 inverse by substituting row after row,
the blocks under the diagonal by block substitution. A caller whose ``g`` can
be lower than ``-80 / sub`` a position has to take smaller sub-blocks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_EXACT = jax.lax.Precision.HIGHEST
# what a lane's exponent may reach inside a sub-block: exp(80) is 5.5e34
_CAP = 80.0
# positions the chunked form takes at a time: a wave's prompts go through it
# in groups of at most this many tokens, one group after the other, because
# its float32 temporaries are a dozen times the keys' size
_SCAN_TOKENS = 4096


def kda_gate(a, A_log, lower_bound: float):
    """The log of the decay, a lane of a head: ``lower_bound . sigmoid(
    exp(A_log) . a)`` in float32, in ``(lower_bound, 0)`` for a negative
    bound. a: [..., H, dk] (the decay's projection plus its bias); A_log:
    [H]."""
    return lower_bound * jax.nn.sigmoid(
        jnp.exp(A_log.astype(_F32))[:, None] * a.astype(_F32))


def l2_normalise(x, eps: float = 1e-6):
    """``x / |x|`` over the last axis, float32."""
    x = x.astype(_F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def kda_step(S, q, k, v, g, beta):
    """One position of the delta rule for every row. S: [B, H, dk, dv]
    float32; q, k: [B, H, dk]; v: [B, H, dv]; g: [B, H, dk] float32 (the log
    of the decay); beta: [B, H] float32. Returns (S' [B, H, dk, dv], o [B, H,
    dv]) float32. Elementwise and two reductions over the key lanes."""
    q, k, v = q.astype(_F32), k.astype(_F32), v.astype(_F32)
    S = jnp.exp(g)[..., None] * S
    u = (S * k[..., None]).sum(axis=-2)
    S = S + (beta[..., None] * k)[..., None] * (v - u)[..., None, :]
    return S, (S * q[..., None]).sum(axis=-2)


def _unit_lower_inverse(L, sub: int):
    """``(I + L)^-1`` of strictly lower triangular ``L`` [..., C, C], ``C`` a
    multiple of ``sub``: each diagonal sub-block's inverse by substituting
    row after row (row t of it is ``e_t - L[t, :t] . rows before``), the
    blocks under the diagonal by block substitution."""
    C = L.shape[-1]
    nb = C // sub
    lead = L.shape[:-2]
    D = jnp.stack([L[..., i * sub:(i + 1) * sub, i * sub:(i + 1) * sub]
                   for i in range(nb)], axis=-3)          # [..., nb, sub, sub]
    Ti = jnp.broadcast_to(jnp.eye(sub, dtype=_F32), D.shape)
    for t in range(1, sub):
        row = -jnp.einsum("...s,...sj->...j", D[..., t, :t], Ti[..., :t, :],
                          precision=_EXACT)
        Ti = Ti.at[..., t, :].add(row)
    rows = []
    done = None  # the inverse's leading [lo, lo] corner
    for i in range(nb):
        lo = i * sub
        mine = Ti[..., i, :, :]
        if i:
            left = -jnp.matmul(mine, jnp.matmul(
                L[..., lo:lo + sub, :lo], done, precision=_EXACT),
                precision=_EXACT)
            row = jnp.concatenate([left, mine], axis=-1)
        else:
            row = mine
        rows.append(jnp.concatenate(
            [row, jnp.zeros(lead + (sub, C - lo - sub), _F32)], axis=-1))
        done = jnp.concatenate(rows, axis=-2)[..., :lo + sub]
    return jnp.concatenate(rows, axis=-2)


def _chunked(q, k, v, g, beta, chunk: int, sub: int):
    """``kda_chunked`` for one group of sequences whose length is whole
    chunks."""
    N, T, H, dk = k.shape
    dv = v.shape[-1]
    C, c, nb = chunk, T // chunk, chunk // sub

    def chunks(a):  # [N, T, H, d] -> [N, c, H, C, d]
        return jnp.moveaxis(a.reshape(N, c, C, H, -1), 3, 2).astype(_F32)

    qc, kc, vc, gc = chunks(q), chunks(k), chunks(v), chunks(g)
    bc = chunks(beta[..., None])[..., 0]                 # [N, c, H, C]
    G = jnp.cumsum(gc, axis=3)                           # through position t
    r = (G - gc)[..., ::sub, :]                          # [N, c, H, nb, dk]
    # from the sub-block's start, both sides moved half the span so that
    # neither end leaves float32's normal range (a chip flushes what does)
    down = jnp.exp(G - jnp.repeat(r, sub, axis=3) + _CAP / 2)
    # the key side relative to every row sub-block's start: [N, c, H, nb, C, dk]
    keys = kc[..., None, :, :] * jnp.exp(jnp.minimum(
        r[..., :, None, :] - G[..., None, :, :], _CAP) - _CAP / 2)

    def against_keys(a):  # rows a [N, c, H, C, dk] -> [N, c, H, C, C]
        rows = (a * down).reshape(N, c, H, nb, sub, dk)
        return jnp.einsum("nchitl,nchisl->nchits", rows, keys,
                          precision=_EXACT).reshape(N, c, H, C, C)

    at = jnp.arange(C)
    by_beta = bc[..., None, :]                           # the column's beta
    A = jnp.where(at[:, None] > at[None, :], against_keys(kc) * by_beta, 0.0)
    Aq = jnp.where(at[:, None] >= at[None, :], against_keys(qc) * by_beta, 0.0)
    Tm = _unit_lower_inverse(A, sub)
    gamma = jnp.exp(G)
    U = jnp.matmul(Tm, vc, precision=_EXACT)             # [N, c, H, C, dv]
    Wk = jnp.matmul(Tm, kc * gamma, precision=_EXACT)    # [N, c, H, C, dk]
    q_in = qc * gamma
    last = G[..., -1:, :]
    k_end = kc * jnp.exp(last - G) * bc[..., None]       # to the chunk's end
    whole = jnp.exp(last[..., 0, :])                     # [N, c, H, dk]

    def carry(S, xs):
        U, Wk, Aq, q_in, k_end, whole = xs
        W = U - jnp.matmul(Wk, S, precision=_EXACT)
        o = jnp.matmul(q_in, S, precision=_EXACT) + jnp.matmul(
            Aq, W, precision=_EXACT)
        S = whole[..., None] * S + jnp.einsum(
            "nhck,nhcv->nhkv", k_end, W, precision=_EXACT)
        return S, o

    S, o = jax.lax.scan(
        carry, jnp.zeros((N, H, dk, dv), _F32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (U, Wk, Aq, q_in, k_end, whole)))
    # o: [c, N, H, C, dv] -> [N, T, H, dv]
    return jnp.moveaxis(o, (0, 3), (1, 2)).reshape(N, T, H, dv), S


def kda_chunked(q, k, v, g, beta, chunk: int = 64, sub: int = 16):
    """The delta rule over whole sequences from a zero state, ``chunk``
    positions at a time in sub-blocks of ``sub``. q, k: [N, T, H, dk]; v:
    [N, T, H, dv]; g: [N, T, H, dk] float32, at least ``-80 / sub`` a
    position (0 where a position must not move the state); beta: [N, T, H]
    float32 (0 there too). Returns (o [N, T, H, dv] float32, the state after
    the last position [N, H, dk, dv] float32). T is padded to whole chunks
    here with ``g`` = 0 and ``beta`` = 0; the tiling changes no result."""
    N, T = k.shape[:2]
    if chunk % sub:
        raise ValueError(f"a chunk of {chunk} is not whole sub-blocks of {sub}")
    pad = -T % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    per = max(1, _SCAN_TOKENS // (T + pad))
    if N <= per or N % per:
        o, S = _chunked(q, k, v, g, beta, chunk, sub)
    else:
        o, S = jax.lax.map(
            lambda a: _chunked(*a, chunk, sub),
            tuple(a.reshape(N // per, per, *a.shape[1:])
                  for a in (q, k, v, g, beta)))
        o, S = o.reshape(N, *o.shape[2:]), S.reshape(N, *S.shape[2:])
    return o[:, :T], S

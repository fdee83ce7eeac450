"""Plain reference of the ``ouro`` shape as Ouro-2.6B's ``config.json`` gives
it and as the file's ``assumed`` reads what it leaves open, written from the
equations and not from the program. Straightforward ``jax.numpy`` in float32
at ``highest`` matmul precision: no cache, no kernels, no batching, no loop of
a program — a Python loop over the passes ``u`` and the layers ``l``, a layer
at a time so that it fits beside an engine, attention as one masked softmax
over the pass's OWN full-sequence keys and values; weights come from (seed,
layer) alone (``lib/weights_looped.py``).

Pass ``u`` = 1 .. U of layer ``l`` at position ``t`` on hidden ``x``, ``N(x) =
x / sqrt(mean x^2 + eps) . g`` with a scale ``g`` of its own:

    x = E[token]                                  before the first pass
    a = N1(x);  q, k, v = a.Wq, a.Wk, a.Wv as heads of hd; q and k rotated
        over the whole head at t, half-split (lane i with lane i + hd / 2),
        base rope_theta — the same t at every pass
    o_t = sum_j softmax_j(q_t . k_j / sqrt(hd)) v_j  over j <= t, k and v
        those of THIS pass of this layer (plane (u - 1) L + l)
    x = x + N2(o . Wo);   x = x + N4(Wdown (silu(Wgate m) * Wup m)), m = N3(x)
    after layer L - 1:  h_u = N_f(x);  lam_u = sigmoid(h_u . w_g + b_g);
        x = h_u goes into pass u + 1
    p_u = lam_u prod_{j<u} (1 - lam_j) for u < U, p_U = prod_{j<U} (1 - lam_j);
        u* = the first u with sum_{j<=u} p_j >= exit_threshold, U if none;
        logits = h_{u*} . W_head.  Every pass is computed whatever u* is.

``mode`` puts the reference in the program's place at a lower precision, as
the control of ``correct`` (``reference/dense_gqa.py``: "bfloat16" and "fp8"
round every matmul input). ``variant`` changes the mathematics, for the
controls that must FAIL the comparison: any field of the config by its name
(``n_passes``, ``exit_threshold``, ``rope_theta``) and what is no field:

* ``read``: whose keys and values a pass attends — ``"first"`` (pass 1's
  plane of the layer), ``"previous"`` (pass u - 1's; pass 1 its own),
  ``"last"`` (the last pass's rows of the positions BEFORE, taken from the
  sound model, and the pass's own row at its own position: what a step sees
  that reads the last pass's cache); ``read_from``: the first position whose
  queries do so (before it: sound). ``{"read": "last", "read_from": prompt}``
  is one plane a layer written by all passes in turn, as a prefill that
  attends its fresh keys and a decode that reads the plane would leave it.
* ``norms``: ``"pre"`` (N2 and N4 dropped) or ``"post"`` (N1 and N3 dropped);
  ``carry``: ``"raw"`` (pass u + 1 takes x, not N_f(x); gate and head normed);
  ``position_step``: a pass rotates at ``t + step . (u - 1)``; ``head_on``:
  ``"mean"`` (the head on the mean of all passes' states); ``rotary_share``:
  the share of a head's lanes that rotate."""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import weights_looped as W
from benchmarks.reference.dense_gqa import _HI, _f32, _mm, _round

_NOT_FIELDS = ("read", "read_from", "norms", "carry", "position_step",
               "head_on", "rotary_share")


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rotate(x, positions, lanes: int, theta: float):
    """x: [T, heads, hd]; the row at ``positions[t]`` rotates lane i with
    lane i + lanes / 2, i < lanes / 2, by ``position . theta^(-2i / lanes)``;
    lanes past ``lanes`` pass."""
    half = lanes // 2
    inv = 1.0 / (theta ** (jnp.arange(0, lanes, 2, dtype=jnp.float32) / lanes))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:lanes]
    return jnp.concatenate([a * c - b * s, a * s + b * c, x[..., lanes:]], -1)


def attention(q, k, v, mode: str, theirs=None, read_from: int = 0,
              own_row: bool = False):
    """q: [T, H, hd]; k, v: [T, KV, hd]; position t attends j <= t. ``theirs``
    = (k, v) of another plane: the queries from ``read_from`` on attend ITS
    rows (``own_row``: but their own row at j = t). Returns [T, H * hd]."""
    T, H, hd = q.shape
    head_of = jnp.arange(H) // (H // k.shape[1])

    def scores(kk):
        return jnp.einsum("qhd,thd->hqt", _round(q, mode),
                          _round(kk, mode)[:, head_of], precision=_HI
                          ) / jnp.sqrt(jnp.float32(hd))

    rows, cols = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    s = scores(k)
    if theirs is not None:
        swap = (rows >= read_from) & ((cols < rows) | (not own_row))
        s = jnp.where(swap[None], scores(theirs[0]), s)
    p = jax.nn.softmax(jnp.where((cols <= rows)[None], s, -1e30), axis=-1)

    def values(pp, vv):
        return jnp.einsum("hqt,thd->qhd", _round(pp, mode),
                          _round(vv, mode)[:, head_of], precision=_HI)

    if theirs is None:
        return values(p, v).reshape(T, H * hd)
    out = values(jnp.where(swap[None], 0.0, p), v) + values(
        jnp.where(swap[None], p, 0.0), theirs[1])
    return out.reshape(T, H * hd)


def layer(w, x, cfg, u: int, mode: str, extra: tuple = (), theirs=None):
    """Pass ``u`` (from 0) of one layer. x: [T, D] float32 -> (x, k [T, KV *
    hd] as attended, v [T, KV * hd])."""
    var = dict(extra)
    T, _ = x.shape
    H, KV, hd, eps = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.rms_norm_eps
    norms = var.get("norms", "both")
    pre = (lambda y, n: _rms(y, w[n]["scale"], eps)) if norms != "post" else (
        lambda y, n: y)
    post = (lambda y, n: _rms(y, w[n]["scale"], eps)) if norms != "pre" else (
        lambda y, n: y)
    nq, nkv = H * hd, KV * hd
    wq, wk, wv = (w["wqkv"]["kernel"][:, a:b] for a, b in (
        (0, nq), (nq, nq + nkv), (nq + nkv, nq + 2 * nkv)))
    ff = w["w_gate_up"]["kernel"].shape[1] // 2
    a = pre(x, "norm1")
    at = jnp.arange(T) + var.get("position_step", 0) * u
    lanes = int(hd * var.get("rotary_share", 1.0))
    q = rotate(_mm(a, wq, mode).reshape(T, H, hd), at, lanes, cfg.rope_theta)
    k = rotate(_mm(a, wk, mode).reshape(T, KV, hd), at, lanes, cfg.rope_theta)
    v = _mm(a, wv, mode).reshape(T, KV, hd)
    if theirs is not None:
        theirs = tuple(t.reshape(T, KV, hd) for t in theirs)
    o = attention(q, k, v, mode, theirs, var.get("read_from", 0),
                  var.get("read") == "last")
    x = x + post(_mm(o, w["wo"]["kernel"], mode), "norm2")
    m = pre(x, "norm3")
    y = _mm(jax.nn.silu(_mm(m, w["w_gate_up"]["kernel"][:, :ff], mode))
            * _mm(m, w["w_gate_up"]["kernel"][:, ff:], mode),
            w["w_down"]["kernel"], mode)
    return x + post(y, "norm4"), k.reshape(T, nkv), v.reshape(T, nkv)


@partial(jax.jit, static_argnames=("cfg", "u", "mode", "extra"))
def _layer_jit(w, x, theirs, cfg, u, mode, extra):
    return layer(_f32(w), x, cfg, u, mode, extra, theirs)


@partial(jax.jit, static_argnames=("cfg",))
def _close_jit(closing, x, cfg):
    """h = N_f(x) and the gate lam = sigmoid(h . w_g + b_g): float32."""
    closing = _f32(closing)
    h = _rms(x, closing["norm"]["scale"], cfg.rms_norm_eps)
    gate = closing["gate"]
    return h, jax.nn.sigmoid(jnp.matmul(h, gate["kernel"], precision=_HI)
                             + gate["bias"])


@partial(jax.jit, static_argnames=("mode",))
def _logits_jit(head, x, mode):
    return _mm(x, head.astype(jnp.float32), mode)


def exit_rule(lams, threshold: float):
    """The gates of the passes, ``lams`` [U, ...] -> (the exit's pdf [U, ...],
    the chosen pass [...] counted from 1), float32 in the passes' order."""
    lams = np.asarray(lams, np.float32)
    left, total = np.ones_like(lams[0]), np.zeros_like(lams[0])
    depth = np.zeros(lams[0].shape, np.int32)
    pdf = []
    for u, lam in enumerate(lams):
        pdf.append(left if u == len(lams) - 1 else lam * left)
        total = total + pdf[-1]
        depth = np.where((depth == 0) & (total >= np.float32(threshold)),
                         u + 1, depth)
        left = left * (np.float32(1) - lam)
    return np.stack(pdf), np.where(depth == 0, len(lams), depth)


def varied(cfg, variant: dict | None):
    """(the config a ``variant`` departs to, what of it is no field)."""
    variant = dict(variant or {})
    extra = tuple(sorted((k, variant.pop(k)) for k in _NOT_FIELDS
                         if k in variant))
    return dataclasses.replace(cfg, **variant), extra


def forward(seed: int, cfg, tokens, *, mode: str = "float32",
            variant: dict | None = None, logits_from: int = 0,
            planes=None, zero_col: int | None = None) -> dict:
    """Full forward pass over ``tokens`` [T]: ``logits`` [T - logits_from,
    vocab] of the positions from ``logits_from`` on; ``k`` and ``v`` {plane:
    [T, KV * hd]} of the planes asked for (None: all; plane ``u L + l`` with
    the L of ``cfg`` as given, zeros where a variant has no such pass); and of
    the positions from ``logits_from`` on every pass's ``h`` [U, T', D] and
    gate ``lam`` [U, T'], the exit's ``pdf`` [U, T'] and the chosen pass
    ``depth`` [T'] (from 1)."""
    L = cfg.n_layers
    sound = cfg
    cfg, extra = varied(cfg, variant)
    var = dict(extra)
    read = var.get("read", "own")
    key = W.seed_key(seed)
    tokens = jnp.asarray(tokens, jnp.int32)
    T, U = tokens.shape[0], cfg.n_passes
    wanted = range(sound.planes) if planes is None else planes
    theirs_of = {}
    if read == "last":  # the last pass's rows of the sound model
        last = forward(seed, sound, tokens, mode=mode, logits_from=T - 1,
                       planes=[(sound.n_passes - 1) * L + l for l in range(L)])
        theirs_of = {l: (last["k"][(sound.n_passes - 1) * L + l],
                         last["v"][(sound.n_passes - 1) * L + l])
                     for l in range(L)}
    x = W.embedding(key, cfg)[tokens].astype(jnp.float32)
    closing = W.close(key, cfg)
    ks, vs, hs, lams = {}, {}, [], []
    for u in range(U):
        made = {}
        for l in range(L):
            theirs = theirs_of.get(l) if (read != "own" and (
                u > 0 or read == "last")) else None
            x, k, v = _layer_jit(W.layer_from_seed(key, cfg, l), x, theirs,
                                 cfg, u, mode, extra)
            made[l] = (k, v)
            if u * L + l in wanted:
                ks[u * L + l], vs[u * L + l] = k, v
        if read == "previous" or (read == "first" and u == 0):
            theirs_of = made
        h, lam = _close_jit(closing, x, cfg)
        hs.append(h[logits_from:]), lams.append(lam[logits_from:])
        if var.get("carry") != "raw":
            x = h
    zeros = jnp.zeros((T, cfg.n_kv_heads * cfg.head_dim), jnp.float32)
    for plane in wanted:  # a pass the variant does not have
        ks.setdefault(plane, zeros), vs.setdefault(plane, zeros)
    hs = jnp.stack(hs)
    pdf, depth = exit_rule(jnp.stack(lams), cfg.exit_threshold)
    chosen = (hs.mean(axis=0) if var.get("head_on") == "mean" else
              jnp.take_along_axis(hs, jnp.asarray(depth - 1)[None, :, None],
                                  axis=0)[0])
    logits = _logits_jit(W.head(key, cfg, zero_col)["kernel"], chosen, mode)
    return {"logits": logits, "k": ks, "v": vs, "h": hs,
            "lam": np.stack([np.asarray(a) for a in lams]), "pdf": pdf,
            "depth": depth}

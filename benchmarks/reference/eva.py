"""Plain reference of the EVA-attention byte model as EvaByte's ``config.json``
gives it (``attention_class: "eva"``, ``chunk_size`` 16, ``window_size``
2048), written from the layer's equations and not from the program.
Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
cache, no kernels, no batching. For each query the rows it attends are built
from the definition — the positions of its own window up to itself, and the
pooled pairs of every chunk of every earlier window — and ONE softmax is taken
over them, a block of queries at a time so that 12k positions of 32 heads fit;
weights come from (seed, layer) alone (``lib/weights_eva.py``).

With ``W = window_size``, ``C = chunk_size``, ``P = W / C``, x ``[T, D]``:

    x' = x + Attn(N(x)) ;  x'' = x' + W_down(silu(W_gate h) . W_up h), h = N(x')
    N(x) = x . rsqrt(mean(x^2) + eps) . (1 + g)
    q, k, v = h.Wq, h.Wk, h.Wv as H heads of hd; q and k rotate over the whole
            head (half-split, theta) at the position's own index
    pair of chunk c, head h:  pi_s = softmax over the chunk's C positions s of
            phi_h . k_s / sqrt(hd);  k^_c = sum pi_s k_s + mu_h;  v^_c = sum pi_s v_s
    query t attends positions s with W.(t // W) <= s <= t and pairs c < P.(t // W),
            scores q.k / sqrt(hd) and q.k^ / sqrt(hd), one softmax, sum p v + sum p v^
    head:   N_f(x) . W_head, [n_pred_heads x vocab] columns head-major, float32

``mode`` puts the reference in the program's place at a lower precision, as
the control of ``correct`` (``reference/dense_gqa.py``: "bfloat16" and "fp8"
round every matmul input, the attention's and the pooling's too). ``variant``
changes the mathematics, for the controls that must FAIL the comparison:
``pool`` ("mean": keys and values pooled by the mean), ``no_mu``,
``own_pairs`` (the pairs of the query's own window's complete chunks attended
too), ``sliding`` (exact keys within ``t - s < W`` instead of the aligned
window), ``two_softmax`` (exact rows and pairs each under a softmax of their
own, added), ``unrotated_pairs`` (pairs pooled from the keys before rotation),
``residual`` ("bfloat16": the residual stream rounded after every addition),
``no_pairs_from`` (an int n: the chunks not complete at length n get no pair
— a program that pools at prefill alone), ``pad`` with ``pad_from`` (the pairs
of the chunks that overlap ``[pad_from, pad)`` pooled from a prompt padded
with token 0 there — a program that pools its pad positions)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import weights_eva as W
from benchmarks.reference.dense_gqa import _HI, _f32, _mm, _rope, _round


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + g)


def pairs(w, k, v, cfg, mode: str, var: dict):
    """The pooled pair of every whole chunk of k, v [T, H, hd] -> (k^, v^)
    [T // C, H, hd]."""
    C, hd = cfg.chunk_size, k.shape[-1]
    n = k.shape[0] // C
    kc = k[:n * C].reshape(n, C, *k.shape[1:])
    vc = v[:n * C].reshape(n, C, *v.shape[1:])
    if var.get("pool") == "mean":
        pi = jnp.full(kc.shape[:3], 1.0 / C)
    else:
        s = jnp.einsum("nchd,hd->nch", _round(kc, mode), _round(w["phi"], mode),
                       precision=_HI) / jnp.sqrt(jnp.float32(hd))
        pi = jax.nn.softmax(s, axis=1)
    kh = jnp.sum(pi[..., None] * kc, axis=1)
    if not var.get("no_mu"):
        kh = kh + w["mu"]
    vh = jnp.sum(pi[..., None] * vc, axis=1)
    if "no_pairs_from" in var:
        made = (jnp.arange(n) + 1) * C <= var["no_pairs_from"]
        kh, vh = (jnp.where(made[:, None, None], a, 0.0) for a in (kh, vh))
    return kh, vh


def attention(q, k, v, kh, vh, cfg, mode: str, var: dict, q_block: int):
    """q, k, v: [T, H, hd]; kh, vh: [Nc, H, hd]. A block of queries at a
    time, each query's rows from the definition. Returns [T, H * hd]."""
    T, H, hd = q.shape
    Wn, C = cfg.window_size, cfg.chunk_size
    blk = min(q_block, T)
    qp = jnp.pad(_round(q, mode), ((0, -T % blk), (0, 0), (0, 0))
                 ).reshape(-1, blk, H, hd)
    kr, vr, khr, vhr = (_round(a, mode) for a in (k, v, kh, vh))
    cols, cc = jnp.arange(T)[None, :], jnp.arange(kh.shape[0])[None, :]
    scale = jnp.sqrt(jnp.float32(hd))

    def block(args):
        qb, first = args
        t = first + jnp.arange(blk)[:, None]
        ok = cols <= t
        ok &= (t - cols < Wn) if var.get("sliding") else (cols >= t // Wn * Wn)
        okp = ((cc + 1) * C <= t) if var.get("own_pairs") else (
            cc < t // Wn * (Wn // C))
        s = jnp.einsum("qhd,thd->hqt", qb, kr, precision=_HI) / scale
        sp = jnp.einsum("qhd,chd->hqc", qb, khr, precision=_HI) / scale
        s = jnp.where(ok[None], s, -1e30)
        sp = jnp.where(okp[None], sp, -1e30)
        if var.get("two_softmax"):
            p = jax.nn.softmax(s, axis=-1)
            pp = jax.nn.softmax(sp, axis=-1) * jnp.any(okp, axis=-1)[None, :, None]
        else:
            both = jax.nn.softmax(jnp.concatenate([s, sp], axis=-1), axis=-1)
            p, pp = both[..., :T], both[..., T:]
        return (jnp.einsum("hqt,thd->qhd", _round(p, mode), vr, precision=_HI)
                + jnp.einsum("hqc,chd->qhd", _round(pp, mode), vhr, precision=_HI))

    out = jax.lax.map(block, (qp, jnp.arange(qp.shape[0]) * blk))
    return out.reshape(-1, H * hd)[:T]


def layer(w, x, cfg, mode: str, variant: tuple = (), q_block: int = 128):
    """One layer. x: [T, D] float32 -> (x, k [T, H * hd] as attended, v, k^
    [T // C, H * hd], v^)."""
    var = dict(variant)
    T = x.shape[0]
    H, hd = cfg.n_heads, cfg.head_dim

    def add(x, y):
        x = x + y
        return _round(x, var["residual"]) if "residual" in var else x

    h = _norm(x, w["attn_norm"]["scale"], cfg.rms_norm_eps)
    q = _mm(h, w["wq"]["kernel"], mode).reshape(T, H, hd)
    k0 = _mm(h, w["wk"]["kernel"], mode).reshape(T, H, hd)
    v = _mm(h, w["wv"]["kernel"], mode).reshape(T, H, hd)
    q, k = _rope(q[None], cfg.rope_theta)[0], _rope(k0[None], cfg.rope_theta)[0]
    kh, vh = pairs(w, k0 if var.get("unrotated_pairs") else k, v, cfg, mode, var)
    att = attention(q, k, v, kh, vh, cfg, mode, var, q_block)
    x = add(x, _mm(att, w["wo"]["kernel"], mode))
    h = _norm(x, w["ffn_norm"]["scale"], cfg.rms_norm_eps)
    y = _mm(jax.nn.silu(_mm(h, w["w_gate"]["kernel"], mode))
            * _mm(h, w["w_up"]["kernel"], mode), w["w_down"]["kernel"], mode)
    flat = (-1, H * hd)
    return (add(x, y), k.reshape(flat), v.reshape(flat), kh.reshape(flat),
            vh.reshape(flat))


@partial(jax.jit, static_argnames=("cfg", "mode", "variant", "q_block"))
def _layer_jit(w, x, cfg, mode, variant, q_block):
    return layer(_f32(w), x, cfg, mode, variant, q_block)


@partial(jax.jit, static_argnames=("cfg", "mode"))
def _logits_jit(tail, x, cfg, mode):
    tail = _f32(tail)
    h = _norm(x, tail["norm"]["scale"], cfg.rms_norm_eps)
    return _mm(h, tail["lm_head"]["kernel"], mode).reshape(
        -1, cfg.n_pred_heads, cfg.vocab_size)


def forward(seed: int, cfg, tokens, *, mode: str = "float32",
            variant: dict | None = None, logits_from: int = 0,
            layers: tuple = (0, -1), zero_col: int | None = None,
            q_block: int = 128) -> dict:
    """Full forward pass over ``tokens`` [T]: ``logits`` [T - logits_from,
    n_pred_heads, vocab] of the positions from ``logits_from`` on (all the
    heads; head j of position t scores token t + 1 + j) and, for each layer
    of ``layers``, the keys and values as its attention reads them ``k``,
    ``v`` ``{layer: [T, H * hd]}`` and the pairs of every whole chunk
    ``kh``, ``vh`` ``{layer: [T // C, H * hd]}``, on the host (so layer i's
    rows hold the work of layers 0 .. i-1). ``zero_col``: the eos id whose
    head-0 column the seeded head has zeroed."""
    variant = dict(variant or {})
    pad = {k: variant.pop(k) for k in ("pad", "pad_from") if k in variant}
    out = _forward(seed, cfg, tokens, mode, variant, logits_from, layers,
                   zero_col, q_block)
    if pad:
        C, Wn = cfg.chunk_size, cfg.window_size
        c0, c1 = pad["pad_from"] // C, pad["pad"] // C
        if len(tokens) > (c0 * C // Wn + 1) * Wn:
            raise ValueError("a query of the sequence sees the padded pairs")
        padded = list(tokens[:pad["pad_from"]]) + [0] * (pad["pad"] - pad["pad_from"])
        wrong = _forward(seed, cfg, padded, mode, variant, len(padded) - 1,
                         layers, zero_col, q_block)
        for name in ("kh", "vh"):
            for i in out[name]:
                out[name][i] = out[name][i].copy()
                upto = min(c1, len(out[name][i]))  # a sequence may end sooner
                out[name][i][c0:upto] = wrong[name][i][c0:upto]
    return out


def _forward(seed, cfg, tokens, mode, variant, logits_from, layers, zero_col,
             q_block):
    key = W.seed_key(seed)
    x = W.embedding(key, cfg).astype(jnp.float32)[jnp.asarray(tokens, jnp.int32)]
    if "residual" in variant:
        x = _round(x, variant["residual"])
    keep = {i % cfg.n_layers for i in layers}
    out = {n: {} for n in ("k", "v", "kh", "vh")}
    var = tuple(sorted(variant.items()))
    for i in range(cfg.n_layers):
        x, *rows = _layer_jit(W.layer_from_seed(key, cfg, i), x, cfg, mode, var,
                              q_block)
        if i in keep:
            for name, a in zip(("k", "v", "kh", "vh"), rows):
                out[name][i] = np.asarray(a)
    out["logits"] = _logits_jit(W.head(key, cfg, zero_col), x[logits_from:],
                                cfg, mode)
    return out

"""Plain reference of the Cohere2 sparse-expert shape as
command-a-plus-05-2026's ``config.json`` gives it, written from the layer's
equations and not from the program. Straightforward ``jax.numpy`` in float32
at ``highest`` matmul precision: no cache, no kernels, no batching, no grouped
product — every held expert is applied to every token and the unchosen ones
are weighed by zero. Attention is computed a block of queries at a time so
that 8k positions of 128 heads fit; weights come from (seed, layer) alone
(``lib/weights_cohere2_moe.py``).

Every layer, with ``h = LN(x)``, ``LN(x) = (x - mean x) / sqrt(var x + eps)
. g`` (no bias; ONE norm feeds both halves):

    x' = x + Attn_l(h) + MoE(h)
    Attn_l: q = h.Wq as H heads of hd, k = h.Wk, v = h.Wv as KV heads of hd;
            scores q.k / sqrt(hd), query head i on KV head i // (H / KV);
            layer_types[l] == "sliding_attention": q and k rotate in adjacent
            pairs (lanes 2i, 2i + 1 by pos . theta^(-2i / hd)) and position i
            attends j where 0 <= i - j < sliding_window;
            "full_attention": no rotation, every j <= i.
    MoE:    s = sigmoid(h.Wr); the k largest s (first of equals: the lower
            index); w_e = s_e / sum of the chosen s;
            routed = sum over the HELD chosen e of w_e . SwiGLU_e(h);
            shared = (1 / n_shared) . sum_j SwiGLU_j(h);  MoE = routed + shared
    head:   logits = logit_scale . LN_f(x) . E^T over the held rows of E.

``mode`` puts the reference in the program's place at a lower precision, as
the control of ``correct`` (``reference/dense_gqa.py``: "bfloat16" and "fp8"
round every matmul input, the router's too). ``variant`` changes the
mathematics, for the controls that must FAIL the comparison: ``window`` (an
int: another window; None: window layers attend everything), ``rotate_full``
(full layers rotate too), ``shared`` ("sum": the shared experts summed, not
averaged), ``sequential`` (the expert half reads ``LN(x + Attn)``)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.lib import weights_cohere2_moe as W
from benchmarks.reference.dense_gqa import _HI, _f32, _mm, _round

_SAME = "as published"


def _layer_norm(x, g, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rotate_pairs(x, theta: float):
    """x: [T, heads, hd]; position t rotates lanes (2i, 2i + 1)."""
    T, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * c - b * s, a * s + b * c], axis=-1).reshape(x.shape)


def attention(q, k, v, window, mode: str, q_block: int):
    """q: [T, H, hd]; k, v: [T, KV, hd]; position i attends j <= i, and
    i - j < window where window is a number. A block of queries at a time.
    Returns [T, H * hd]."""
    T, H, hd = q.shape
    KV = k.shape[1]
    blk = min(q_block, T)
    pad = -T % blk
    qp = jnp.pad(_round(q, mode), ((0, pad), (0, 0), (0, 0))).reshape(
        -1, blk, KV, H // KV, hd)
    kr, vr = _round(k, mode), _round(v, mode)
    cols = jnp.arange(T)[None, :]

    def block(args):
        qb, first = args
        rows = first + jnp.arange(blk)[:, None]
        ok = cols <= rows
        if window is not None:
            ok &= rows - cols < window
        s = jnp.einsum("qkgd,tkd->kgqt", qb, kr, precision=_HI
                       ) / jnp.sqrt(jnp.float32(hd))
        p = jax.nn.softmax(jnp.where(ok[None, None], s, -1e30), axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", _round(p, mode), vr, precision=_HI)

    out = jax.lax.map(block, (qp, jnp.arange(qp.shape[0]) * blk))
    return out.reshape(-1, H * hd)[:T]


def route(h, router, cfg, mode: str):
    """h: [T, D] -> (chosen [T, k], combine [T, E]: each token's weight for
    each expert, zero for the unchosen)."""
    s = jax.nn.sigmoid(_mm(h, router, mode))
    left, chosen = s, []
    for _ in range(cfg.n_experts_per_tok):   # k rounds of "the largest left"
        e = jnp.argmax(left, axis=-1)        # first of equals: the lower index
        chosen.append(e)
        left = left.at[jnp.arange(h.shape[0]), e].set(-jnp.inf)
    chosen = jnp.stack(chosen, axis=-1)
    picked = jnp.zeros_like(s).at[jnp.arange(h.shape[0])[:, None], chosen].set(1.0)
    w = s * picked
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return chosen, w


def _swiglu(h, w_gate, w_up, w_down, mode):
    return _mm(jax.nn.silu(_mm(h, w_gate, mode)) * _mm(h, w_up, mode), w_down, mode)


def routed_sum(h, combine, experts, held, mode: str):
    """sum over the held experts of combine[:, e] * SwiGLU_e(h): every held
    expert on every token, one expert at a time (its float32 copy is cast
    here)."""
    lo, hi = held

    def one(acc, xs):
        wg, wu, wd, cw = xs
        y = _swiglu(h, wg.astype(jnp.float32), wu.astype(jnp.float32),
                    wd.astype(jnp.float32), mode)
        return acc + y * cw[:, None], None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        experts["w_gate"], experts["w_up"], experts["w_down"],
        combine[:, lo:hi].T))
    return out


def moe(w, h, cfg, mode: str, held=None, shared: str = "average"):
    """The expert half on h [T, D] (normed). ``held`` = (lo, hi) gives one
    holder's routed part (``w["experts"]`` then holds those experts alone);
    ``shared``: "average" as published, "sum" (a control), or None (left
    out: for adding the holders' parts up). Returns (y, chosen)."""
    chosen, combine = route(h, w["router"]["kernel"], cfg, mode)
    y = routed_sum(h, combine, w["experts"], held or cfg.held, mode)
    if shared is not None:
        F, sh = cfg.d_expert, w["shared"]
        each = [_swiglu(h, sh["w_gate"]["kernel"][:, j * F:(j + 1) * F],
                        sh["w_up"]["kernel"][:, j * F:(j + 1) * F],
                        sh["w_down"]["kernel"][j * F:(j + 1) * F], mode)
                for j in range(cfg.n_shared_experts)]
        y = y + (sum(each) if shared == "sum" else sum(each) / len(each))
    return y, chosen


def layer(w, x, cfg, i: int, mode: str, variant: tuple = (), q_block: int = 128):
    """One layer. x: [T, D] float32 -> (x, k [T, KV * hd] as attended, v,
    chosen [T, k])."""
    var = dict(variant)
    T, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    is_window = cfg.layer_types[i] == "sliding_attention"
    g = w["norm"]["scale"]
    h = _layer_norm(x, g, cfg.layer_norm_eps)
    q = _mm(h, w["wq"]["kernel"], mode).reshape(T, H, hd)
    k = _mm(h, w["wk"]["kernel"], mode).reshape(T, KV, hd)
    v = _mm(h, w["wv"]["kernel"], mode).reshape(T, KV, hd)
    if is_window or var.get("rotate_full"):
        q, k = _rotate_pairs(q, cfg.rope_theta), _rotate_pairs(k, cfg.rope_theta)
    window = var.get("window", cfg.sliding_window) if is_window else None
    att = _mm(attention(q, k, v, window, mode, q_block), w["wo"]["kernel"], mode)
    if var.get("sequential"):
        h = _layer_norm(x + att, g, cfg.layer_norm_eps)
    y, chosen = moe(w["moe"], h, cfg, mode, shared=var.get("shared", "average"))
    return x + att + y, k.reshape(T, KV * hd), v.reshape(T, KV * hd), chosen


def _f32_but_experts(w):
    moe_w = w["moe"]
    out = _f32({k: v for k, v in w.items() if k != "moe"})
    out["moe"] = {**_f32({k: v for k, v in moe_w.items() if k != "experts"}),
                  "experts": moe_w["experts"]}
    return out


@partial(jax.jit, static_argnames=("cfg", "i", "mode", "variant", "q_block"))
def _layer_jit(w, x, cfg, i, mode, variant, q_block):
    return layer(_f32_but_experts(w), x, cfg, i, mode, variant, q_block)


@partial(jax.jit, static_argnames=("cfg", "mode"))
def _logits_jit(emb, x, cfg, mode):
    x = _layer_norm(x, jnp.ones((cfg.d_model,), jnp.float32), cfg.layer_norm_eps)
    return cfg.logit_scale * _mm(x, emb.astype(jnp.float32).T, mode)


def forward(seed: int, cfg, tokens, *, mode: str = "float32",
            variant: dict | None = None, logits_from: int = 0,
            q_block: int = 128) -> dict:
    """Full forward pass over ``tokens`` [T] (ids over the held slice of the
    vocabulary): ``logits`` [T - logits_from, held rows] of the positions
    from ``logits_from`` on, every layer's keys and values as its attention
    reads them ``k``, ``v`` [L, T, KV * hd] (so layer i's rows hold the work
    of layers 0 .. i-1), and every layer's choices ``chosen`` [L, T, k]."""
    key = W.seed_key(seed)
    emb = W.embedding(key, cfg)
    x = emb.astype(jnp.float32)[jnp.asarray(tokens, jnp.int32)]
    variant = tuple(sorted((variant or {}).items()))
    ks, vs, chosen = [], [], []
    for i in range(cfg.n_layers):
        x, k, v, ch = _layer_jit(W.layer_from_seed(key, cfg, i), x, cfg, i, mode,
                                 variant, q_block)
        ks.append(k), vs.append(v), chosen.append(ch)
    return {"logits": _logits_jit(emb, x[logits_from:], cfg, mode),
            "k": jnp.stack(ks), "v": jnp.stack(vs), "chosen": jnp.stack(chosen)}

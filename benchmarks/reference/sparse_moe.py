"""Plain reference of the learned-sparse-attention expert shape as
Keye-VL-2.0-30B-A3B's ``config.json`` gives its language model, written from
the layer's equations and not from the program. Straightforward ``jax.numpy``
in float32 at ``highest`` matmul precision: no cache, no kernels, no batching,
no grouped product — every held expert is applied to every token and the
unchosen ones are weighed by zero. Scores, selection and attention are
computed a block of queries at a time so that 8k positions fit; the selection
is ``lax.top_k`` over the reference's OWN float32 scores (it is never handed
the program's picks); weights come from (seed, layer) alone
(``lib/weights_sparse_moe.py``).

Every layer, with ``RMS(x) = x / sqrt(mean x^2 + eps) . g``:

    h  = RMS_a(x)
    q  = h.Wq as H heads of hd, k = h.Wk, v = h.Wv as KV heads of hd;
         q, k <- RMS per head (gains of width hd), then rotated half-split
         over the whole head at theta
    qI = h.WqI as J heads of dk, kI = LN(h.WkI) (one head), both rotated over
         their dk lanes at theta; w = (h.Ww) / sqrt(J . dk)
    I[t, s] = sum_j w[t, j] . relu(qI[t, j] . kI[s])        for s <= t
    S_t = the topk positions s <= t with the largest I[t, s] (first of
          equals: the lower position); all of them while t < topk
    o_t = softmax over s in S_t of (q_t . k_s / sqrt(hd)) . v_s, query head i
          on KV head i // (H / KV), one S_t for all heads
    x  <- x + o.Wo
    h2 = RMS_f(x); p = softmax(h2.Wr); the k largest p, renormalised to sum 1
    x  <- x + sum over the HELD chosen e of p_e . SwiGLU_e(h2)
    head: logits = RMS(x) . W_head over the held rows.

``mode`` puts the reference in the program's place at a lower precision, as
the control of ``correct`` ("bfloat16" rounds every matmul input, the
router's and the indexer's too). ``variant`` changes the mathematics, for the
controls that must FAIL the comparison: ``select`` ("none": every s <= t is
attended; "recent": the topk most recent positions instead of the learned
pick), ``topk`` (another count), ``parallel`` (the expert half reads
``RMS_f(x)`` of the layer's input, not of ``x + attention``), ``router``
("sigmoid": sigmoid scores normalised over the chosen), ``shared`` (held
expert 0 is added to every token as a shared expert)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.lib import weights_sparse_moe as W
from benchmarks.reference.cohere2_moe import (
    _f32_but_experts, _layer_norm, _swiglu, routed_sum)
from benchmarks.reference.dense_gqa import _HI, _mm, _rope, _round


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def pick(scores, valid, k: int):
    """The ``k`` largest valid scores of each row as a mask, first of equals
    the lower position; every valid one where there are no more."""
    k = min(k, scores.shape[-1])
    _, idx = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf), k)
    rows = jnp.arange(scores.shape[0])[:, None]
    return jnp.zeros(scores.shape, bool).at[rows, idx].set(True) & valid


def attention(q, k, v, qi, ki, w, cfg, mode: str, var: dict, q_block: int):
    """q: [T, H, hd]; k, v: [T, KV, hd]; qi: [T, J, dk]; ki: [T, dk]; w:
    [T, J]. A block of queries at a time: score, select, attend. Returns
    ([T, H * hd], how many keys each query attended [T])."""
    T, H, hd = q.shape
    KV = k.shape[1]
    blk = min(q_block, T)
    pad = -T % blk
    topk = var.get("topk", cfg.topk)
    how = var.get("select", "learned")

    def blocks(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape(-1, blk, *a.shape[1:])

    kr, vr, kir = _round(k, mode), _round(v, mode), _round(ki, mode)
    cols = jnp.arange(T)[None, :]

    def block(args):
        qb, qib, wb, first = args
        rows = first + jnp.arange(blk)[:, None]
        ok = cols <= rows
        if how == "learned":
            s_i = jnp.einsum("qjd,td->qjt", _round(qib, mode), kir, precision=_HI)
            ok = pick((jax.nn.relu(s_i) * wb[:, :, None]).sum(axis=1), ok, topk)
        elif how == "recent":
            ok &= rows - cols < topk
        s = jnp.einsum("qkgd,tkd->kgqt", _round(qb, mode).reshape(
            blk, KV, H // KV, hd), kr, precision=_HI) / jnp.sqrt(jnp.float32(hd))
        p = jax.nn.softmax(jnp.where(ok[None, None], s, -1e30), axis=-1)
        return (jnp.einsum("kgqt,tkd->qkgd", _round(p, mode), vr, precision=_HI),
                ok.sum(axis=-1))

    out, n = jax.lax.map(block, (blocks(q), blocks(qi), blocks(w),
                                 jnp.arange(-(-T // blk)) * blk))
    return out.reshape(-1, H * hd)[:T], n.reshape(-1)[:T]


def route(h, router, cfg, mode: str, how: str = "softmax"):
    """h: [T, D] -> (chosen [T, k], combine [T, E]: each token's weight for
    each expert, zero for the unchosen)."""
    z = _mm(h, router, mode)
    s = jax.nn.softmax(z, axis=-1) if how == "softmax" else jax.nn.sigmoid(z)
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


def moe(w, h, cfg, mode: str, held=None, var: dict | None = None):
    """The expert half on h [T, D] (normed). ``held`` = (lo, hi) gives one
    holder's part (``w["experts"]`` then holds those experts alone): with no
    shared expert the holders' parts add up to the layer. Returns (y,
    chosen)."""
    var = var or {}
    chosen, combine = route(h, w["router"]["kernel"], cfg, mode,
                            var.get("router", "softmax"))
    y = routed_sum(h, combine, w["experts"], held or cfg.held, mode)
    if var.get("shared"):
        e = jax.tree.map(lambda a: a[0].astype(jnp.float32), w["experts"])
        y = y + _swiglu(h, e["w_gate"], e["w_up"], e["w_down"], mode)
    return y, chosen


def layer(w, x, cfg, mode: str, variant: tuple = (), q_block: int = 128):
    """One layer. x: [T, D] float32 -> (x, k [T, KV * hd] as attended, v, kI
    [T, dk] as scored, chosen [T, k], keys attended [T])."""
    var = dict(variant)
    T, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    J, dk, eps = cfg.indexer_heads, cfg.indexer_head_dim, cfg.rms_norm_eps
    h = _rms(x, w["attn_norm"]["scale"], eps)
    q = _rms(_mm(h, w["wq"]["kernel"], mode).reshape(T, H, hd),
             w["q_norm"]["scale"], eps)
    k = _rms(_mm(h, w["wk"]["kernel"], mode).reshape(T, KV, hd),
             w["k_norm"]["scale"], eps)
    v = _mm(h, w["wv"]["kernel"], mode).reshape(T, KV, hd)
    q, k = _rope(q[None], cfg.rope_theta)[0], _rope(k[None], cfg.rope_theta)[0]
    ix = w["indexer"]
    qi = _rope(_mm(h, ix["wq"]["kernel"], mode).reshape(1, T, J, dk),
               cfg.rope_theta)[0]
    ki = _layer_norm(_mm(h, ix["wk"]["kernel"], mode), ix["k_norm"]["scale"], eps)
    ki = _rope(ki[None, :, None, :], cfg.rope_theta)[0, :, 0]
    wj = _mm(h, ix["w"]["kernel"], mode) * (J * dk) ** -0.5
    att, n = attention(q, k, v, qi, ki, wj, cfg, mode, var, q_block)
    x1 = x + _mm(att, w["wo"]["kernel"], mode)
    h2 = _rms(x if var.get("parallel") else x1, w["ffn_norm"]["scale"], eps)
    y, chosen = moe(w["moe"], h2, cfg, mode, var=var)
    return x1 + y, k.reshape(T, KV * hd), v.reshape(T, KV * hd), ki, chosen, n


@partial(jax.jit, static_argnames=("cfg", "mode", "variant", "q_block"))
def _layer_jit(w, x, cfg, mode, variant, q_block):
    return layer(_f32_but_experts(w), x, cfg, mode, variant, q_block)


@partial(jax.jit, static_argnames=("cfg", "mode"))
def _logits_jit(head, x, cfg, mode):
    x = _rms(x, jnp.ones((cfg.d_model,), jnp.float32), cfg.rms_norm_eps)
    return _mm(x, head.astype(jnp.float32), mode)


def forward(seed: int, cfg, tokens, *, mode: str = "float32",
            variant: dict | None = None, logits_from: int = 0,
            q_block: int = 128) -> dict:
    """Full forward pass over ``tokens`` [T] (ids over the held slice of the
    vocabulary): ``logits`` [T - logits_from, held rows] of the positions
    from ``logits_from`` on, every layer's keys, values and indexer keys as
    its attention reads them ``k``, ``v`` [L, T, KV * hd], ``ki`` [L, T, dk]
    (so layer i's rows hold the work of layers 0 .. i-1), every layer's
    expert choices ``chosen`` [L, T, k] and how many keys each query attended
    ``attended`` [L, T]."""
    key = W.seed_key(seed)
    x = W.embedding(key, cfg).astype(jnp.float32)[jnp.asarray(tokens, jnp.int32)]
    variant = tuple(sorted((variant or {}).items()))
    outs = []
    for i in range(cfg.n_layers):
        x, *rest = _layer_jit(W.layer_from_seed(key, cfg, i), x, cfg, mode,
                              variant, q_block)
        outs.append(rest)
    k, v, ki, chosen, n = (jnp.stack(a) for a in zip(*outs))
    return {"logits": _logits_jit(W.head(key, cfg)["kernel"], x[logits_from:],
                                  cfg, mode),
            "k": k, "v": v, "ki": ki, "chosen": chosen, "attended": n}

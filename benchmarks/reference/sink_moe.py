"""Plain reference of the ``mimo_v2_flash`` shape as MiMo-V2-Flash's
``config.json`` gives it, written from the layer's equations and not from the
program. Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision: no cache, no kernels, no batching, no grouped product — every held
expert is applied to every token and the unchosen (and the experts held
elsewhere) are weighed by zero. Attention is one masked softmax, a block of
queries at a time so that 4k positions of 64 heads fit, with the sink as an
appended column that is dropped; weights come from (seed, layer) alone
(``lib/weights_sink_moe.py``).

Layer ``l`` at position ``t`` on hidden ``x``, ``rms(x) = x / sqrt(mean x^2 +
eps) . g``:

    h = rms_a(x);  q = h.Wq as H heads of hd;  k = h.Wk as KV heads of hd;
        v = value_scale . h.Wv as KV heads of hv;   KV = n_kv_heads (full
        layer) or swa_n_kv_heads (window layer); query head n on KV head
        n // (H / KV)
    the first R = int(hd . partial_rotary_factor) lanes of every q and k head
        rotate at t in the half-split form (lane i with lane i + R / 2) at
        base rope_theta (full) or swa_rope_theta (window); the rest pass
    s_tj = q_t.k_j / sqrt(hd) over j <= t (full) or 0 <= t - j < window;
        full: p = softmax_j(s);  window: p_tj = exp(s_tj) / (exp(sink_n) +
        sum_j' exp(s_tj')) — the sink takes mass and gives no value
    x = x + (sum_j p_tj v_j) . Wo
    g = rms_f(x);  layer 0: x = x + SwiGLU(g) at width d_ff;  every other:
        s = sigmoid(g.Wr); the k largest s + e_bias (first of equals: the
        lower index) are chosen, weighed by s alone, the k weights divided
        by their sum; x = x + sum over the HELD chosen e of w_e . SwiGLU_e(g)
    logits = rms(x) . W_head over the held columns.

``mode`` puts the reference in the program's place at a lower precision, as
the control of ``correct`` (``reference/dense_gqa.py``: "bfloat16" and "fp8"
round every matmul input, the router's too). ``variant`` changes the
mathematics, for the controls that must FAIL the comparison: any field of
the config by its name (``sliding_window``, ``value_scale``,
``partial_rotary_factor``, ``swa_rope_theta``, ``norm_topk_prob``,
``sink_window``, ``sink_full``, ``layer_moe``) — a piece the departed model
lacks (a full layer's sink, layer 0's experts) is drawn from the same seed —
and three that are no field: ``sink_value`` (the sink's column keeps a value:
the query's own value row, so its mass is counted), ``window_group`` (query
heads a KV head in a window layer: 16 is the full layers' grouping),
``bias_in_weight`` (the chosen are weighed by ``s + e_bias``)."""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.lib import weights_sink_moe as W
from benchmarks.reference.dense_gqa import _HI, _f32, _mm, _round

_NOT_FIELDS = ("sink_value", "window_group", "bias_in_weight")


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rotate_lanes(x, lanes: int, theta: float):
    """x: [T, heads, hd]; position t rotates lane i with lane i + lanes / 2
    for i < lanes / 2, by ``t . theta^(-2i / lanes)``; lanes past ``lanes``
    pass."""
    T = x.shape[0]
    half = lanes // 2
    inv = 1.0 / (theta ** (jnp.arange(0, lanes, 2, dtype=jnp.float32) / lanes))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:lanes]
    return jnp.concatenate([a * c - b * s, a * s + b * c, x[..., lanes:]], -1)


def attention(q, k, v, window, sink, mode: str, q_block: int,
              group: int | None = None, sink_value: bool = False):
    """q: [T, H, hd]; k: [T, KV, hd]; v: [T, KV, hv]; position i attends
    j <= i, and i - j < window where window is a number; ``sink`` [H] or
    None: one more column of every softmax, dropped. A block of queries at a
    time. Returns (out [T, H * hv], the sink's share of each query head's
    mass [T, H])."""
    T, H, hd = q.shape
    group = group or H // k.shape[1]
    head_of = jnp.arange(H) // group          # the KV head a query head reads
    blk = min(q_block, T)
    pad = -T % blk
    qp = jnp.pad(_round(q, mode), ((0, pad), (0, 0), (0, 0))).reshape(
        -1, blk, H, hd)
    kr, vr = _round(k, mode)[:, head_of], _round(v, mode)[:, head_of]
    cols = jnp.arange(T)[None, :]
    vp = jnp.pad(vr, ((0, pad), (0, 0), (0, 0))).reshape(-1, blk, H, v.shape[-1])

    def block(args):
        qb, own, first = args
        rows = first + jnp.arange(blk)[:, None]
        ok = cols <= rows
        if window is not None:
            ok &= rows - cols < window
        s = jnp.einsum("qhd,thd->hqt", qb, kr, precision=_HI
                       ) / jnp.sqrt(jnp.float32(hd))
        s = jnp.where(ok[None], s, -1e30)
        if sink is not None:
            s = jnp.concatenate([s, jnp.broadcast_to(
                sink[:, None, None], (H, blk, 1))], axis=-1)
        p = jax.nn.softmax(s, axis=-1)
        share = p[..., -1] if sink is not None else jnp.zeros((H, blk))
        if sink is not None:
            p = p[..., :-1]
        out = jnp.einsum("hqt,thd->qhd", _round(p, mode), vr, precision=_HI)
        if sink_value:  # the control: the sink's mass on the query's own row
            out = out + share.T[:, :, None] * own
        return out, share.T

    out, share = jax.lax.map(block, (qp, vp, jnp.arange(qp.shape[0]) * blk))
    return out.reshape(-1, H * v.shape[-1])[:T], share.reshape(-1, H)[:T]


def route(g, router, cfg, mode: str, bias_in_weight: bool = False):
    """g: [T, D] -> (scores s [T, E], chosen [T, k], combine [T, E]: each
    token's weight for each expert, zero for the unchosen)."""
    s = jax.nn.sigmoid(_mm(g, router["kernel"], mode))
    by = s + router["bias"].astype(jnp.float32)
    left, chosen = by, []
    for _ in range(cfg.n_experts_per_tok):   # k rounds of "the largest left"
        e = jnp.argmax(left, axis=-1)        # first of equals: the lower index
        chosen.append(e)
        left = left.at[jnp.arange(g.shape[0]), e].set(-jnp.inf)
    chosen = jnp.stack(chosen, axis=-1)
    picked = jnp.zeros_like(s).at[jnp.arange(g.shape[0])[:, None], chosen].set(1.0)
    w = (by if bias_in_weight else s) * picked
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return s, chosen, w * cfg.routed_scale


def _swiglu(h, w_gate, w_up, w_down, mode):
    return _mm(jax.nn.silu(_mm(h, w_gate, mode)) * _mm(h, w_up, mode), w_down, mode)


def routed_sum(g, combine, experts, held, mode: str):
    """sum over the held experts of combine[:, e] * SwiGLU_e(g): every held
    expert on every token, one expert at a time (its float32 copy is cast
    here); the columns of ``combine`` outside ``held`` are masked out."""
    lo, hi = held

    def one(acc, xs):
        wg, wu, wd, cw = xs
        y = _swiglu(g, wg.astype(jnp.float32), wu.astype(jnp.float32),
                    wd.astype(jnp.float32), mode)
        return acc + y * cw[:, None], None

    out, _ = jax.lax.scan(one, jnp.zeros_like(g), (
        experts["w_gate"], experts["w_up"], experts["w_down"],
        combine[:, lo:hi].T))
    return out


def moe(w, g, cfg, mode: str, held=None, bias_in_weight: bool = False):
    """The expert half on g [T, D] (normed). ``held`` = (lo, hi) gives one
    holder's part (``w["experts"]`` then holds those experts alone).
    Returns (y, scores, chosen)."""
    s, chosen, combine = route(g, w["router"], cfg, mode, bias_in_weight)
    return routed_sum(g, combine, w["experts"], held or cfg.held, mode), s, chosen


def layer(w, x, cfg, i: int, mode: str, extra: tuple = (), q_block: int = 128):
    """One layer. x: [T, D] float32 -> dict(x, k [T, KV * hd] as attended, v
    [T, KV * hv] as cached, att [T, H * hv] before Wo, sink_share [T, H],
    scores [T, E] and chosen [T, k] of an expert layer)."""
    var = dict(extra)
    T, _ = x.shape
    H, hd, hv = cfg.n_heads, cfg.head_dim, cfg.v_head_dim
    window = cfg.is_window(i)
    KV = cfg.kv_heads(window)
    h = _rms(x, w["attn_norm"]["scale"], cfg.rms_norm_eps)
    q = _mm(h, w["wq"]["kernel"], mode).reshape(T, H, hd)
    k = _mm(h, w["wk"]["kernel"], mode).reshape(T, KV, hd)
    v = cfg.value_scale * _mm(h, w["wv"]["kernel"], mode).reshape(T, KV, hv)
    theta = cfg.swa_rope_theta if window else cfg.rope_theta
    q = rotate_lanes(q, cfg.rotary_lanes, theta)
    k = rotate_lanes(k, cfg.rotary_lanes, theta)
    att, share = attention(
        q, k, v, cfg.sliding_window if window else None, w.get("sink"), mode,
        q_block, var.get("window_group") if window else None,
        bool(var.get("sink_value")))
    x = x + _mm(att, w["wo"]["kernel"], mode)
    g = _rms(x, w["ffn_norm"]["scale"], cfg.rms_norm_eps)
    out = {"k": k.reshape(T, KV * hd), "v": v.reshape(T, KV * hv), "att": att,
           "sink_share": share}
    if "ffn" in w:
        f = w["ffn"]
        y = _swiglu(g, f["w_gate"], f["w_up"], f["w_down"], mode)
    else:
        y, out["scores"], out["chosen"] = moe(
            w["moe"], g, cfg, mode,
            bias_in_weight=bool(var.get("bias_in_weight")))
    return {**out, "x": x + y}


def _f32_but_experts(w):
    if "moe" not in w:
        return _f32(w)
    moe_w = w["moe"]
    out = _f32({k: v for k, v in w.items() if k != "moe"})
    out["moe"] = {**_f32({k: v for k, v in moe_w.items() if k != "experts"}),
                  "experts": moe_w["experts"]}
    return out


@partial(jax.jit, static_argnames=("cfg", "i", "mode", "extra", "q_block"))
def _layer_jit(w, x, cfg, i, mode, extra, q_block):
    return layer(_f32_but_experts(w), x, cfg, i, mode, extra, q_block)


@partial(jax.jit, static_argnames=("cfg", "mode"))
def _logits_jit(head, x, cfg, mode):
    x = _rms(x, jnp.ones((cfg.d_model,), jnp.float32), cfg.rms_norm_eps)
    return _mm(x, head.astype(jnp.float32), mode)


def varied(cfg, variant: dict | None):
    """(the config a ``variant`` departs to, what of it is no field)."""
    variant = dict(variant or {})
    extra = tuple(sorted((k, variant.pop(k)) for k in _NOT_FIELDS
                         if k in variant))
    if "layer_moe" in variant:
        variant["layer_moe"] = tuple(variant["layer_moe"])
    return dataclasses.replace(cfg, **variant), extra


def forward(seed: int, cfg, tokens, *, mode: str = "float32",
            variant: dict | None = None, logits_from: int = 0,
            q_block: int = 128, probe: tuple = ()) -> dict:
    """Full forward pass over ``tokens`` [T] (ids over the held slice of the
    vocabulary): ``logits`` [T - logits_from, held rows] of the positions
    from ``logits_from`` on; every layer's keys and values as its attention
    reads them, ``k`` and ``v`` lists of [T, KV_l * width] (so layer i's rows
    hold the work of layers 0 .. i-1); every expert layer's choices
    ``chosen`` {layer: [T, k]}; and of the layers in ``probe``: ``att``
    {layer: [T, H * hv]} the attention's output before Wo, ``sink_share``
    {layer: [T, H]} the sink's share of each softmax's mass, ``scores``
    {layer: [T, E]} the router's s."""
    cfg, extra = varied(cfg, variant)
    key = W.seed_key(seed)
    x = W.embedding(key, cfg).astype(jnp.float32)[jnp.asarray(tokens, jnp.int32)]
    ks, vs, chosen = [], [], {}
    probed = {"att": {}, "sink_share": {}, "scores": {}}
    for i in range(cfg.n_layers):
        out = _layer_jit(W.layer_from_seed(key, cfg, i), x, cfg, i, mode,
                         extra, q_block)
        x = out["x"]
        ks.append(out["k"]), vs.append(out["v"])
        if "chosen" in out:
            chosen[i] = out["chosen"]
        if i in probe:
            for name, kept in probed.items():
                if name in out:
                    kept[i] = out[name]
    logits = _logits_jit(W.head(key, cfg)["kernel"], x[logits_from:], cfg, mode)
    return {"logits": logits, "k": ks, "v": vs, "chosen": chosen, **probed}

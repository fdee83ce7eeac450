"""Plain reference of the DeepSeek-V3 shape as Kanana-2-30B-A3B's
``config.json`` gives it: multi-head latent attention with no query
bottleneck, sigmoid top-k routing with a selection bias (``noaux_tc``, one
group), three-matrix SwiGLU experts, shared experts, leading dense layers.
Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
cache, no batching, no grouped product — every expert is applied to every
token and the unchosen ones are weighed by zero (a dense sum over experts,
``expert_block`` of them at a time so that their float32 copies fit). Weights
come from (seed, layer) alone (``lib/weights_mla_moe.py``).

One expert layer, ``h = rms_norm(x)`` before each half, residual add after:

    q = h.Wq -> [H, nope + rope];  a = h.Wkva -> [r + rope]
    c = rms_norm(a[:r]);  k_rope = rope(a[r:]) (one for all heads)
    [k_nope, v] = c.Wkvb per head
    scores = (q_nope.k_nope + rope(q_rope).k_rope) / sqrt(nope + rope)
    s = sigmoid(h.Wg);  chosen = the k largest of s + b;
    w = s[chosen] / sum(s[chosen]) * routed_scaling_factor
    y = sum_e w_e swiglu_e(h) + swiglu_shared(h)

Departures: (1) rope is the half-split form of ``ops/basic.py``, which is the
published ``rope_interleave: true`` under a fixed permutation of the rope
columns — immaterial with seeded weights; (2) ``b`` is seeded non-zero, so
that choosing by ``s + b`` and weighing by ``s`` are both exercised; (3) an
exact tie of ``s + b`` goes to the lower expert index.

``mode`` puts the reference in the program's place at a lower precision, as
the control of ``correct`` (``reference/dense_gqa.py``: "bfloat16" and "fp8"
round every matmul input, the router's too)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.lib import weights_mla_moe as W
from benchmarks.reference.dense_gqa import _HI, _f32, _mm, _rms_norm, _rope, _round


def route(h, router, cfg, mode: str):
    """h: [T, D] -> (chosen [T, k], combine [T, E]: each token's weight for
    each expert, zero for the unchosen)."""
    s = jax.nn.sigmoid(_mm(h, router["kernel"], mode))
    biased = s + router["bias"]
    chosen = []
    for _ in range(cfg.n_experts_per_tok):   # k rounds of "the largest left"
        e = jnp.argmax(biased, axis=-1)      # first of equals: the lower index
        chosen.append(e)
        biased = biased.at[jnp.arange(h.shape[0]), e].set(-jnp.inf)
    chosen = jnp.stack(chosen, axis=-1)
    picked = jnp.zeros_like(s).at[jnp.arange(h.shape[0])[:, None], chosen].set(1.0)
    w = s * picked
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * cfg.routed_scaling_factor


def _swiglu(h, w_gate, w_up, w_down, mode):
    return _mm(jax.nn.silu(_mm(h, w_gate, mode)) * _mm(h, w_up, mode), w_down, mode)


def experts_sum(h, combine, experts, held, mode: str, expert_block: int):
    """sum over the held experts of combine[:, e] * swiglu_e(h): every held
    expert on every token."""
    lo, hi = held
    n = hi - lo
    blk = expert_block if n % expert_block == 0 else n
    hr = _round(h, mode)

    def block(acc, xs):
        wg, wu, wd, cw = xs                       # [blk, D, F] ..., [blk, T]
        g = jnp.einsum("td,edf->etf", hr, _round(wg.astype(jnp.float32), mode),
                       precision=_HI)
        u = jnp.einsum("td,edf->etf", hr, _round(wu.astype(jnp.float32), mode),
                       precision=_HI)
        hid = _round(jax.nn.silu(g) * u, mode)
        y = jnp.einsum("etf,efd->etd", hid, _round(wd.astype(jnp.float32), mode),
                       precision=_HI)
        return acc + jnp.sum(y * cw[:, :, None], axis=0), None

    def split(w):
        return w.reshape(n // blk, blk, *w.shape[1:])

    cw = combine[:, lo:hi].T.reshape(n // blk, blk, -1)
    out, _ = jax.lax.scan(block, jnp.zeros_like(h),
                          (split(experts["w_gate"]), split(experts["w_up"]),
                           split(experts["w_down"]), cw))
    return out


def moe(w, h, cfg, mode: str, expert_block: int = 16, held=None,
        shared: bool = True):
    """The expert layer on h [T, D] (normed). ``held`` = (lo, hi) gives one
    holder's part: ``w["experts"]`` then holds those experts alone.
    Returns (y, chosen)."""
    chosen, combine = route(h, w["router"], cfg, mode)
    y = experts_sum(h, combine, w["experts"], held or cfg.held, mode,
                    expert_block)
    if shared:
        sh = w["shared"]
        y = y + _swiglu(h, sh["w_gate"]["kernel"], sh["w_up"]["kernel"],
                        sh["w_down"]["kernel"], mode)
    return y, chosen


def layer(w, x, cfg, mode: str, expert_block: int = 16):
    """One decoder layer. x: [B, T, D] float32 -> (x, c [B, T, r], k_rope
    [B, T, rope] after rope, chosen [B * T, k] or None)."""
    B, T, D = x.shape
    H, r, n, v = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.v_head_dim
    h = _rms_norm(x, w["attn_norm"]["scale"])
    q = _mm(h, w["wq"]["kernel"], mode).reshape(B, T, H, cfg.qk_head_dim)
    q_nope, q_rope = q[..., :n], _rope(q[..., n:], cfg.rope_theta)
    a = _mm(h, w["wkv_a"]["kernel"], mode)
    c = _rms_norm(a[..., :r], w["kv_norm"]["scale"])
    k_rope = _rope(a[..., None, r:], cfg.rope_theta)[:, :, 0]
    kv = _mm(c, w["wkv_b"]["kernel"], mode).reshape(B, T, H, n + v)
    k_nope, val = kv[..., :n], kv[..., n:]
    scores = (jnp.einsum("bqhd,bkhd->bhqk", _round(q_nope, mode),
                         _round(k_nope, mode), precision=_HI)
              + jnp.einsum("bqhd,bkd->bhqk", _round(q_rope, mode),
                           _round(k_rope, mode), precision=_HI)
              ) / jnp.sqrt(jnp.float32(cfg.qk_head_dim))
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal[None, None], scores, -1e30), axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", _round(p, mode), _round(val, mode),
                     precision=_HI).reshape(B, T, H * v)
    x = x + _mm(att, w["wo"]["kernel"], mode)
    h = _rms_norm(x, w["ffn_norm"]["scale"])
    if "moe" not in w:
        return x + _swiglu(h, w["w_gate"]["kernel"], w["w_up"]["kernel"],
                           w["w_down"]["kernel"], mode), c, k_rope, None
    y, chosen = moe(w["moe"], h.reshape(B * T, D), cfg, mode, expert_block)
    return x + y.reshape(B, T, D), c, k_rope, chosen


def _f32_but_experts(w):
    """Float32 copies of everything but the routed experts, which
    ``experts_sum`` casts a block at a time."""
    experts = w.get("moe", {}).get("experts")
    out = _f32({k: v for k, v in w.items() if k != "moe"})
    if experts is not None:
        out["moe"] = {**_f32({k: v for k, v in w["moe"].items() if k != "experts"}),
                      "experts": experts}
    return out


@partial(jax.jit, static_argnames=("cfg", "mode"))
def _layer_jit(w, x, cfg, mode):
    return layer(_f32_but_experts(w), x, cfg, mode)


@partial(jax.jit, static_argnames=("cfg", "mode"))
def _logits_jit(head, x, cfg, mode):
    x = _rms_norm(x, jnp.ones((cfg.d_model,), jnp.float32))
    return _mm(x, head.astype(jnp.float32), mode)


def forward(seed: int, cfg, tokens, *, mode: str = "float32",
            zero_col: int | None = None) -> dict:
    """Full forward pass over ``tokens`` [B, T]: logits [B, T, V], every
    layer's cache rows ``rows`` [L, B, T, r + rope] (``[c, k_rope]``: what a
    layer's attention reads, so layer i's rows hold the work of layers
    0 .. i-1), and every expert layer's choices ``chosen``
    [n_moe_layers, B * T, k]."""
    key = W.seed_key(seed)
    x = W.embedding(key, cfg).astype(jnp.float32)[tokens]
    rows, chosen = [], []
    for i in range(cfg.n_layers):
        x, c, k_rope, ch = _layer_jit(W.layer_from_seed(key, cfg, i), x, cfg, mode)
        rows.append(jnp.concatenate([c, k_rope], axis=-1))
        if ch is not None:
            chosen.append(ch)
    return {"logits": _logits_jit(W.head(key, cfg, zero_col), x, cfg, mode),
            "rows": jnp.stack(rows),
            "chosen": jnp.stack(chosen) if chosen else None}

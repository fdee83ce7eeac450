"""Plain reference of a dense decoder with grouped-query attention, rotary
positions (rotate-half), RMSNorm and a SwiGLU feed-forward: Mistral-7B and
Yi-6B as their ``config.json`` describes them. Straightforward ``jax.numpy``
in float32 at ``highest`` matmul precision, no kernels, no cache, no batching
tricks. It makes its weights from the seed (``lib/weights.py``), one layer at
a time, so a model whose float32 copy would not fit beside the program's is
still checked at published widths.

Departure: RMSNorm eps is the program's 1e-6 (ops/basic.py), not the models'
1e-5, so that the comparison is of precision and code paths.

``mode`` puts the reference in the program's place at a lower precision, as
the control of ``correct``: "float32" is the reference; "bfloat16" rounds the
matmul inputs to bf16 (what the program states); "fp8" rounds them to
float8_e4m3 with one scale per tensor — the nearest step below bf16."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.lib import weights as W

EPS = 1e-6
_HI = jax.lax.Precision.HIGHEST


def _round(x, mode: str):
    """Round to ``mode``'s grid; straight-through for the backward pass (a
    cotangent cast to fp8 would underflow to zero)."""
    if mode == "float32":
        return x
    if mode == "bfloat16":
        # not astype there and back: XLA may drop that round trip on the chip
        # (xla_allow_excess_precision), and did: it read 5e-7 where the CPU
        # read 8e-3
        r = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    elif mode == "fp8":
        scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
        r = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    else:
        raise ValueError(f"unknown mode {mode!r}")
    return x + jax.lax.stop_gradient(r - x)


def _mm(a, b, mode: str):
    return jnp.matmul(_round(a, mode), _round(b, mode), precision=_HI)


def _rms_norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * scale


def _rope(x, theta: float):
    """x: [B, T, H, D]; position t rotates (x[..., :D/2], x[..., D/2:])."""
    T, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def _f32(tree):
    return jax.tree.map(lambda w: w.astype(jnp.float32), tree)


def layer(w, x, cfg, mode: str):
    """One decoder layer. x: [B, T, D] float32 -> (x, k, v); k is post-rope."""
    B, T, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = _rms_norm(x, w["attn_norm"]["scale"])
    q = _rope(_mm(h, w["wq"]["kernel"], mode).reshape(B, T, H, hd), cfg.rope_theta)
    k = _rope(_mm(h, w["wk"]["kernel"], mode).reshape(B, T, KV, hd), cfg.rope_theta)
    v = _mm(h, w["wv"]["kernel"], mode).reshape(B, T, KV, hd)
    kr, vr = (jnp.repeat(t, H // KV, axis=2) for t in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", _round(q, mode), _round(kr, mode),
                        precision=_HI) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal[None, None], scores, -1e30), axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", _round(p, mode), _round(vr, mode),
                     precision=_HI).reshape(B, T, H * hd)
    x = x + _mm(att, w["wo"]["kernel"], mode)
    h = _rms_norm(x, w["ffn_norm"]["scale"])
    ff = jax.nn.silu(_mm(h, w["w_gate"]["kernel"], mode)) * _mm(
        h, w["w_up"]["kernel"], mode)
    return x + _mm(ff, w["w_down"]["kernel"], mode), k, v


@partial(jax.jit, static_argnames=("cfg", "mode"))
def _layer_jit(w, x, cfg, mode):
    return layer(_f32(w), x, cfg, mode)


def _layer_from_seed(key, i, x, cfg, mode):
    return _layer_jit(W.layer_weights(W.layer_key(key, i), cfg), x, cfg, mode)


def _embed(key, tokens, cfg):
    return W.embedding(key, cfg).astype(jnp.float32)[tokens]


@partial(jax.jit, static_argnames=("cfg", "mode"))
def _logits_jit(head, x, cfg, mode):
    x = _rms_norm(x, jnp.ones((cfg.d_model,), jnp.float32))
    return _mm(x, head.astype(jnp.float32), mode)


def _logits(key, x, cfg, mode, zero_col):
    return _logits_jit(W.head(key, cfg, zero_col), x, cfg, mode)


def forward(seed: int, cfg, tokens, *, mode: str = "float32",
            zero_col: int | None = None) -> dict:
    """Full forward pass over ``tokens`` [B, T]: logits [B, T, V] and the
    last layer's keys (after rope) and values [B, T, KV, hd]."""
    key = W.seed_key(seed)
    x = _embed(key, tokens, cfg)
    k = v = None
    for i in range(cfg.n_layers):
        x, k, v = _layer_from_seed(key, i, x, cfg, mode)
    return {"logits": _logits(key, x, cfg, mode, zero_col), "k": k, "v": v}


# ------------------------------------------------------------------ training
def _nll(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0].mean()


@partial(jax.jit, static_argnames=("cfg", "mode"))
def _head_loss_vjp(head, x, targets, cfg, mode):
    head = head.astype(jnp.float32)
    scale = jnp.ones((cfg.d_model,), jnp.float32)

    def f(x, head, scale):
        return _nll(_mm(_rms_norm(x, scale), head, mode), targets)

    loss, (gx, ghead, gscale) = jax.value_and_grad(f, argnums=(0, 1, 2))(
        x, head, scale)
    return loss, gx, jnp.sum(ghead * ghead) + jnp.sum(gscale * gscale), gscale


# The gradient vectors that are compared one by one, as submodule -> leaf.
# The last layer's ffn_norm passes back through the head and one feed-forward
# alone. The first layer's attn_norm, wq, wk and wv pass back through every
# layer's attention, and wq (dq), wk (dk) and wv (dv) each through one output
# of the first layer's attention backward.
LAYER_VECTORS = {"ffn_norm": "scale", "attn_norm": "scale", "wq": "kernel",
                 "wk": "kernel", "wv": "kernel"}


def picked_vectors(n_layers: int) -> dict:
    """name -> (layer, submodule) of each gradient vector compared, besides
    ``final_norm``."""
    return {"last_ffn_norm": (n_layers - 1, "ffn_norm"),
            **{f"first_{sub}": (0, sub) for sub in ("attn_norm", "wq", "wk", "wv")}}


@partial(jax.jit, static_argnames=("cfg", "mode"))
def _layer_vjp(w, x, gx, cfg, mode):
    w = _f32(w)
    _, vjp = jax.vjp(lambda w, x: layer(w, x, cfg, mode)[0], w, x)
    gw, gx = vjp(gx)
    sq = sum(jnp.sum(g * g) for g in jax.tree.leaves(gw))
    return gx, sq, {sub: gw[sub][leaf] for sub, leaf in LAYER_VECTORS.items()}


@partial(jax.jit, static_argnames=("cfg",))
def _embed_grad_sq(tokens, gx, cfg):
    g = jnp.zeros((cfg.vocab_size, cfg.d_model), jnp.float32).at[tokens].add(gx)
    return jnp.sum(g * g)


def loss_and_grads(seed: int, cfg, tokens, *, mode: str = "float32") -> dict:
    """Next-token loss of ``tokens`` [B, T+1], the norm of its gradient over
    every parameter, and under ``vectors`` the gradients of the final norm's
    scale and of ``picked_vectors`` — backward one layer at a time,
    recomputing each layer's forward from its saved input."""
    key = W.seed_key(seed)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    xs = [_embed(key, inputs, cfg)]
    for i in range(cfg.n_layers):
        xs.append(_layer_from_seed(key, i, xs[-1], cfg, mode)[0])
    loss, gx, sq, g_final = _head_loss_vjp(W.head(key, cfg, None), xs[-1], targets,
                                            cfg, mode)
    vectors = {"final_norm": g_final}
    picked = picked_vectors(cfg.n_layers)
    for i in reversed(range(cfg.n_layers)):
        gx, layer_sq, gw = _layer_vjp(
            W.layer_weights(W.layer_key(key, i), cfg), xs[i], gx, cfg, mode)
        sq = sq + layer_sq
        vectors.update({name: gw[sub] for name, (layer_i, sub) in picked.items()
                        if layer_i == i})
    sq = sq + _embed_grad_sq(inputs, gx, cfg)
    return {"loss": loss, "grad_norm": jnp.sqrt(sq), "vectors": vectors}

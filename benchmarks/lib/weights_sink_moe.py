"""The benchmark's own seeded weights in the layout ``models/sink_moe.py``
takes, made as ``lib/weights_cohere2_moe.py`` makes that family's (a copy of
its scheme: README_sink_moe.md): each piece (a layer outside its routed
experts, one of its expert stacks, the embedding, the head) is one jitted
program of its own with a key of its own, called by the program's
``params_fn`` and by the plain reference alike, so the reference makes layer i
again from (seed, i) alone, never reads what the program holds, and gets the
same bits. A piece's key does not depend on which pieces the configuration
has: a control that gives full layers a sink, or routes layer 0, draws the
same ``wq`` as the model it departs from.

Every routed expert has a key of its own (``fold_in(k, expert id)``) and the
held rows of the embedding and columns of the head are drawn under their
holder's slice, so a holder of experts ``[lo, hi)`` and rows ``[lo, hi)``
makes its share without drawing the 256 experts (12.9 GB a layer) or the
152,576 rows: the shares of one seed are slices of one model all the same.

**The sinks** are drawn ``SINK_MEAN(window) + 0.5 . normal``, ``SINK_MEAN =
log(window) - 0.5``: under these weights a window layer's scores spread about
0.85 around 0, so a full window's mass is about ``window . e^0.36`` and the
sink's share of it about 0.3 — at 0 the sink is untested, near 1 the window
layers say nothing (the measured share: PERF.md section 6, this PR's entry).
**``e_bias``** is drawn at 0.05: the eight largest of 256 sigmoid scores lie
between about 0.93 and 0.99, a hundredth or less apart, so the bias decides
among the experts whose scores are high and the score still decides which
those are, and weighing by ``s`` alone differs from weighing by ``s +
e_bias`` by a few per cent of a weight.

The embedding is drawn at unit scale, as ``lib/weights_mla_moe.py``'s (the
head is a matrix of its own: no row scores its own token)."""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.lib.weights import _dense, layer_key, seed_key  # noqa: F401

SINK_STD, BIAS_STD = 0.5, 0.05


def sink_mean(cfg) -> float:
    return math.log(cfg.sliding_window) - 0.5


@partial(jax.jit, static_argnames=("cfg", "i"))
def layer_fixed(key, cfg, i: int) -> dict:
    """Layer ``i`` outside its routed experts: the norms, the four attention
    matrices at its kind's KV heads, its sink where the kind has one, and
    the dense SwiGLU or the router with its bias."""
    dtype = jnp.dtype(cfg.dtype)
    d, H, hd, hv = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.v_head_dim
    window = cfg.is_window(i)
    KV = cfg.kv_heads(window)
    k = jax.random.split(key, 10)
    out = {
        "attn_norm": {"scale": jnp.ones((d,), dtype)},
        "wq": _dense(k[0], d, H * hd, dtype),
        "wk": _dense(k[1], d, KV * hd, dtype),
        "wv": _dense(k[2], d, KV * hv, dtype),
        "wo": _dense(k[3], H * hv, d, dtype),
        "ffn_norm": {"scale": jnp.ones((d,), dtype)},
    }
    if cfg.has_sink(window):
        out["sink"] = sink_mean(cfg) + SINK_STD * jax.random.normal(
            k[4], (H,), jnp.float32)
    if cfg.is_moe(i):
        out["router"] = {
            "kernel": _dense(k[5], d, cfg.n_experts, dtype)["kernel"],
            "bias": BIAS_STD * jax.random.normal(k[6], (cfg.n_experts,))}
    else:
        out["ffn"] = {"w_gate": _dense(k[7], d, cfg.d_ff, dtype)["kernel"],
                      "w_up": _dense(k[8], d, cfg.d_ff, dtype)["kernel"],
                      "w_down": _dense(k[9], cfg.d_ff, d, dtype)["kernel"]}
    return out


@partial(jax.jit, static_argnames=("cfg", "which"))
def expert_stack(key, cfg, which: int):
    """One of the three matrices (0 gate, 1 up, 2 down) of the held experts,
    [held, d_in, d_out]: expert e's from ``fold_in(key of the matrix, e)``."""
    d_in, d_out = ((cfg.d_model, cfg.d_expert) if which < 2
                   else (cfg.d_expert, cfg.d_model))
    lo, hi = cfg.held
    k = jax.random.fold_in(key, 100 + which)
    return jax.lax.map(
        lambda e: _dense(jax.random.fold_in(k, e), d_in, d_out,
                         jnp.dtype(cfg.dtype))["kernel"],
        jnp.arange(lo, hi))


def layer_from_seed(key, cfg, i: int) -> dict:
    k = layer_key(key, i)
    layer = dict(layer_fixed(k, cfg, i))
    if cfg.is_moe(i):
        layer["moe"] = {"router": layer.pop("router"),
                        "experts": {"w_gate": expert_stack(k, cfg, 0),
                                    "w_up": expert_stack(k, cfg, 1),
                                    "w_down": expert_stack(k, cfg, 2)}}
    return layer


def _slice_key(key, which: int, cfg):
    lo = cfg.vocab_held[0] if cfg.vocab_held else 0
    return jax.random.fold_in(jax.random.fold_in(key, which), lo)


@partial(jax.jit, static_argnames=("cfg",))
def embedding(key, cfg):
    """The held rows of the embedding."""
    return jax.random.normal(_slice_key(key, 0, cfg), (
        cfg.vocab_size, cfg.d_model)).astype(jnp.dtype(cfg.dtype))


@partial(jax.jit, static_argnames=("cfg",))
def head(key, cfg):
    """The held columns of the head, a matrix of its own [D, held rows],
    scaled as the published width's (``d_model`` into 152,576 or whatever
    the slice is cut from would differ by holder: the slice's own)."""
    return _dense(_slice_key(key, 1, cfg), cfg.d_model, cfg.vocab_size,
                  jnp.dtype(cfg.dtype))


def make_params(key, cfg) -> dict:
    params = {"tok": {"embedding": embedding(key, cfg)}}
    for i in range(cfg.n_layers):
        params[f"layers_{i}"] = layer_from_seed(key, cfg, i)
    params["norm"] = {"scale": jnp.ones((cfg.d_model,), jnp.dtype(cfg.dtype))}
    params["head"] = head(key, cfg)
    return params

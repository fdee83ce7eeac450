"""The benchmark's own seeded weights in the layout ``models/eva.py`` takes,
made as ``lib/weights.py`` makes the dense ones: each piece (a layer, the
embedding, the head) is one jitted program of its own with a key of its own,
called by the program's ``params_fn`` and by the plain reference alike, so the
reference makes layer i again from (seed, i) alone, never reads what the
program holds, and gets the same bits.

What differs from ``lib/weights.py``: the norms' gains are drawn (0.1 x
normal) and not left at their initial zero, so that the unit offset ``1 + g``
is exercised; ``phi`` and ``mu`` are drawn as the model initialises them, a
normal clamped at two deviations times ``head_dim ** -0.5``; the head has
``n_pred_heads x vocab_size`` columns, head-major, with the eos id's column of
head 0 zeroed (its logit is then exactly 0 and, of 320 random logits, never
the largest: random weights decide no request's length); the embedding is at
unit scale (the head is untied and reads the norm's output), as
``lib/weights_mla_moe.py``'s."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.lib.weights import _dense, layer_key, seed_key  # noqa: F401


@partial(jax.jit, static_argnames=("cfg",))
def layer_weights(key, cfg) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    d, H, hd, ff = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff
    k = jax.random.split(key, 11)

    def gain(key):
        return (0.1 * jax.random.normal(key, (d,))).astype(dtype)

    def per_head(key):
        return (jax.random.truncated_normal(key, -2.0, 2.0, (H, hd))
                * hd ** -0.5).astype(dtype)

    return {
        "attn_norm": {"scale": gain(k[0])},
        "wq": _dense(k[1], d, H * hd, dtype),
        "wk": _dense(k[2], d, H * hd, dtype),
        "wv": _dense(k[3], d, H * hd, dtype),
        "wo": _dense(k[4], H * hd, d, dtype),
        "phi": per_head(k[5]), "mu": per_head(k[6]),
        "ffn_norm": {"scale": gain(k[7])},
        "w_gate": _dense(k[8], d, ff, dtype),
        "w_up": _dense(k[9], d, ff, dtype),
        "w_down": _dense(k[10], ff, d, dtype),
    }


def layer_from_seed(key, cfg, i: int) -> dict:
    return layer_weights(layer_key(key, i), cfg)


@partial(jax.jit, static_argnames=("cfg",))
def embedding(key, cfg):
    return jax.random.normal(jax.random.fold_in(key, 0),
                             (cfg.vocab_size, cfg.d_model)
                             ).astype(jnp.dtype(cfg.dtype))


@partial(jax.jit, static_argnames=("cfg", "zero_col"))
def head(key, cfg, zero_col: int | None):
    """The final norm's gain and the untied head, [d_model, n_pred_heads x
    vocab_size] head-major; ``zero_col``: the eos id, zeroed in head 0."""
    dtype = jnp.dtype(cfg.dtype)
    w = _dense(jax.random.fold_in(key, 1), cfg.d_model,
               cfg.n_pred_heads * cfg.vocab_size, dtype)["kernel"]
    if zero_col is not None:
        w = w.at[:, zero_col].set(0)
    g = 0.1 * jax.random.normal(jax.random.fold_in(key, 2), (cfg.d_model,))
    return {"norm": {"scale": g.astype(dtype)}, "lm_head": {"kernel": w}}


def make_params(key, cfg, zero_col: int | None = None) -> dict:
    params = {"tok": {"embedding": embedding(key, cfg)}}
    for i in range(cfg.n_layers):
        params[f"layers_{i}"] = layer_from_seed(key, cfg, i)
    return {**params, **head(key, cfg, zero_col)}

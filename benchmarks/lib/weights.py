"""The benchmark's own seeded weights, in the layout ``models/llama.py``
takes. One jitted call makes the whole tree on the device in the type it is
served in; every layer has a key of its own, so the plain reference makes
layer i again from (seed, i) alone and never reads what the program holds.

Each piece (a layer, the embedding, the head) is one jitted program of its
own, called by the program's ``params_fn`` and by the reference alike: the
same executable gives the same bits, which inlining into two different jitted
callers does not (XLA may fuse the scaling differently by an ulp)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any whole number, also above 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _dense(key, d_in: int, d_out: int, dtype):
    scale = (2.0 / (d_in + d_out)) ** 0.5
    return {"kernel": (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)}


@partial(jax.jit, static_argnames=("cfg",))
def layer_weights(key, cfg) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    d, hd, ff = cfg.d_model, cfg.head_dim, cfg.d_ff
    k = jax.random.split(key, 7)
    return {
        "attn_norm": {"scale": jnp.ones((d,), dtype)},
        "wq": _dense(k[0], d, cfg.n_heads * hd, dtype),
        "wk": _dense(k[1], d, cfg.n_kv_heads * hd, dtype),
        "wv": _dense(k[2], d, cfg.n_kv_heads * hd, dtype),
        "wo": _dense(k[3], cfg.n_heads * hd, d, dtype),
        "ffn_norm": {"scale": jnp.ones((d,), dtype)},
        "w_gate": _dense(k[4], d, ff, dtype),
        "w_up": _dense(k[5], d, ff, dtype),
        "w_down": _dense(k[6], ff, d, dtype),
    }


def layer_key(key, i: int):
    return jax.random.fold_in(key, 16 + i)


@partial(jax.jit, static_argnames=("cfg",))
def embedding(key, cfg):
    dtype = jnp.dtype(cfg.dtype)
    return (jax.random.normal(jax.random.fold_in(key, 0),
                              (cfg.vocab_size, cfg.d_model)) * 0.02).astype(dtype)


@partial(jax.jit, static_argnames=("cfg", "zero_col"))
def head(key, cfg, zero_col: int | None):
    w = _dense(jax.random.fold_in(key, 1), cfg.d_model, cfg.vocab_size,
               jnp.dtype(cfg.dtype))["kernel"]
    if zero_col is not None:
        # the eos id's logit is then exactly 0 and, of 32768 random logits,
        # never the largest: random weights decide no request's length
        w = w.at[:, zero_col].set(0)
    return w


def make_params(key, cfg, zero_col: int | None = None) -> dict:
    params = {"tok": {"embedding": embedding(key, cfg)}}
    for i in range(cfg.n_layers):
        params[f"layers_{i}"] = layer_weights(layer_key(key, i), cfg)
    params["norm"] = {"scale": jnp.ones((cfg.d_model,), jnp.dtype(cfg.dtype))}
    params["lm_head"] = {"kernel": head(key, cfg, zero_col)}
    return params

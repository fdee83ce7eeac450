"""The benchmark's own seeded weights in the layout ``models/mla_moe.py``
takes, made as ``lib/weights.py`` makes the dense ones: each piece (a layer,
the embedding, the head) is one jitted program of its own with a key of its
own, called by the program's ``params_fn`` and by the plain reference alike,
so the reference makes layer i again from (seed, i) alone, never reads what
the program holds, and gets the same bits.

``e_score_correction_bias`` is drawn non-zero (std 0.1 beside sigmoid scores
in (0, 1)), so that choosing by ``s + b`` and weighing by ``s`` differ.

The embedding is drawn at unit scale, not ``llama_init``'s 0.02: every
sublayer's output has unit scale under these weights, so a token whose
embedding is fifty times smaller is a rounding error of its own residual
after the first add. Measured on the chip (PR 27, PERF.md section 6): the
share of (position, expert layer) pairs whose top-6 differs between the
float32 reference and its bf16-rounded self fell from 0.19 to 0.07, and the
last layer's rows agree to 0.19 where they agreed to 0.29. It did NOT spread
the routing: 61 of 128 experts touched a step a layer before, 63 after —
under seeded weights attention over a long random context adds nearly the
same vector to every position, and no trained bias evens the load."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.lib.weights import _dense, layer_key, seed_key  # noqa: F401


def _stack(key, n: int, d_in: int, d_out: int, dtype):
    scale = (2.0 / (d_in + d_out)) ** 0.5
    return (jax.random.normal(key, (n, d_in, d_out)) * scale).astype(dtype)


@partial(jax.jit, static_argnames=("cfg", "moe"))
def layer_weights(key, cfg, moe: bool) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    k = jax.random.split(key, 12)
    out = {
        "attn_norm": {"scale": jnp.ones((d,), dtype)},
        "wq": _dense(k[0], d, H * cfg.qk_head_dim, dtype),
        "wkv_a": _dense(k[1], d, cfg.latent_width, dtype),
        "kv_norm": {"scale": jnp.ones((r,), dtype)},
        "wkv_b": _dense(k[2], r, H * (cfg.qk_nope_head_dim + cfg.v_head_dim),
                        dtype),
        "wo": _dense(k[3], H * cfg.v_head_dim, d, dtype),
        "ffn_norm": {"scale": jnp.ones((d,), dtype)},
    }
    if not moe:
        ff = cfg.d_ff
        return {**out, "w_gate": _dense(k[4], d, ff, dtype),
                "w_up": _dense(k[5], d, ff, dtype),
                "w_down": _dense(k[6], ff, d, dtype)}
    lo, hi = cfg.held
    E, F, Fs = cfg.n_experts, cfg.d_expert, cfg.n_shared_experts * cfg.d_expert
    out["moe"] = {
        "router": {"kernel": _dense(k[4], d, E, dtype)["kernel"],
                   "bias": 0.1 * jax.random.normal(k[5], (E,))},
        "experts": {"w_gate": _stack(k[6], E, d, F, dtype)[lo:hi],
                    "w_up": _stack(k[7], E, d, F, dtype)[lo:hi],
                    "w_down": _stack(k[8], E, F, d, dtype)[lo:hi]},
        "shared": {"w_gate": _dense(k[9], d, Fs, dtype),
                   "w_up": _dense(k[10], d, Fs, dtype),
                   "w_down": _dense(k[11], Fs, d, dtype)},
    }
    return out


def layer_from_seed(key, cfg, i: int) -> dict:
    return layer_weights(layer_key(key, i), cfg, cfg.is_moe_layer(i))


@partial(jax.jit, static_argnames=("cfg",))
def embedding(key, cfg):
    return jax.random.normal(jax.random.fold_in(key, 0),
                             (cfg.vocab_size, cfg.d_model)
                             ).astype(jnp.dtype(cfg.dtype))


@partial(jax.jit, static_argnames=("cfg", "zero_col"))
def head(key, cfg, zero_col: int | None):
    w = _dense(jax.random.fold_in(key, 1), cfg.d_model, cfg.vocab_size,
               jnp.dtype(cfg.dtype))["kernel"]
    if zero_col is not None:
        w = w.at[:, zero_col].set(0)  # the eos id's logit: exactly 0, never best
    return w


def make_params(key, cfg, zero_col: int | None = None) -> dict:
    params = {"tok": {"embedding": embedding(key, cfg)}}
    for i in range(cfg.n_layers):
        params[f"layers_{i}"] = layer_from_seed(key, cfg, i)
    params["norm"] = {"scale": jnp.ones((cfg.d_model,), jnp.dtype(cfg.dtype))}
    params["lm_head"] = {"kernel": head(key, cfg, zero_col)}
    return params

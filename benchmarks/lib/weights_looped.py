"""The benchmark's own seeded weights in the layout ``models/looped.py`` takes,
made as ``lib/weights.py`` makes the dense family's (a copy of its scheme:
README_looped.md): each piece (a layer, the embedding, the head, the closing
norm with the gate) is one jitted program of its own with a key of its own,
called by the program's ``params_fn`` and by the plain reference alike, so the
reference makes layer i again from (seed, i) alone, never reads what the
program holds, and gets the same bits.

A looped model under random weights has two ways of saying nothing, and the
draws are set against both (the measured numbers: PERF.md section 6, PR 57):

* **The passes must not converge.** If ``h_4`` came out as ``h_3``, three
  passes would pass for four. Every norm's scale is drawn a lane (``1 + 0.1 .
  normal``: a scale of ones would make four norms one), and the norms that
  close a branch (``norm2``, ``norm4``) at ``BRANCH`` times that: a pass adds
  ``2 . n_layers`` branches of that size to a state of size 1, so at 0.1 and
  48 layers about half of ``h_{u+1}`` is what the pass added and half what it
  was handed — the states differ by the order of their own size, and a
  rounding error is not amplified without bound on its way through 192 layer
  applications.
* **The gate must decide nothing at the published threshold and something
  below it.** ``w_g`` is drawn so that the gate's logit is about a unit
  normal (``h`` has lanes of size 1): ``lam`` has a median of 0.5, stays
  within ``sigmoid(+-6)`` and never reaches 1.0 in float32 — at threshold 1
  the fourth pass is chosen for every token, at 0.5 (the control) each of the
  four for some.

The embedding is drawn at unit scale (the head is a matrix of its own: no row
scores its own token); the eos id's column of the head is zeroed, so its logit
is exactly 0 and, of 49,152 random logits, never the largest."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.lib.weights import _dense, layer_key, seed_key  # noqa: F401

BRANCH, SCALE_STD = 0.1, 0.1


def _scale(key, d: int, around: float, dtype):
    return {"scale": (around * (1.0 + SCALE_STD * jax.random.normal(key, (d,)))
                      ).astype(dtype)}


@partial(jax.jit, static_argnames=("cfg",))
def layer_weights(key, cfg) -> dict:
    """One layer: q, k and v drawn as three matrices and laid side by side
    (``wqkv``), gate and up likewise (``w_gate_up``)."""
    dtype = jnp.dtype(cfg.dtype)
    d, hd, ff = cfg.d_model, cfg.head_dim, cfg.d_ff
    nq, nkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    k = jax.random.split(key, 11)

    def side_by_side(*parts):
        return {"kernel": jnp.concatenate([p["kernel"] for p in parts], axis=1)}

    return {
        "norm1": _scale(k[0], d, 1.0, dtype),
        "norm2": _scale(k[1], d, BRANCH, dtype),
        "norm3": _scale(k[2], d, 1.0, dtype),
        "norm4": _scale(k[3], d, BRANCH, dtype),
        "wqkv": side_by_side(_dense(k[4], d, nq, dtype), _dense(k[5], d, nkv, dtype),
                             _dense(k[6], d, nkv, dtype)),
        "wo": _dense(k[7], nq, d, dtype),
        "w_gate_up": side_by_side(_dense(k[8], d, ff, dtype),
                                  _dense(k[9], d, ff, dtype)),
        "w_down": _dense(k[10], ff, d, dtype),
    }


def layer_from_seed(key, cfg, i: int) -> dict:
    return layer_weights(layer_key(key, i), cfg)


@partial(jax.jit, static_argnames=("cfg",))
def embedding(key, cfg):
    return jax.random.normal(jax.random.fold_in(key, 0), (
        cfg.vocab_size, cfg.d_model)).astype(jnp.dtype(cfg.dtype))


@partial(jax.jit, static_argnames=("cfg", "zero_col"))
def head(key, cfg, zero_col: int | None = None):
    w = _dense(jax.random.fold_in(key, 1), cfg.d_model, cfg.vocab_size,
               jnp.dtype(cfg.dtype))["kernel"]
    return {"kernel": w if zero_col is None else w.at[:, zero_col].set(0)}


@partial(jax.jit, static_argnames=("cfg",))
def close(key, cfg) -> dict:
    """What closes a pass: the model's one final norm, and the exit gate
    (float32: a row of ``d_model`` and a bias)."""
    k = jax.random.split(jax.random.fold_in(key, 2), 2)
    d = cfg.d_model
    return {"norm": _scale(k[0], d, 1.0, jnp.dtype(cfg.dtype)),
            "gate": {"kernel": jax.random.normal(k[1], (d,)) * d ** -0.5,
                     "bias": jnp.zeros((), jnp.float32)}}


def make_params(key, cfg, zero_col: int | None = None) -> dict:
    params = {"tok": {"embedding": embedding(key, cfg)}}
    for i in range(cfg.n_layers):
        params[f"layers_{i}"] = layer_from_seed(key, cfg, i)
    return {**params, **close(key, cfg), "head": head(key, cfg, zero_col)}

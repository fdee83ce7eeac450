"""The benchmark's own seeded weights in the layout ``models/cohere2_moe.py``
takes, made as ``lib/weights.py`` makes the dense ones: each piece (a layer's
attention and shared part, one of its expert stacks, the embedding) is one
jitted program of its own with a key of its own, called by the program's
``params_fn`` and by the plain reference alike, so the reference makes layer
i again from (seed, i) alone, never reads what the program holds, and gets
the same bits.

Every routed expert has a key of its own (``fold_in(k, expert id)``) and every
row of the embedding is drawn under its holder's slice, so a holder of
experts ``[lo, hi)`` and rows ``[lo, hi)`` makes its share without drawing the
128 experts (13 GB a layer) or the 262,144 rows: the shares of one seed are
slices of one model all the same.

The embedding, which is the head too (``tie_word_embeddings``), is drawn at
``llama_init``'s 0.02 and NOT at the unit scale of ``lib/weights_mla_moe.py``:
under a tied head a unit-scale row scores its own token ``sqrt(d_model)`` = 64
logit spreads above the rest, every request then decodes its last prompt
token for ever, and a step sees as many distinct tokens as the traffic has
distinct prompts (measured on the chip, PR 31: with 8 prompts cycled the 48
slots touched 46 % of the held experts a step where 256 prompts touched
93 %). At 0.02 the logits spread 1.3, the greedy sequence wanders, and 48
slots are 48 different tokens whatever the list. ``LayerNorm`` rescales the
row before every use, so its scale is no rounding matter here."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.lib.weights import _dense, layer_key, seed_key  # noqa: F401


@partial(jax.jit, static_argnames=("cfg",))
def layer_fixed(key, cfg) -> dict:
    """A layer outside its routed experts: the norm, the four attention
    matrices, the router, the shared experts as one SwiGLU of their summed
    width (columns ``[j * F, (j + 1) * F)`` are shared expert j)."""
    dtype = jnp.dtype(cfg.dtype)
    d, hd = cfg.d_model, cfg.head_dim
    Fs = cfg.n_shared_experts * cfg.d_expert
    k = jax.random.split(key, 8)
    return {
        "norm": {"scale": jnp.ones((d,), dtype)},
        "wq": _dense(k[0], d, cfg.n_heads * hd, dtype),
        "wk": _dense(k[1], d, cfg.n_kv_heads * hd, dtype),
        "wv": _dense(k[2], d, cfg.n_kv_heads * hd, dtype),
        "wo": _dense(k[3], cfg.n_heads * hd, d, dtype),
        "router": {"kernel": _dense(k[4], d, cfg.n_experts, dtype)["kernel"]},
        "shared": {"w_gate": _dense(k[5], d, Fs, dtype),
                   "w_up": _dense(k[6], d, Fs, dtype),
                   "w_down": _dense(k[7], Fs, d, dtype)},
    }


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
    fixed = dict(layer_fixed(k, cfg))
    return {**{n: fixed[n] for n in ("norm", "wq", "wk", "wv", "wo")},
            "moe": {"router": fixed["router"], "shared": fixed["shared"],
                    "experts": {"w_gate": expert_stack(k, cfg, 0),
                                "w_up": expert_stack(k, cfg, 1),
                                "w_down": expert_stack(k, cfg, 2)}}}


@partial(jax.jit, static_argnames=("cfg",))
def embedding(key, cfg):
    """The held rows of the embedding, which is the head too."""
    lo = cfg.vocab_held[0] if cfg.vocab_held else 0
    return (0.02 * jax.random.normal(
        jax.random.fold_in(jax.random.fold_in(key, 0), lo),
        (cfg.vocab_size, cfg.d_model))).astype(jnp.dtype(cfg.dtype))


def make_params(key, cfg) -> dict:
    params = {"tok": {"embedding": embedding(key, cfg)}}
    for i in range(cfg.n_layers):
        params[f"layers_{i}"] = layer_from_seed(key, cfg, i)
    params["norm"] = {"scale": jnp.ones((cfg.d_model,), jnp.dtype(cfg.dtype))}
    return params

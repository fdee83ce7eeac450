"""The benchmark's own seeded weights in the layout ``models/sparse_moe.py``
takes, made as ``lib/weights_cohere2_moe.py`` makes that family's: each piece
(a layer outside its routed experts, one of its expert stacks, the embedding,
the head) is one jitted program of its own with a key of its own, called by
the program's ``params_fn`` and by the plain reference alike, so the reference
makes layer i again from (seed, i) alone, never reads what the program holds,
and gets the same bits.

Every routed expert has a key of its own (``fold_in(k, expert id)``) and the
rows of embedding and head are drawn under their holder's slice, so a holder
of experts ``[lo, hi)`` and rows ``[lo, hi)`` makes its share without drawing
the 128 experts or the 151,936 rows.

The head is untied and reads RMSNorm's output, so the embedding's scale is no
rounding matter: unit scale, as ``lib/weights_mla_moe.py``'s."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.lib.weights import _dense, layer_key, seed_key  # noqa: F401
from benchmarks.lib.weights_cohere2_moe import expert_stack


@partial(jax.jit, static_argnames=("cfg",))
def layer_fixed(key, cfg) -> dict:
    """A layer outside its routed experts: the two norms, the four attention
    matrices with the per-head q and k gains, the indexer, the router."""
    dtype = jnp.dtype(cfg.dtype)
    d, hd = cfg.d_model, cfg.head_dim
    J, dk = cfg.indexer_heads, cfg.indexer_head_dim
    k = jax.random.split(key, 8)

    def one(n):
        return {"scale": jnp.ones((n,), dtype)}

    return {
        "attn_norm": one(d), "ffn_norm": one(d),
        "wq": _dense(k[0], d, cfg.n_heads * hd, dtype), "q_norm": one(hd),
        "wk": _dense(k[1], d, cfg.n_kv_heads * hd, dtype), "k_norm": one(hd),
        "wv": _dense(k[2], d, cfg.n_kv_heads * hd, dtype),
        "wo": _dense(k[3], cfg.n_heads * hd, d, dtype),
        "indexer": {"wq": _dense(k[4], d, J * dk, dtype),
                    "wk": _dense(k[5], d, dk, dtype), "k_norm": one(dk),
                    "w": _dense(k[6], d, J, dtype)},
        "router": {"kernel": _dense(k[7], d, cfg.n_experts, dtype)["kernel"]},
    }


def layer_from_seed(key, cfg, i: int) -> dict:
    k = layer_key(key, i)
    fixed = dict(layer_fixed(k, cfg))
    router = fixed.pop("router")
    return {**fixed,
            "moe": {"router": router,
                    "experts": {"w_gate": expert_stack(k, cfg, 0),
                                "w_up": expert_stack(k, cfg, 1),
                                "w_down": expert_stack(k, cfg, 2)}}}


def _slice_key(key, cfg, what: int):
    lo = cfg.vocab_held[0] if cfg.vocab_held else 0
    return jax.random.fold_in(jax.random.fold_in(key, what), lo)


@partial(jax.jit, static_argnames=("cfg",))
def embedding(key, cfg):
    """The held rows of the embedding."""
    return jax.random.normal(_slice_key(key, cfg, 0),
                             (cfg.vocab_size, cfg.d_model)
                             ).astype(jnp.dtype(cfg.dtype))


@partial(jax.jit, static_argnames=("cfg",))
def head(key, cfg):
    """The held columns of the untied head, [d_model, held rows]."""
    return _dense(_slice_key(key, cfg, 1), cfg.d_model, cfg.vocab_size,
                  jnp.dtype(cfg.dtype))


def make_params(key, cfg) -> dict:
    params = {"tok": {"embedding": embedding(key, cfg)}}
    for i in range(cfg.n_layers):
        params[f"layers_{i}"] = layer_from_seed(key, cfg, i)
    params["norm"] = {"scale": jnp.ones((cfg.d_model,), jnp.dtype(cfg.dtype))}
    params["lm_head"] = head(key, cfg)
    return params

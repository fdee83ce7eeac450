"""The benchmark's own seeded weights in the layout ``models/ssm_moe.py``
takes, made as ``lib/weights_sparse_moe.py`` makes that family's: each piece
(a block outside its routed experts, one of its expert stacks, the embedding,
the head) is one jitted program of its own with a key of its own, called by
the program's ``params_fn`` and by the plain reference alike, so the reference
makes block i again from (seed, i) alone, never reads what the program holds,
and gets the same bits.

Every routed expert has a key of its own (``fold_in(k, expert id)``: the
stacks are ``lib/weights_cohere2_moe.py``'s up and down, this family has no
gate) and the rows of embedding and head are drawn under their holder's
slice, so a holder of experts ``[lo, hi)`` and rows ``[lo, hi)`` makes its
share without drawing the 128 experts or the 131,072 rows.

What a trained model has and a normal draw has not: ``A_log``, ``dt_bias``
and ``D`` are drawn in the ranges the family's own initialisation and its
trained checkpoints keep (``A`` in [1, 16], ``dt = softplus(dt_bias)`` log-
uniform in [0.001, 0.1] = ``time_step_min .. time_step_max``, ``D`` = 1), so
that a step's decay ``exp(dt . A)`` lies in [0.2, 0.999]: neither 0 (a state
that forgets everything reads no recurrence) nor 1. The conv taps are
normal / sqrt(K) with a zero bias; ``e_score_correction_bias`` is drawn
non-zero (std 0.1), so that choosing by ``s + b`` and weighing by ``s`` are
both exercised, as ``lib/weights_mla_moe.py``'s.

The head is untied and reads RMSNorm's output: unit-scale embedding, as
``lib/weights_mla_moe.py``'s."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.lib.weights import _dense, layer_key, seed_key  # noqa: F401
from benchmarks.lib.weights_cohere2_moe import expert_stack
from benchmarks.lib.weights_sparse_moe import embedding, head  # noqa: F401
from ray_tpu.models.ssm_moe import ATTENTION, MAMBA


@partial(jax.jit, static_argnames=("cfg", "kind"))
def block_fixed(key, cfg, kind: str) -> dict:
    """A block outside its routed experts, by its pattern character."""
    dtype = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    k = jax.random.split(key, 8)
    out: dict = {"norm": {"scale": jnp.ones((d,), dtype)}}
    if kind == MAMBA:
        Hm, C, K = cfg.mamba_heads, cfg.conv_width, cfg.conv_kernel
        dt = jnp.exp(jax.random.uniform(k[3], (Hm,), jnp.float32,
                                        jnp.log(1e-3), jnp.log(1e-1)))
        out |= {
            "in_proj": _dense(k[0], d, cfg.d_inner + C + Hm, dtype),
            "conv": {"kernel": (jax.random.normal(k[1], (K, C)) * K ** -0.5
                                ).astype(dtype),
                     "bias": jnp.zeros((C,), dtype)},
            "A_log": jnp.log(jax.random.uniform(k[2], (Hm,), jnp.float32,
                                                1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
            "D": jnp.ones((Hm,), jnp.float32),
            "gate_norm": {"scale": jnp.ones((cfg.d_inner,), dtype)},
            "out_proj": _dense(k[4], cfg.d_inner, d, dtype),
        }
    elif kind == ATTENTION:
        H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        out |= {"wq": _dense(k[0], d, H * hd, dtype),
                "wk": _dense(k[1], d, KV * hd, dtype),
                "wv": _dense(k[2], d, KV * hd, dtype),
                "wo": _dense(k[3], H * hd, d, dtype)}
    else:
        Fs = cfg.n_shared_experts * cfg.d_shared
        out["moe"] = {
            "router": {"kernel": _dense(k[0], d, cfg.n_experts, dtype)["kernel"],
                       "bias": 0.1 * jax.random.normal(k[1], (cfg.n_experts,))},
            "shared": {"w_up": _dense(k[2], d, Fs, dtype),
                       "w_down": _dense(k[3], Fs, d, dtype)}}
    return out


def layer_from_seed(key, cfg, i: int) -> dict:
    k, kind = layer_key(key, i), cfg.pattern[i]
    out = dict(block_fixed(k, cfg, kind))
    if "moe" in out:
        out["moe"] = {**out["moe"],
                      "experts": {"w_up": expert_stack(k, cfg, 1),
                                  "w_down": expert_stack(k, cfg, 2)}}
    return out


def make_params(key, cfg) -> dict:
    params = {"tok": {"embedding": embedding(key, cfg)}}
    for i in range(cfg.n_layers):
        params[f"layers_{i}"] = layer_from_seed(key, cfg, i)
    params["norm"] = {"scale": jnp.ones((cfg.d_model,), jnp.dtype(cfg.dtype))}
    params["lm_head"] = head(key, cfg)
    return params

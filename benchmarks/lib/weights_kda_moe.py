"""The benchmark's own seeded weights in the layout ``models/kda_moe.py``
takes, made as ``lib/weights_ssm_moe.py`` makes that family's: each piece (a
layer outside its routed experts, one of its expert stacks, the embedding, the
head) is one jitted program of its own with a key of its own, called by the
program's ``params_fn`` and by the plain reference alike, so the reference
makes layer i again from (seed, i) alone, never reads what the program holds,
and gets the same bits.

Every routed expert has a key of its own (``fold_in(k, expert id)``: the
stacks are ``lib/weights_cohere2_moe.py``'s) and the rows of embedding and
head are drawn under their holder's slice, so a holder of experts ``[lo, hi)``
and rows ``[lo, hi)`` makes its share without drawing the 512 experts or the
157,184 rows.

What a trained model has and a normal draw has not: the decay's ``A_log`` and
bias are drawn so that a step's decay is neither 0 nor 1 — ``exp(A_log)``
uniform in [0.5, 1.5] a head, the bias uniform in [-7, -2] a lane, and the
decay's projection ``W_a`` at the Glorot scale (``a`` then has a spread of
about 0.9): ``g = -5 . sigmoid(exp(A_log) . (a + bias))`` runs from about
-0.005 (a lane that keeps 200 positions) to -0.6 (two), so a state holds both
lanes that a 4,096-token prompt saturates and lanes that forget within a
page. ``W_beta`` at the Glorot scale gives ``beta = sigmoid(.)`` a spread of
1.4 before the sigmoid: most of (0.06, 0.94). The conv taps are normal /
sqrt(K) with no bias; ``e_score_correction_bias`` is drawn non-zero (std
0.1), so that choosing by ``s + b`` and weighing by ``s`` are both exercised
and the routing groups' sums differ, as ``lib/weights_mla_moe.py``'s.

The head is untied and reads RMSNorm's output: unit-scale embedding, as
``lib/weights_mla_moe.py``'s."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.lib.weights import _dense, layer_key, seed_key  # noqa: F401
from benchmarks.lib.weights_cohere2_moe import expert_stack
from benchmarks.lib.weights_sparse_moe import embedding, head  # noqa: F401
from ray_tpu.models.kda_moe import KDA


@partial(jax.jit, static_argnames=("cfg", "kind", "moe"))
def layer_fixed(key, cfg, kind: str, moe: bool) -> dict:
    """A layer outside its routed experts, by its mixer's kind."""
    dtype = jnp.dtype(cfg.dtype)
    d, H, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    k = jax.random.split(key, 16)

    def one(n):
        return {"scale": jnp.ones((n,), dtype)}

    out: dict = {"attn_norm": one(d), "ffn_norm": one(d)}
    if kind == KDA:
        K, C, di = cfg.conv_kernel, cfg.conv_width, cfg.d_inner
        # [q | k | v | a | beta | gate], each piece at its own Glorot scale
        pieces = [_dense(k[0], d, C, dtype), _dense(k[1], d, di, dtype),
                  _dense(k[2], d, H, dtype), _dense(k[3], d, H, dtype)]
        out |= {
            "in_proj": {"kernel": jnp.concatenate(
                [p["kernel"] for p in pieces], axis=1)},
            "conv": {"kernel": (jax.random.normal(k[4], (K, C)) * K ** -0.5
                                ).astype(dtype)},
            "A_log": jnp.log(jax.random.uniform(k[5], (H,), jnp.float32,
                                                0.5, 1.5)),
            "a_bias": jax.random.uniform(k[6], (di,), jnp.float32, -7.0, -2.0),
            "o_norm": one(hd),
            "wo": _dense(k[7], di, d, dtype),
        }
    else:
        r = cfg.kv_lora_rank
        out |= {
            "wq": _dense(k[0], d, H * cfg.qk_head_dim, dtype),
            "wkv_a": _dense(k[1], d, cfg.latent_width, dtype),
            "kv_norm": one(r),
            "wkv_b": _dense(k[2], r, H * (cfg.qk_nope_head_dim + cfg.v_head_dim),
                            dtype),
            "wg": _dense(k[3], d, H, dtype),
            "wo": _dense(k[7], H * cfg.v_head_dim, d, dtype),
        }
    if not moe:
        return {**out, "w_gate": _dense(k[8], d, cfg.d_ff, dtype),
                "w_up": _dense(k[9], d, cfg.d_ff, dtype),
                "w_down": _dense(k[10], cfg.d_ff, d, dtype)}
    Fs = cfg.d_shared
    out["moe"] = {
        "router": {"kernel": _dense(k[8], d, cfg.n_experts, dtype)["kernel"],
                   "bias": 0.1 * jax.random.normal(k[9], (cfg.n_experts,))},
        "shared": {"w_gate": _dense(k[10], d, Fs, dtype),
                   "w_up": _dense(k[11], d, Fs, dtype),
                   "w_down": _dense(k[12], Fs, d, dtype)}}
    return out


def layer_from_seed(key, cfg, i: int) -> dict:
    k = layer_key(key, i)
    out = dict(layer_fixed(k, cfg, cfg.mixer(i), cfg.is_moe_layer(i)))
    if "moe" in out:
        out["moe"] = {**out["moe"],
                      "experts": {"w_gate": expert_stack(k, cfg, 0),
                                  "w_up": expert_stack(k, cfg, 1),
                                  "w_down": expert_stack(k, cfg, 2)}}
    return out


def make_params(key, cfg) -> dict:
    params = {"tok": {"embedding": embedding(key, cfg)}}
    for i in range(cfg.n_layers):
        params[f"layers_{i}"] = layer_from_seed(key, cfg, i)
    params["norm"] = {"scale": jnp.ones((cfg.d_model,), jnp.dtype(cfg.dtype))}
    params["lm_head"] = head(key, cfg)
    return params

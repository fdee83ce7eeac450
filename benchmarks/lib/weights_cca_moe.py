"""The benchmark's own seeded weights in the layout ``models/cca_moe.py``
takes, made as ``lib/weights_kda_moe.py`` makes that family's: each piece (a
layer outside its routed experts, one of its expert stacks, the tied table)
is one jitted program of its own with a key of its own, called by the
program's ``params_fn`` and by the plain reference alike, so the reference
makes layer i again from (seed, i) alone, never reads what the program holds,
and gets the same bits. Every routed expert has a key of its own
(``fold_in(k, expert id)``: the stacks are ``lib/weights_cohere2_moe.py``'s),
so a holder of experts ``[lo, hi)`` makes its share without drawing the rest.

What a trained model has and a normal draw has not, and the ranges chosen:

* **the convolutions' taps**: a depthwise tap is ``+-uniform(0.4, 1.0)`` a
  lane, so neither the position's own tap nor its predecessor's vanishes in
  any lane; a grouped tap is a normal ``[hd, hd]`` matrix a head at ``(2 .
  hd) ** -0.5``, so the two taps' sum keeps a lane's scale; both biases are
  normal at 0.1;
* **the temperature** ``tau`` log-uniform in (0.5, 2) a key head: ``tau = 1``
  and a key cached before it are both far from every head's;
* **the router**: ``gamma_l`` uniform in (0.3, 0.9) — the carry is neither
  lost nor dominant (at 0.6 the stream of ten layers back still weighs 0.6 %
  of this layer's); ``W_down`` Glorot, the MLP's matrices Glorot x
  ``ROUTER_GAIN`` = 1.5 with biases normal at 0.1, and ``W2``, ``W3`` with
  every column's mean over its inputs taken out: a gelu's output has a
  positive mean, which an uncentred matrix turns into an offset that is the
  SAME for every token — some experts then take a third of all tokens and
  others none (measured at the published router widths on random unit
  inputs: 8 to 15 of 16 experts touched by 80 tokens), where a trained
  router is balanced by its bias. Centred, 80 tokens touch 15.5 to 15.9
  of 16, an expert's share of tokens runs 2 to 13 %, the largest ``p`` of a
  token has a median of 0.35 (flat: 0.06; one-hot: 1) and leads the second
  by a median of 0.15; the balancing bias is normal at ``BIAS_STD`` = 0.05,
  of the order of that gap: it changes the choice for 10 to 17 % of tokens,
  so choosing by ``p + bias`` and weighing by ``p`` are both exercised;
* **the residual gains** ``1 + 0.1 . normal`` a lane, all four: gains of 1
  are then far from every layer's;
* **the tied table** at 0.02 and NOT at unit scale, for the reason
  ``lib/weights_cohere2_moe.py`` gives (under a tied head a unit-scale row
  scores its own token ``sqrt(d_model)`` logit spreads above the rest and
  every request decodes its last prompt token for ever); RMSNorm rescales the
  row before every use. The eos id's ROW is zeroed: with a tied head the
  zeroed head column IS the zeroed embedding row, so the traffic draws no
  prompt id equal to it (``lib/traffic.py`` reserves ids under 3) and its
  logit is exactly 0, never the largest of 262,272: random weights decide no
  request's length.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.lib.weights import _dense, layer_key, seed_key  # noqa: F401
from benchmarks.lib.weights_cohere2_moe import expert_stack

ROUTER_GAIN = 1.5
BIAS_STD = 0.05


@partial(jax.jit, static_argnames=("cfg",))
def layer_fixed(key, cfg) -> dict:
    """A layer outside its routed experts."""
    dtype, f32 = jnp.dtype(cfg.dtype), jnp.float32
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    C, R, E = cfg.conv_width, cfg.router_hidden, cfg.n_experts
    k = jax.random.split(key, 24)

    def gain(kk):
        return (1.0 + 0.1 * jax.random.normal(kk, (d,))).astype(dtype)

    def wide(kk, d_in, d_out, centred=True):  # a router matrix, float32
        w = ROUTER_GAIN * _dense(kk, d_in, d_out, f32)["kernel"]
        return w - w.mean(axis=0, keepdims=True) if centred else w

    # [q~ | k~ | v1 | v2], each piece at its own Glorot scale
    pieces = [_dense(k[0], d, H * hd, dtype), _dense(k[1], d, KV * hd, dtype),
              _dense(k[2], d, cfg.v_half, dtype),
              _dense(k[3], d, cfg.v_half, dtype)]
    taps = (jax.random.uniform(k[4], (2, C), f32, 0.4, 1.0)
            * jnp.where(jax.random.bernoulli(k[5], 0.5, (2, C)), 1.0, -1.0))
    return {
        "attn_norm": {"scale": jnp.ones((d,), dtype)},
        "ffn_norm": {"scale": jnp.ones((d,), dtype)},
        "w_in": {"kernel": jnp.concatenate([p["kernel"] for p in pieces], 1)},
        "conv0": {"kernel": taps.astype(dtype),
                  "bias": (0.1 * jax.random.normal(k[6], (C,))).astype(dtype)},
        "conv1": {"kernel": (jax.random.normal(k[7], (2, H + KV, hd, hd))
                             * (2 * hd) ** -0.5).astype(dtype),
                  "bias": (0.1 * jax.random.normal(k[8], (C,))).astype(dtype)},
        "temp": jnp.exp(jax.random.uniform(k[9], (KV,), f32, jnp.log(0.5),
                                           jnp.log(2.0))),
        "wo": _dense(k[10], H * hd, d, dtype),
        "res": {"attn_x": gain(k[11]), "attn_y": gain(k[12]),
                "ffn_x": gain(k[13]), "ffn_y": gain(k[14])},
        "router": {
            "down": _dense(k[15], d, R, dtype)["kernel"],
            "gamma": jax.random.uniform(k[16], (), f32, 0.3, 0.9),
            "norm": {"scale": jnp.ones((R,), f32)},
            "w1": wide(k[17], R, R, False),
            "b1": 0.1 * jax.random.normal(k[18], (R,)),
            "w2": wide(k[19], R, R),
            "b2": 0.1 * jax.random.normal(k[20], (R,)),
            "w3": wide(k[21], R, E),
            "bias": BIAS_STD * jax.random.normal(k[22], (E,))},
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


@partial(jax.jit, static_argnames=("cfg", "zero_row"))
def embedding(key, cfg, zero_row: int | None = None):
    """The tied table, the head too: [vocab, d_model], the eos id's row
    zeroed where one is given."""
    table = (0.02 * jax.random.normal(
        jax.random.fold_in(key, 0), (cfg.vocab_size, cfg.d_model))
    ).astype(jnp.dtype(cfg.dtype))
    return table if zero_row is None else table.at[zero_row].set(0)


def make_params(key, cfg, zero_row: int | None = None) -> dict:
    params = {"tok": {"embedding": embedding(key, cfg, zero_row)}}
    for i in range(cfg.n_layers):
        params[f"layers_{i}"] = layer_from_seed(key, cfg, i)
    params["norm"] = {"scale": jnp.ones((cfg.d_model,), jnp.dtype(cfg.dtype))}
    return params

"""Eighth model family: attention inside a compressed latent whose queries and
keys are mixed by two short convolutions, and ONE wide expert a token chosen
by an MLP router that carries a stream of its own from layer to layer
(``model_type: zaya``).

Same functional-pytree idiom as ``models/llama.py``. A layer is two sublayers,
each with learned residual gains a lane: ``x <- a1 . x + b1 . CCA(N(x))``, then
``x <- a2 . x + b2 . MoE(N(x), r)``; ``N`` an RMSNorm.

* **CCA mixer** (Compressed Convolutional Attention, ``ops/cca.py`` has the
  equations in order). ONE projection of the normed input, ``[q~ | k~ | v1 |
  v2] = h . W_in``: 8 query heads and 2 key heads of 128 lanes — 1,280 lanes
  of a 2,048-wide model — and the value's two halves. Everything up to ``W_o``
  happens inside that latent: the q-k mean, a depthwise and a head-grouped
  causal convolution of two taps each over ``[q~ ; k~]``, L2 norms with a
  learned temperature a key head, rotation of HALF of each head, a value whose
  second half comes from the position before; grouped-query causal attention;
  ``W_o`` back out. A slot's cache of a layer is a K/V page as it grows AND
  one row that does not: ``u``, ``c0`` and ``v2`` of the position before.
* **Expert sublayer.** ``parallel/moe.py`` ``mlp_top1_route``: the router's
  stream ``r_l = h . W_down + gamma_l . r_{l-1}`` goes on beside the residual
  (a token's own, inside one pass over the layers, cached nowhere); an MLP of
  the router's width scores the experts, a softmax, the one of largest ``p +
  bias`` is chosen and weighed by ``p``. Experts are three-matrix SwiGLUs with
  no shared expert. The layer is told which experts it holds
  (``experts_held``): holders' parts add up to the layer.
* **Head.** RMSNorm and ``logits = x . E^T`` over the embedding (tied): ONE
  table read two ways.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ray_tpu.ops import cca
from ray_tpu.ops.attention import masked_attention
from ray_tpu.ops.basic import dense_init, experts_init, rms_norm, rope_freqs
from ray_tpu.parallel.moe import mlp_top1_route, moe_experts
from ray_tpu.utils import tracing


@dataclasses.dataclass(frozen=True)
class CcaMoeConfig:
    family = "cca_moe"   # whose programs serve it: ray_tpu.llm.<family>
    vocab_size: int = 262272
    d_model: int = 2048
    n_layers: int = 40
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 128
    rotary_dim: int = 64              # head_dim . partial_rotary_factor
    rope_theta: float = 5e6
    router_hidden: int = 256
    n_experts: int = 16               # routed, over all holders
    d_expert: int = 2048
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 131072
    dtype: str = "bfloat16"
    # this holder's routed experts of every layer, [lo, hi); None = all
    experts_held: tuple[int, int] | None = None

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads or (
                self.n_kv_heads * self.head_dim) % 2:
            raise ValueError("heads do not divide into their groups, or the "
                             "value into its two halves")
        if self.rotary_dim % 2 or self.rotary_dim > self.head_dim:
            raise ValueError(f"{self.rotary_dim} lanes of a head of "
                             f"{self.head_dim} cannot be rotated in pairs")

    @property
    def held(self) -> tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    @property
    def conv_width(self) -> int:
        """Lanes the convolutions run over: every query and key head."""
        return (self.n_heads + self.n_kv_heads) * self.head_dim

    @property
    def v_half(self) -> int:
        """Lanes of each half of the value: this position's, the one
        before's."""
        return self.n_kv_heads * self.head_dim // 2

    @property
    def in_width(self) -> int:
        """Columns of the one input projection: [q~ | k~ | v1 | v2]."""
        return self.conv_width + 2 * self.v_half

    @classmethod
    def tiny(cls, **kw) -> "CcaMoeConfig":
        """Every ratio kept: 4 query heads on 2 key heads so that the mean
        averages a group, half a head rotated, 4 experts of which 1, a
        router narrower than the model, 3 layers so that the router's carry
        crosses two."""
        base = dict(vocab_size=256, d_model=64, n_layers=3, n_heads=4,
                    n_kv_heads=2, head_dim=16, rotary_dim=8, router_hidden=16,
                    n_experts=4, d_expert=32, max_seq_len=128, dtype="float32")
        return cls(**{**base, **kw})


def cca_moe_layer_init(key, cfg: CcaMoeConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    C, R, E, F = cfg.conv_width, cfg.router_hidden, cfg.n_experts, cfg.d_expert
    k = jax.random.split(key, 17)
    lo, hi = cfg.held

    def gain(kk, n):  # a residual gain a lane, spread about 1
        return (1.0 + 0.1 * jax.random.normal(kk, (n,))).astype(dtype)

    return {
        "attn_norm": {"scale": jnp.ones((D,), dtype)},
        "ffn_norm": {"scale": jnp.ones((D,), dtype)},
        "w_in": dense_init(k[0], D, cfg.in_width, dtype),
        # taps oldest first; neither tap vanishes
        "conv0": {"kernel": (jax.random.normal(k[1], (2, C)) * 2 ** -0.5
                             ).astype(dtype),
                  "bias": (0.1 * jax.random.normal(k[2], (C,))).astype(dtype)},
        "conv1": {"kernel": (jax.random.normal(k[3], (2, H + KV, hd, hd))
                             * (2 * hd) ** -0.5).astype(dtype),
                  "bias": (0.1 * jax.random.normal(k[4], (C,))).astype(dtype)},
        "temp": jax.random.uniform(k[5], (KV,), jnp.float32, 0.5, 2.0),
        "wo": dense_init(k[6], H * hd, D, dtype),
        "res": {"attn_x": gain(k[7], D), "attn_y": gain(k[8], D),
                "ffn_x": gain(k[9], D), "ffn_y": gain(k[10], D)},
        "moe": {
            "router": {
                "down": dense_init(k[11], D, R, dtype)["kernel"],
                "gamma": jnp.float32(0.6),
                "norm": {"scale": jnp.ones((R,), jnp.float32)},
                **_router_mlp(k[12], R, E),
                # the balancing bias; non-zero so that choosing by p + bias
                # and weighing by p are two things
                "bias": 0.05 * jax.random.normal(k[13], (E,))},
            # every holder draws all experts' numbers and keeps its own, so
            # the shares of one seed are slices of one model
            "experts": {
                "w_gate": experts_init(k[14], E, D, F, dtype)[lo:hi],
                "w_up": experts_init(k[15], E, D, F, dtype)[lo:hi],
                "w_down": experts_init(k[16], E, F, D, dtype)[lo:hi]},
        },
    }


def _router_mlp(key, R: int, E: int, scale: float = 2.0) -> dict:
    """The router's three matrices and two biases in float32, scaled up so
    that ``p`` is neither flat nor one-hot."""
    k = jax.random.split(key, 5)
    return {"w1": scale * dense_init(k[0], R, R, jnp.float32)["kernel"],
            "b1": 0.1 * jax.random.normal(k[1], (R,)),
            "w2": scale * dense_init(k[2], R, R, jnp.float32)["kernel"],
            "b2": 0.1 * jax.random.normal(k[3], (R,)),
            "w3": scale * dense_init(k[4], R, E, jnp.float32)["kernel"]}


def cca_moe_init(key, cfg: CcaMoeConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, cfg.n_layers + 1)
    # 0.02 and not unit scale: under a tied head a unit-scale row scores its
    # own token sqrt(d_model) spreads above the rest
    params: dict = {"tok": {"embedding": (0.02 * jax.random.normal(
        keys[0], (cfg.vocab_size, cfg.d_model))).astype(dtype)}}
    for i in range(cfg.n_layers):
        params[f"layers_{i}"] = cca_moe_layer_init(keys[1 + i], cfg)
    params["norm"] = {"scale": jnp.ones((cfg.d_model,), dtype)}
    return params


# ------------------------------------------------------------------ the halves
def cca_rope(cfg: CcaMoeConfig):
    """cos, sin over the rotated lanes of a head."""
    return rope_freqs(cfg.rotary_dim, cfg.max_seq_len, cfg.rope_theta)


@tracing.part("project")
def cca_in(layer, x, cfg: CcaMoeConfig):
    """The mixer's norm and its one projection into the latent. x: [B, T,
    D]. Returns z [B, T, in_width] = [q~ | k~ | v1 | v2]."""
    h = rms_norm(x, layer["attn_norm"]["scale"], cfg.rms_norm_eps)
    return h @ layer["w_in"]["kernel"]


@tracing.part("attn_out")
def cca_out(layer, x, att):
    """``a1 . x + b1 . (att . W_o)``: the mixer's output back out of the
    latent and onto the residual under the sublayer's gains."""
    res = layer["res"]
    return res["attn_x"] * x + res["attn_y"] * (att @ layer["wo"]["kernel"])


def cca_experts(layer, x, r, cfg: CcaMoeConfig, valid=None):
    """The expert sublayer on the residual x [B, T, D] with the router's
    stream ``r`` [B . T, R] float32 of the layer before (None: the first).
    ``valid`` [B, T]: rows that are routed (None: all). Returns (x, r, load
    [held experts])."""
    B, T, D = x.shape
    with tracing.part("experts"):
        h = rms_norm(x, layer["ffn_norm"]["scale"], cfg.rms_norm_eps)
        flat = h.reshape(B * T, D)
    idx, w, r = mlp_top1_route(flat, r, layer["moe"]["router"],
                               cfg.rms_norm_eps)
    y, load = moe_experts(flat, idx, w, layer["moe"], cfg.held,
                          None if valid is None else valid.reshape(B * T))
    with tracing.part("experts"):
        res = layer["res"]
        x = res["ffn_x"] * x + res["ffn_y"] * y.reshape(B, T, D)
    return x, r, load


@tracing.part("head")
def cca_moe_logits(params, x, cfg: CcaMoeConfig):
    """The tied head over every row of the embedding. x: [..., D]. The table
    is contracted on its own second axis: no transposed copy of it."""
    x = rms_norm(x, params["norm"]["scale"], cfg.rms_norm_eps)
    return jnp.einsum("...d,vd->...v", x, params["tok"]["embedding"])


def cca_moe_forward(params, tokens, cfg: CcaMoeConfig):
    """tokens: [B, T] int32 -> logits [B, T, vocab]: the whole model with no
    cache, plain masked attention."""
    B, T = tokens.shape
    cos, sin = cca_rope(cfg)
    idx = jnp.arange(T)
    positions = jnp.broadcast_to(idx[None, :], (B, T))
    causal = jnp.broadcast_to(idx[:, None] >= idx[None, :], (B, T, T))
    x, r = params["tok"]["embedding"][tokens], None
    for i in range(cfg.n_layers):
        layer = params[f"layers_{i}"]
        z = cca_in(layer, x, cfg)
        with tracing.part("mix"):
            q, k, v, _ = cca.cca_mix(layer, z, cos, sin, positions, cfg)
        x = cca_out(layer, x, masked_attention(q, k, v, causal))
        x, r, _ = cca_experts(layer, x, r, cfg)
    return cca_moe_logits(params, x, cfg)

"""Third model family: the Cohere2 sparse-expert shape (``model_type:
cohere2_moe``) — window and full attention layers in one model, a parallel
attention + expert block, LayerNorm, a tied head.

Same functional-pytree idiom as ``models/llama.py``. Every layer is the same
block: with ``h = layer_norm(x)`` (no bias; ONE norm feeds both halves),

    x' = x + Attn_l(h) + MoE(h)

* **Attention.** ``q = h.Wq`` as H heads of ``head_dim``, ``k``, ``v`` as KV
  heads, query head i on KV head ``i // (H // KV)``, scores over
  ``sqrt(head_dim)``, no bias, no QK norm. The layer's KIND is the config's
  ``layer_types[l]``: a ``sliding_attention`` layer rotates q and k over the
  whole head in the adjacent-pair form (``ops/basic.py`` ``rope_pairs``) and
  position i attends j where ``0 <= i - j < sliding_window``; a
  ``full_attention`` layer rotates nothing and attends causally.
* **Experts** (``parallel/moe.py`` ``moe_layer``): sigmoid scores, the k
  largest, weights normalised over the chosen, no selection bias and no
  routed scale; the shared experts are AVERAGED with each other (one SwiGLU
  of their summed width times ``1 / n_shared_experts``) and the mean is
  added to the routed sum. The layer is told which experts it holds
  (``experts_held``): it routes over all of them and computes its own part.
* **Head.** ``logits = logit_scale . layer_norm(x) . E^T`` with E the
  embedding (tied). ``vocab_size`` counts the rows held here (``vocab_held``
  says which of the published ones): ids, logits and sampling are over them.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import masked_attention
from ray_tpu.ops.basic import (
    dense_init, experts_init, layer_norm, rope_freqs, rope_pairs)
from ray_tpu.parallel.moe import moe_layer_chunked
from ray_tpu.utils import tracing

WINDOW, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class Cohere2MoeConfig:
    family = "cohere2_moe"   # whose programs serve it: ray_tpu.llm.<family>
    vocab_size: int = 262144          # rows of the embedding held HERE
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 128
    n_kv_heads: int = 8
    head_dim: int = 128
    layer_types: tuple[str, ...] = (WINDOW, WINDOW, WINDOW, FULL) * 8
    sliding_window: int = 4096
    n_experts: int = 128              # routed, over all holders
    n_experts_per_tok: int = 8
    d_expert: int = 4096
    n_shared_experts: int = 4
    norm_topk_prob: bool = True
    logit_scale: float = 1.0
    layer_norm_eps: float = 1e-5
    max_seq_len: int = 200000
    rope_theta: float = 50000.0
    dtype: str = "bfloat16"
    # this holder's routed experts of every layer, [lo, hi); None = all
    experts_held: tuple[int, int] | None = None
    # which rows of the published vocabulary the vocab_size rows here are,
    # [lo, hi); None = all of it. A sliced vocabulary is a smaller one: ids
    # run from 0 over the slice
    vocab_held: tuple[int, int] | None = None

    def __post_init__(self):
        if len(self.layer_types) != self.n_layers:
            raise ValueError(f"{len(self.layer_types)} layer_types for "
                             f"{self.n_layers} layers")
        if set(self.layer_types) - {WINDOW, FULL}:
            raise ValueError(f"unknown layer type in {self.layer_types}")
        if self.vocab_held and (
                self.vocab_held[1] - self.vocab_held[0] != self.vocab_size):
            raise ValueError(f"vocab_held {self.vocab_held} is not "
                             f"{self.vocab_size} rows")

    @property
    def held(self) -> tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    def is_window(self, i: int) -> bool:
        return self.layer_types[i] == WINDOW

    def layers_of(self, window: bool) -> tuple[int, ...]:
        """The layers of one kind, in order: a layer's place in this list
        is its index into that kind's page pools."""
        return tuple(i for i in range(self.n_layers)
                     if self.is_window(i) == window)

    @classmethod
    def tiny(cls, **kw) -> "Cohere2MoeConfig":
        """The published shape's ratios kept: G = 4 query heads a KV head,
        three window layers and a full one, shared width = 2 x expert
        width averaged, a window much shorter than the context."""
        base = dict(vocab_size=256, d_model=64, n_layers=4, n_heads=8,
                    n_kv_heads=2, head_dim=16,
                    layer_types=(WINDOW, WINDOW, WINDOW, FULL),
                    sliding_window=32, n_experts=16, n_experts_per_tok=4,
                    d_expert=32, n_shared_experts=2, max_seq_len=256,
                    dtype="float32")
        return cls(**{**base, **kw})


def cohere2_moe_layer_init(key, cfg: Cohere2MoeConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    F, Fs = cfg.d_expert, cfg.n_shared_experts * cfg.d_expert
    lo, hi = cfg.held
    k = jax.random.split(key, 11)
    return {
        "norm": {"scale": jnp.ones((D,), dtype)},
        "wq": dense_init(k[0], D, H * hd, dtype),
        "wk": dense_init(k[1], D, KV * hd, dtype),
        "wv": dense_init(k[2], D, KV * hd, dtype),
        "wo": dense_init(k[3], H * hd, D, dtype),
        "moe": {
            "router": {"kernel": dense_init(k[4], D, cfg.n_experts, dtype)["kernel"]},
            # every holder draws all experts' numbers and keeps its own, so
            # the shares of one seed are slices of one model
            "experts": {
                "w_gate": experts_init(k[5], cfg.n_experts, D, F, dtype)[lo:hi],
                "w_up": experts_init(k[6], cfg.n_experts, D, F, dtype)[lo:hi],
                "w_down": experts_init(k[7], cfg.n_experts, F, D, dtype)[lo:hi],
            },
            "shared": {"w_gate": dense_init(k[8], D, Fs, dtype),
                       "w_up": dense_init(k[9], D, Fs, dtype),
                       "w_down": dense_init(k[10], Fs, D, dtype)},
        },
    }


def cohere2_moe_init(key, cfg: Cohere2MoeConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, cfg.n_layers + 1)
    params: dict = {"tok": {"embedding": (0.02 * jax.random.normal(
        keys[0], (cfg.vocab_size, cfg.d_model))).astype(dtype)}}
    for i in range(cfg.n_layers):
        params[f"layers_{i}"] = cohere2_moe_layer_init(keys[1 + i], cfg)
    params["norm"] = {"scale": jnp.ones((cfg.d_model,), dtype)}
    return params


# ------------------------------------------------------------------ the halves
def cohere2_rope_freqs(cfg: Cohere2MoeConfig):
    return rope_freqs(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)


@tracing.part("project")
def cohere2_project(layer, h, cos, sin, positions, cfg: Cohere2MoeConfig,
                    window: bool):
    """The attention half's projections of the normed ``h`` [B, T, D]:
    q [B, T, H, hd], k and v [B, T, KV, hd] — rotated on a window layer,
    as they are on a full one."""
    B, T, _ = h.shape
    q = (h @ layer["wq"]["kernel"]).reshape(B, T, cfg.n_heads, cfg.head_dim)
    k = (h @ layer["wk"]["kernel"]).reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    v = (h @ layer["wv"]["kernel"]).reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    if window:
        q = rope_pairs(q, cos, sin, positions)
        k = rope_pairs(k, cos, sin, positions)
    return q, k, v


@tracing.part("attn_out")
def cohere2_attn_out(layer, att):
    """The attention half's output projection. att: [B, T, H * hd]."""
    return att @ layer["wo"]["kernel"]


def cohere2_reach(q_pos, k_pos, cfg: Cohere2MoeConfig, window: bool):
    """Which key positions a query position attends on a layer of this
    kind: causal, and on a window layer ``q - k < sliding_window``."""
    ok = q_pos >= k_pos
    if window:
        ok &= q_pos - k_pos < cfg.sliding_window
    return ok


@tracing.part("experts")
def cohere2_experts(layer, h, cfg: Cohere2MoeConfig, valid=None):
    """The expert half on the normed ``h`` [B, T, D] -> (y [B, T, D], load
    [held experts])."""
    return moe_layer_chunked(
        h, layer["moe"], valid, k=cfg.n_experts_per_tok, scale=1.0,
        norm=cfg.norm_topk_prob, held=cfg.held,
        shared_scale=1.0 / cfg.n_shared_experts)


@tracing.part("head")
def cohere2_logits(params, x, cfg: Cohere2MoeConfig):
    """The tied head over the held rows of the embedding. x: [..., D]."""
    x = layer_norm(x, params["norm"]["scale"], cfg.layer_norm_eps)
    logits = x @ params["tok"]["embedding"].T
    return logits if cfg.logit_scale == 1 else logits * cfg.logit_scale


def cohere2_moe_forward(params, tokens, cfg: Cohere2MoeConfig):
    """tokens: [B, T] int32 -> logits [B, T, held rows]: the whole model
    with no cache, plain masked attention."""
    B, T = tokens.shape
    cos, sin = cohere2_rope_freqs(cfg)
    idx = jnp.arange(T)
    positions = jnp.broadcast_to(idx[None, :], (B, T))
    x = params["tok"]["embedding"][tokens]
    for i in range(cfg.n_layers):
        layer, window = params[f"layers_{i}"], cfg.is_window(i)
        h = layer_norm(x, layer["norm"]["scale"], cfg.layer_norm_eps)
        q, k, v = cohere2_project(layer, h, cos, sin, positions, cfg, window)
        mask = jnp.broadcast_to(
            cohere2_reach(idx[:, None], idx[None, :], cfg, window), (B, T, T))
        att = masked_attention(q, k, v, mask)
        y, _ = cohere2_experts(layer, h, cfg)
        x = x + cohere2_attn_out(layer, att) + y
    return cohere2_logits(params, x, cfg)

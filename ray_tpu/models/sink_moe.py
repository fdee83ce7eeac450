"""Ninth model family: window layers whose softmax carries a learned SINK
beside full layers of another KV-head count, keys wider than values, a third
of a head rotated (``model_type: mimo_v2_flash``).

Same functional-pytree idiom as ``models/llama.py``. A layer is sequential
under two RMSNorms:

    x = x + Attn_l(rms(x));  x = x + FFN_l(rms(x))

* **Attention.** ``q = h.Wq`` as H heads of ``head_dim``; ``k = h.Wk`` as KV
  heads of ``head_dim``; ``v = value_scale . h.Wv`` as KV heads of
  ``v_head_dim`` (narrower than a key); KV is ``n_kv_heads`` on a full layer
  and ``swa_n_kv_heads`` on a window layer (``layer_window[l]``), query head
  i on KV head ``i // (H // KV)``. The first ``rotary_lanes = int(head_dim .
  partial_rotary_factor)`` lanes of every q and k head rotate in the
  half-split form (``ops/basic.py`` ``rope_lanes``) at the KIND's base
  (``rope_theta`` full, ``swa_rope_theta`` window); the other lanes pass.
  Scores over ``sqrt(head_dim)``; a full layer attends causally, a window
  layer ``0 <= i - j < sliding_window``. A window layer (``sink_window``; a
  full one where ``sink_full``) has one learned score ``sink[h]`` a query
  head that joins the softmax as one more column and is dropped: it takes
  mass and gives no value. The output is H heads of ``v_head_dim``.
* **FFN.** Layer l is an expert layer where ``layer_moe[l]`` (``parallel/
  moe.py`` ``moe_layer``: sigmoid scores, the k largest ``s + bias`` among
  all experts, weighed by ``s`` alone, normalised over the chosen, no shared
  expert; the layer routes over all experts and computes the ones it holds,
  ``experts_held``), else one SwiGLU of width ``d_ff``.
* **Head.** ``logits = rms(x) . W_head``, a matrix of its own (untied).
  ``vocab_size`` counts the rows held here (``vocab_held`` says which of the
  published ones): ids, logits and sampling are over them.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import masked_attention
from ray_tpu.ops.basic import (
    dense_init, experts_init, rms_norm, rope_freqs, rope_lanes, swiglu)
from ray_tpu.parallel.moe import moe_layer_chunked
from ray_tpu.utils import tracing


@dataclasses.dataclass(frozen=True)
class SinkMoeConfig:
    family = "sink_moe"      # whose programs serve it: ray_tpu.llm.<family>
    vocab_size: int = 152576          # rows of embedding and head held HERE
    d_model: int = 4096
    n_layers: int = 48
    n_heads: int = 64
    n_kv_heads: int = 4               # of a full layer
    swa_n_kv_heads: int = 8           # of a window layer
    head_dim: int = 192               # of q and k
    v_head_dim: int = 128
    # hybrid_layer_pattern: True = a window layer
    layer_window: tuple[bool, ...] = (
        (False,) + ((True,) * 4 + (False,) + (True,)) * 7 + (True,) * 4
        + (False,))[:48]
    # moe_layer_freq: True = an expert layer
    layer_moe: tuple[bool, ...] = (False,) + (True,) * 47
    sliding_window: int = 128
    partial_rotary_factor: float = 0.334
    rope_theta: float = 5000000.0     # full layers
    swa_rope_theta: float = 10000.0   # window layers
    value_scale: float = 0.707
    sink_window: bool = True          # add_swa_attention_sink_bias
    sink_full: bool = False           # add_full_attention_sink_bias
    d_ff: int = 16384                 # a dense layer's SwiGLU
    n_experts: int = 256              # routed, over all holders
    n_experts_per_tok: int = 8
    d_expert: int = 2048
    norm_topk_prob: bool = True
    routed_scale: float = 1.0
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 262144
    dtype: str = "bfloat16"
    # this holder's routed experts of every layer, [lo, hi); None = all
    experts_held: tuple[int, int] | None = None
    # which rows of the published vocabulary the vocab_size rows here are,
    # [lo, hi); None = all of it. A sliced vocabulary is a smaller one: ids
    # run from 0 over the slice
    vocab_held: tuple[int, int] | None = None

    def __post_init__(self):
        for name in ("layer_window", "layer_moe"):
            if len(getattr(self, name)) != self.n_layers:
                raise ValueError(f"{len(getattr(self, name))} entries of "
                                 f"{name} for {self.n_layers} layers")
        if self.n_heads % self.n_kv_heads or self.n_heads % self.swa_n_kv_heads:
            raise ValueError("the query heads do not group over the KV heads")
        if self.rotary_lanes % 2:
            raise ValueError(f"{self.rotary_lanes} rotated lanes are not pairs")
        if self.vocab_held and (
                self.vocab_held[1] - self.vocab_held[0] != self.vocab_size):
            raise ValueError(f"vocab_held {self.vocab_held} is not "
                             f"{self.vocab_size} rows")

    @property
    def held(self) -> tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    @property
    def rotary_lanes(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    def is_window(self, i: int) -> bool:
        return bool(self.layer_window[i])

    def is_moe(self, i: int) -> bool:
        return bool(self.layer_moe[i])

    def has_sink(self, window: bool) -> bool:
        return self.sink_window if window else self.sink_full

    def kv_heads(self, window: bool) -> int:
        return self.swa_n_kv_heads if window else self.n_kv_heads

    def layers_of(self, window: bool) -> tuple[int, ...]:
        """The layers of one kind, in order: a layer's place in this list
        is its index into that kind's page pools."""
        return tuple(i for i in range(self.n_layers)
                     if self.is_window(i) == window)

    @classmethod
    def tiny(cls, **kw) -> "SinkMoeConfig":
        """The published shape's ratios kept: 8 query heads on 2 (full) and
        4 (window) KV heads, keys of 24 lanes with the first 8 rotated
        against values of 16, a dense layer then a period of five window
        layers around a full one, a window much shorter than the context."""
        base = dict(vocab_size=256, d_model=64, n_layers=7, n_heads=8,
                    n_kv_heads=2, swa_n_kv_heads=4, head_dim=24,
                    v_head_dim=16,
                    layer_window=(False, True, True, True, True, False, True),
                    layer_moe=(False,) + (True,) * 6, sliding_window=16,
                    d_ff=128, n_experts=16, n_experts_per_tok=4, d_expert=32,
                    max_seq_len=256, dtype="float32")
        return cls(**{**base, **kw})


def sink_moe_layer_init(key, cfg: SinkMoeConfig, i: int) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    D, H, hd, hv = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.v_head_dim
    window = cfg.is_window(i)
    KV = cfg.kv_heads(window)
    k = jax.random.split(key, 10)
    layer = {
        "attn_norm": {"scale": jnp.ones((D,), dtype)},
        "wq": dense_init(k[0], D, H * hd, dtype),
        "wk": dense_init(k[1], D, KV * hd, dtype),
        "wv": dense_init(k[2], D, KV * hv, dtype),
        "wo": dense_init(k[3], H * hv, D, dtype),
        "ffn_norm": {"scale": jnp.ones((D,), dtype)},
    }
    if cfg.has_sink(window):
        layer["sink"] = jax.random.normal(k[4], (H,), jnp.float32)
    if not cfg.is_moe(i):
        F = cfg.d_ff
        return {**layer, "ffn": {
            "w_gate": dense_init(k[5], D, F, dtype)["kernel"],
            "w_up": dense_init(k[6], D, F, dtype)["kernel"],
            "w_down": dense_init(k[7], F, D, dtype)["kernel"]}}
    F = cfg.d_expert
    lo, hi = cfg.held
    # every holder draws all experts' numbers and keeps its own, so the
    # shares of one seed are slices of one model
    return {**layer, "moe": {
        "router": {"kernel": dense_init(k[5], D, cfg.n_experts, dtype)["kernel"],
                   "bias": 0.05 * jax.random.normal(k[9], (cfg.n_experts,))},
        "experts": {
            "w_gate": experts_init(k[6], cfg.n_experts, D, F, dtype)[lo:hi],
            "w_up": experts_init(k[7], cfg.n_experts, D, F, dtype)[lo:hi],
            "w_down": experts_init(k[8], cfg.n_experts, F, D, dtype)[lo:hi]}}}


def sink_moe_init(key, cfg: SinkMoeConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, cfg.n_layers + 2)
    params: dict = {"tok": {"embedding": jax.random.normal(
        keys[0], (cfg.vocab_size, cfg.d_model)).astype(dtype)}}
    for i in range(cfg.n_layers):
        params[f"layers_{i}"] = sink_moe_layer_init(keys[2 + i], cfg, i)
    params["norm"] = {"scale": jnp.ones((cfg.d_model,), dtype)}
    params["head"] = dense_init(keys[1], cfg.d_model, cfg.vocab_size, dtype)
    return params


# ------------------------------------------------------------------ the halves
def sink_rope_freqs(cfg: SinkMoeConfig) -> dict:
    """(cos, sin) over the rotated lanes, a table a kind of layer (window:
    True) at that kind's base."""
    return {window: rope_freqs(cfg.rotary_lanes, cfg.max_seq_len,
                               cfg.swa_rope_theta if window else cfg.rope_theta)
            for window in (False, True)}


@tracing.part("project")
def sink_project(layer, h, ropes, positions, cfg: SinkMoeConfig, window: bool):
    """The attention half's projections of the normed ``h`` [B, T, D]: q
    [B, T, H, hd] and k [B, T, KV, hd] with their first ``rotary_lanes``
    lanes rotated at the kind's base, v [B, T, KV, hv] scaled — KV the
    kind's."""
    B, T, _ = h.shape
    KV = cfg.kv_heads(window)
    cos, sin = ropes[window]
    q = (h @ layer["wq"]["kernel"]).reshape(B, T, cfg.n_heads, cfg.head_dim)
    k = (h @ layer["wk"]["kernel"]).reshape(B, T, KV, cfg.head_dim)
    v = (h @ layer["wv"]["kernel"]).reshape(B, T, KV, cfg.v_head_dim)
    q = rope_lanes(q, cos, sin, positions, cfg.rotary_lanes)
    k = rope_lanes(k, cos, sin, positions, cfg.rotary_lanes)
    return q, k, v * jnp.asarray(cfg.value_scale, v.dtype)


@tracing.part("attn_out")
def sink_attn_out(layer, att):
    """The attention half's output projection. att: [B, T, H * hv]."""
    return att @ layer["wo"]["kernel"]


def sink_reach(q_pos, k_pos, cfg: SinkMoeConfig, window: bool):
    """Which key positions a query position attends on a layer of this
    kind: causal, and on a window layer ``q - k < sliding_window``."""
    ok = q_pos >= k_pos
    if window:
        ok &= q_pos - k_pos < cfg.sliding_window
    return ok


def sink_ffn(layer, g, cfg: SinkMoeConfig, valid=None):
    """The layer's second half on the normed ``g`` [B, T, D]: the dense
    SwiGLU or the held experts' part -> (y [B, T, D], load [held experts],
    None of a dense layer)."""
    if "ffn" in layer:
        with tracing.part("ffn"):
            f = layer["ffn"]
            return swiglu(g, f["w_gate"], f["w_up"], f["w_down"]), None
    with tracing.part("experts"):
        return moe_layer_chunked(
            g, layer["moe"], valid, k=cfg.n_experts_per_tok,
            scale=cfg.routed_scale, norm=cfg.norm_topk_prob, held=cfg.held)


@tracing.part("head")
def sink_logits(params, x, cfg: SinkMoeConfig):
    """The untied head over the held rows. x: [..., D]."""
    x = rms_norm(x, params["norm"]["scale"], cfg.rms_norm_eps)
    return x @ params["head"]["kernel"]


def sink_moe_forward(params, tokens, cfg: SinkMoeConfig):
    """tokens: [B, T] int32 -> logits [B, T, held rows]: the whole model
    with no cache, plain masked attention."""
    B, T = tokens.shape
    ropes = sink_rope_freqs(cfg)
    idx = jnp.arange(T)
    positions = jnp.broadcast_to(idx[None, :], (B, T))
    x = params["tok"]["embedding"][tokens]
    for i in range(cfg.n_layers):
        layer, window = params[f"layers_{i}"], cfg.is_window(i)
        h = rms_norm(x, layer["attn_norm"]["scale"], cfg.rms_norm_eps)
        q, k, v = sink_project(layer, h, ropes, positions, cfg, window)
        mask = jnp.broadcast_to(
            sink_reach(idx[:, None], idx[None, :], cfg, window), (B, T, T))
        att = masked_attention(q, k, v, mask, layer.get("sink"))
        x = x + sink_attn_out(layer, att)
        g = rms_norm(x, layer["ffn_norm"]["scale"], cfg.rms_norm_eps)
        x = x + sink_ffn(layer, g, cfg)[0]
    return sink_logits(params, x, cfg)

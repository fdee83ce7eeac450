"""Second model family: the DeepSeek-V3 shape (``model_type: deepseek_v3``)
— multi-head latent attention and sparse experts with shared experts.

Same functional-pytree idiom as ``models/llama.py``. What differs:

* **Attention (MLA, no query bottleneck).** ``q = h.Wq -> [H, nope + rope]``;
  ``a = h.Wkva -> [kv_lora_rank + rope]``; the latent ``c = rms_norm(a[:r])``
  and ONE rotary key ``k_rope = rope(a[r:])`` for all heads are what a cache
  holds (``cfg.latent_width`` numbers a token a layer); ``[k_nope, v] =
  c.Wkvb`` per head. Scores ``(q_nope.k_nope + q_rope.k_rope) /
  sqrt(nope + rope)``. The serving programs (``llm/mla_moe.py``) take the
  same sum two ways over one cache: expanded (here, and in prefill) and
  absorbed into the latent (decode).
* **Feed-forward.** The first ``first_dense_layers`` layers are a plain
  SwiGLU; every later one is ``parallel/moe.py``'s ``moe_layer``: sigmoid
  top-k routing with a selection bias, no capacity, ``n_shared_experts``
  shared experts. The pattern is the config's, not a modulus.

Rope is the half-split form of ``ops/basic.py``; the published
``rope_interleave: true`` is this under a fixed permutation of the rope
columns of ``wq`` and ``wkv_a`` (a loader of real weights would permute).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ray_tpu.ops.basic import (
    dense_init, experts_init, rms_norm, rope_freqs, swiglu)
from ray_tpu.ops.mla import (  # noqa: F401  (the family's halves, by its name)
    mla_absorb, mla_attend_absorbed, mla_attend_expanded, mla_attend_window,
    mla_expand, mla_project)
from ray_tpu.parallel.moe import moe_layer
from ray_tpu.utils import tracing


@dataclasses.dataclass(frozen=True)
class MlaMoeConfig:
    family = "mla_moe"   # whose programs serve it: ray_tpu.llm.<family>
    vocab_size: int = 128256
    d_model: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 6144                 # the leading dense layers
    first_dense_layers: int = 1
    n_experts: int = 128             # routed, over all holders
    n_experts_per_tok: int = 6
    d_expert: int = 768
    n_shared_experts: int = 2
    routed_scaling_factor: float = 2.448
    norm_topk_prob: bool = True
    max_seq_len: int = 32768
    rope_theta: float = 1e6
    dtype: str = "bfloat16"
    # the routed experts THIS holder has of every expert layer, [lo, hi):
    # it routes over all n_experts and computes its own experts' part
    experts_held: tuple[int, int] | None = None

    @property
    def held(self) -> tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """What a token leaves in the cache per layer: [c, k_rope]."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def is_moe_layer(self, i: int) -> bool:
        return i >= self.first_dense_layers

    @property
    def n_moe_layers(self) -> int:
        return max(0, self.n_layers - self.first_dense_layers)

    @classmethod
    def tiny(cls, **kw) -> "MlaMoeConfig":
        """Every width ratio of the published shape kept: rope part smaller
        than the nope part, v_head_dim != qk_head_dim, shared width = 2 x
        expert width, first layer dense."""
        base = dict(vocab_size=256, d_model=64, n_layers=3, n_heads=4,
                    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                    v_head_dim=16, d_ff=192, first_dense_layers=1,
                    n_experts=16, n_experts_per_tok=3, d_expert=24,
                    n_shared_experts=2, max_seq_len=128, dtype="float32")
        return cls(**{**base, **kw})


def mla_moe_layer_init(key, cfg: MlaMoeConfig, i: int) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    D, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    k = jax.random.split(key, 12)
    layer = {
        "attn_norm": {"scale": jnp.ones((D,), dtype)},
        "wq": dense_init(k[0], D, H * cfg.qk_head_dim, dtype),
        "wkv_a": dense_init(k[1], D, cfg.latent_width, dtype),
        "kv_norm": {"scale": jnp.ones((r,), dtype)},
        # per head: [k_nope | v]
        "wkv_b": dense_init(k[2], r, H * (cfg.qk_nope_head_dim + cfg.v_head_dim),
                        dtype),
        "wo": dense_init(k[3], H * cfg.v_head_dim, D, dtype),
        "ffn_norm": {"scale": jnp.ones((D,), dtype)},
    }
    if not cfg.is_moe_layer(i):
        layer["w_gate"] = dense_init(k[4], D, cfg.d_ff, dtype)
        layer["w_up"] = dense_init(k[5], D, cfg.d_ff, dtype)
        layer["w_down"] = dense_init(k[6], cfg.d_ff, D, dtype)
        return layer
    lo, hi = cfg.held
    F, Fs = cfg.d_expert, cfg.n_shared_experts * cfg.d_expert
    layer["moe"] = {
        "router": {
            "kernel": dense_init(k[4], D, cfg.n_experts, dtype)["kernel"],
            # e_score_correction_bias; non-zero so that choosing by s + b
            # and weighing by s are two things
            "bias": 0.1 * jax.random.normal(k[5], (cfg.n_experts,)),
        },
        # every holder draws all experts' numbers and keeps its own, so the
        # shares of one seed are slices of one model
        "experts": {
            "w_gate": experts_init(k[6], cfg.n_experts, D, F, dtype)[lo:hi],
            "w_up": experts_init(k[7], cfg.n_experts, D, F, dtype)[lo:hi],
            "w_down": experts_init(k[8], cfg.n_experts, F, D, dtype)[lo:hi],
        },
        "shared": {"w_gate": dense_init(k[9], D, Fs, dtype),
                   "w_up": dense_init(k[10], D, Fs, dtype),
                   "w_down": dense_init(k[11], Fs, D, dtype)},
    }
    return layer


def mla_moe_init(key, cfg: MlaMoeConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, cfg.n_layers + 2)
    params: dict = {"tok": {"embedding": (
        jax.random.normal(keys[0], (cfg.vocab_size, cfg.d_model)) * 0.02
    ).astype(dtype)}}
    for i in range(cfg.n_layers):
        params[f"layers_{i}"] = mla_moe_layer_init(keys[2 + i], cfg, i)
    params["norm"] = {"scale": jnp.ones((cfg.d_model,), dtype)}
    params["lm_head"] = dense_init(keys[1], cfg.d_model, cfg.vocab_size, dtype)
    return params


# ---------------------------------------------------------------- feed-forward
@tracing.part("ffn")
def mla_moe_ffn(layer, x, cfg: MlaMoeConfig, valid=None):
    """The layer's second half on the residual ``x`` [B, T, D]. Returns
    (x, load): ``load`` [held experts] is None for a dense layer."""
    h = rms_norm(x, layer["ffn_norm"]["scale"])
    if "moe" not in layer:
        return x + swiglu(h, layer["w_gate"]["kernel"], layer["w_up"]["kernel"],
                          layer["w_down"]["kernel"]), None
    B, T, D = h.shape
    y, load = moe_layer(
        h.reshape(B * T, D), layer["moe"], k=cfg.n_experts_per_tok,
        scale=cfg.routed_scaling_factor, norm=cfg.norm_topk_prob,
        held=cfg.held, valid=None if valid is None else valid.reshape(B * T))
    return x + y.reshape(B, T, D), load


def mla_moe_forward(params, tokens, cfg: MlaMoeConfig):
    """tokens: [B, T] int32 -> logits [B, T, V]: the whole model with no
    cache (expanded attention, causal)."""
    B, T = tokens.shape
    cos, sin = rope_freqs(cfg.qk_rope_head_dim, cfg.max_seq_len, cfg.rope_theta)
    positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    idx = jnp.arange(T)
    mask = jnp.broadcast_to(idx[None, :, None] >= idx[None, None, :], (B, T, T))
    x = params["tok"]["embedding"][tokens]
    for i in range(cfg.n_layers):
        layer = params[f"layers_{i}"]
        h = rms_norm(x, layer["attn_norm"]["scale"])
        q, latent = mla_project(layer, h, cos, sin, positions, cfg)
        x = x + mla_attend_expanded(layer, q, latent, mask, cfg
                                    ) @ layer["wo"]["kernel"]
        x, _ = mla_moe_ffn(layer, x, cfg)
    x = rms_norm(x, params["norm"]["scale"])
    return x @ params["lm_head"]["kernel"]

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
    dense_init, experts_init, rms_norm, rope, rope_freqs, swiglu)
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


# ------------------------------------------------------------------ attention
@tracing.part("project")
def mla_project(layer, h, cos, sin, positions, cfg: MlaMoeConfig):
    """The two projections of a layer's attention input ``h`` [B, T, D]:
    queries ``[B, T, H, nope + rope]`` (rope part rotated) and the cache row
    ``[B, T, r + rope]`` = [c, k_rope] — what both attention paths read."""
    B, T, _ = h.shape
    r, n = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q = (h @ layer["wq"]["kernel"]).reshape(B, T, cfg.n_heads, cfg.qk_head_dim)
    q = jnp.concatenate(
        [q[..., :n], rope(q[..., n:], cos, sin, positions)], axis=-1)
    a = h @ layer["wkv_a"]["kernel"]
    c = rms_norm(a[..., :r], layer["kv_norm"]["scale"])
    k_rope = rope(a[..., None, r:], cos, sin, positions)[:, :, 0]
    return q, jnp.concatenate([c, k_rope], axis=-1)


def _wkv_b(layer, cfg: MlaMoeConfig):
    """``wkv_b`` as [r, H, nope + v]: the K half and the V half per head."""
    return layer["wkv_b"]["kernel"].reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_head_dim + cfg.v_head_dim)


def _softmax_scores(scores, mask, cfg, dtype):
    scores = scores.astype(jnp.float32) / jnp.sqrt(jnp.float32(cfg.qk_head_dim))
    scores = jnp.where(mask[:, None], scores, jnp.float32(-1e30))
    return jax.nn.softmax(scores, axis=-1).astype(dtype)


def _head_groups(B, H, Tq, Tk, limit: int = 1 << 29) -> int:
    """Into how many groups of heads ``mla_attend_expanded`` splits its
    work: the fewest (a divisor of H) that keep one group's bf16 scores
    under ``limit`` bytes. 1 except for a prefill wave of long prompts,
    whose ``[B, H, Tq, Tk]`` scores and probabilities would otherwise be
    the largest temporaries of the program by far."""
    want = -(-B * H * Tq * Tk * 2 // limit)
    return next(g for g in range(1, H + 1) if H % g == 0 and g >= min(want, H))


@tracing.part("attention")
def mla_attend_expanded(layer, q, latent, mask, cfg: MlaMoeConfig):
    """Attention with the cache rows expanded to per-head keys and values
    (``[k_nope, v] = c.Wkvb``): the form for many queries (prefill, the
    plain forward), where the expansion is paid once for all of them. Heads
    are independent, so a large wave runs them a group at a time
    (``_head_groups``), one after the other.

    q: [B, Tq, H, nope + rope]; latent: [B, Tk, r + rope]; mask:
    [B, Tq, Tk]. Returns [B, Tq, H * v]."""
    B, Tk, _ = latent.shape
    Tq = q.shape[1]
    r, n, H = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.n_heads
    kv = jnp.einsum("btr,rhd->bthd", latent[..., :r], _wkv_b(layer, cfg))
    k = jnp.concatenate(
        [kv[..., :n], jnp.broadcast_to(latent[:, :, None, r:],
                                       (B, Tk, H, cfg.qk_rope_head_dim))],
        axis=-1)
    v = kv[..., n:]

    def attend(qkv):
        q, k, v = qkv
        p = _softmax_scores(jnp.einsum("bqhd,bkhd->bhqk", q, k), mask, cfg,
                            q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    g = _head_groups(B, H, Tq, Tk)
    if g == 1:
        out = attend((q, k, v))
    else:
        def grouped(x):  # [B, T, H, d] -> [g, B, T, H / g, d]
            return jnp.moveaxis(x.reshape(*x.shape[:2], g, H // g, -1), 2, 0)

        out = jax.lax.map(attend, (grouped(q), grouped(k), grouped(v)))
        out = jnp.moveaxis(out, 0, 2).reshape(B, Tq, H, -1)
    return out.reshape(B, Tq, H * cfg.v_head_dim)


@tracing.part("attention")
def mla_absorb(layer, q, cfg: MlaMoeConfig):
    """The queries carried into the cache rows' space: ``q_nope`` through the
    K half of ``wkv_b`` per head, beside ``q_rope`` as it is. q: [B, Tq, H,
    nope + rope] -> [B, Tq, H, r + rope]."""
    n = cfg.qk_nope_head_dim
    return jnp.concatenate(
        [jnp.einsum("bqhn,rhn->bqhr", q[..., :n], _wkv_b(layer, cfg)[..., :n]),
         q[..., n:]], axis=-1)


@tracing.part("attention")
def mla_attend_window(q_lat, latent, mask, cfg: MlaMoeConfig):
    """Absorbed queries against cache rows as they lie: scores over the whole
    row, the probabilities sum the rows' latent part. q_lat: [B, Tq, H, r +
    rope]; latent: [B, Tk, r + rope]; mask: [B, Tq, Tk]. Returns [B, Tq, H,
    r]. The plain form of ``ops/paged_attention.py``'s latent kernel."""
    p = _softmax_scores(jnp.einsum("bqhc,bkc->bhqk", q_lat, latent), mask, cfg,
                        q_lat.dtype)
    return jnp.einsum("bhqk,bkr->bqhr", p, latent[..., :cfg.kv_lora_rank])


@tracing.part("attention")
def mla_expand(layer, o_lat, cfg: MlaMoeConfig):
    """The V half of ``wkv_b`` applied once to the summed latents: [B, Tq, H,
    r] -> [B, Tq, H * v]."""
    out = jnp.einsum("bqhr,rhd->bqhd", o_lat,
                     _wkv_b(layer, cfg)[..., cfg.qk_nope_head_dim:])
    return out.reshape(*o_lat.shape[:2], cfg.n_heads * cfg.v_head_dim)


def mla_attend_absorbed(layer, q, latent, mask, cfg: MlaMoeConfig):
    """The same sum without expanding the cache: ``q_nope`` is carried into
    the latent space (``mla_absorb``), scored against the cache rows as they
    lie, the probabilities sum the latents (``mla_attend_window``), and the
    V half of ``wkv_b`` is applied once to the result (``mla_expand``). The
    form for few queries over a long cache (decode): the window is read
    twice and never rewritten to H heads. On a TPU the decode step keeps the
    two ends and lets a kernel attend the pool in place (``llm/mla_moe.py``).

    Shapes as ``mla_attend_expanded``."""
    return mla_expand(
        layer, mla_attend_window(mla_absorb(layer, q, cfg), latent, mask, cfg),
        cfg)


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

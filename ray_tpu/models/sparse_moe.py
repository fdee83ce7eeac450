"""Fourth model family: learned sparse attention over sparse experts (the
``KeyeVL2`` language model's shape: Qwen3-MoE's block with a DeepSeek-V3.2
style indexer in front of its attention).

Same functional-pytree idiom as ``models/llama.py``. Every layer is
``x + Attn(norm(x))`` then ``x + MoE(norm(x))``, RMSNorm, and what differs
from the other families is WHICH keys a query attends: the model chooses.

* **Projections.** ``q = h.Wq`` as H heads of ``head_dim``, ``k``, ``v`` as
  KV heads, no bias; q and k RMS-normed per head, then rotated (half-split,
  the whole head). Query head i reads KV head ``i // (H // KV)``.
* **The indexer** has weights of its own a layer: ``qI = h.WqI`` as J heads
  of ``indexer_head_dim``, ONE key head ``kI = layer_norm(h.WkI)``, both
  rotated over their own width at the same theta, and a weight a head
  ``w = (h.Ww) / sqrt(J . indexer_head_dim)``. The score of key s for query
  t is ``I[t, s] = sum_j w[t, j] . relu(qI[t, j] . kI[s])``, accumulated in
  float32 from the model dtype's inputs (a bf16 score would flip near-tied
  picks, as a bf16 router would).
* **Selection.** Query t attends ``S_t``: the ``topk`` positions ``s <= t``
  with the largest ``I[t, s]``, equal scores to the lower position; all of
  them while ``t < topk``. Exact (``ops/select.py``: the k-th largest by
  bisection on the scores' bits; one kernel a call on a TPU, plain operations
  elsewhere): an approximate or block-level pick is another model. One
  ``S_t`` serves every head.
* **Attention** is a softmax over ``S_t`` alone, scores over
  ``sqrt(head_dim)``.
* **Experts** (``parallel/moe.py`` ``moe_layer``): a softmax over all
  experts in float32, the k most probable renormalised, no shared expert,
  no routed scale. The layer is told which experts it holds
  (``experts_held``): holders' parts add up to the layer.
* **Head.** RMSNorm and an untied head over the held rows (``vocab_held``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import masked_attention
from ray_tpu.ops.basic import (
    dense_init, experts_init, layer_norm, rms_norm, rope, rope_freqs)
from ray_tpu.ops.select import topk_prefix_mask
from ray_tpu.parallel.moe import moe_layer_chunked
from ray_tpu.utils import tracing


@dataclasses.dataclass(frozen=True)
class SparseMoeConfig:
    family = "sparse_moe"   # whose programs serve it: ray_tpu.llm.<family>
    vocab_size: int = 151936          # rows of embedding and head held HERE
    d_model: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    # sa_config's six
    indexer_heads: int = 16
    indexer_head_dim: int = 64
    indexer_kv_heads: int = 1
    topk: int = 2048
    q_chunk: int = 512                # the tiling of scoring and selection:
    kv_chunk: int = 512               # no result depends on either
    n_experts: int = 128              # routed, over all holders
    n_experts_per_tok: int = 8
    d_expert: int = 768
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 262144
    rope_theta: float = 1e7
    dtype: str = "bfloat16"
    # this holder's routed experts of every layer, [lo, hi); None = all
    experts_held: tuple[int, int] | None = None
    # which rows of the published vocabulary the vocab_size rows here are
    vocab_held: tuple[int, int] | None = None

    def __post_init__(self):
        if self.indexer_kv_heads != 1:
            raise ValueError("the indexer has one key head")
        if self.vocab_held and (
                self.vocab_held[1] - self.vocab_held[0] != self.vocab_size):
            raise ValueError(f"vocab_held {self.vocab_held} is not "
                             f"{self.vocab_size} rows")

    @property
    def held(self) -> tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    @classmethod
    def tiny(cls, **kw) -> "SparseMoeConfig":
        """The published shape's ratios kept: G = 8 query heads a KV head,
        an indexer whose keys pack eight to a 128-lane row at pages of 8, a
        topk far under the context."""
        base = dict(vocab_size=256, d_model=64, n_layers=3, n_heads=16,
                    n_kv_heads=2, head_dim=16, indexer_heads=4,
                    indexer_head_dim=16, topk=16, q_chunk=8, kv_chunk=8,
                    n_experts=16, n_experts_per_tok=4, d_expert=32,
                    max_seq_len=128, dtype="float32")
        return cls(**{**base, **kw})


def sparse_moe_layer_init(key, cfg: SparseMoeConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    J, dk, F = cfg.indexer_heads, cfg.indexer_head_dim, cfg.d_expert
    lo, hi = cfg.held
    k = jax.random.split(key, 11)
    one = lambda n: {"scale": jnp.ones((n,), dtype)}  # noqa: E731
    return {
        "attn_norm": one(D), "ffn_norm": one(D),
        "wq": dense_init(k[0], D, H * hd, dtype), "q_norm": one(hd),
        "wk": dense_init(k[1], D, KV * hd, dtype), "k_norm": one(hd),
        "wv": dense_init(k[2], D, KV * hd, dtype),
        "wo": dense_init(k[3], H * hd, D, dtype),
        "indexer": {"wq": dense_init(k[4], D, J * dk, dtype),
                    "wk": dense_init(k[5], D, dk, dtype), "k_norm": one(dk),
                    "w": dense_init(k[6], D, J, dtype)},
        "moe": {
            "router": {"kernel": dense_init(k[7], D, cfg.n_experts, dtype)["kernel"]},
            # every holder draws all experts' numbers and keeps its own, so
            # the shares of one seed are slices of one model
            "experts": {
                "w_gate": experts_init(k[8], cfg.n_experts, D, F, dtype)[lo:hi],
                "w_up": experts_init(k[9], cfg.n_experts, D, F, dtype)[lo:hi],
                "w_down": experts_init(k[10], cfg.n_experts, F, D, dtype)[lo:hi],
            },
        },
    }


def sparse_moe_init(key, cfg: SparseMoeConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, cfg.n_layers + 2)
    params: dict = {"tok": {"embedding": jax.random.normal(
        keys[0], (cfg.vocab_size, cfg.d_model)).astype(dtype)}}
    for i in range(cfg.n_layers):
        params[f"layers_{i}"] = sparse_moe_layer_init(keys[2 + i], cfg)
    params["norm"] = {"scale": jnp.ones((cfg.d_model,), dtype)}
    params["lm_head"] = dense_init(keys[1], cfg.d_model, cfg.vocab_size, dtype)
    return params


# ------------------------------------------------------------------ the halves
def sparse_rope_freqs(cfg: SparseMoeConfig):
    """(cos, sin) of the heads' rotation and of the indexer's: the same
    theta over each one's own width."""
    return (rope_freqs(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta),
            rope_freqs(cfg.indexer_head_dim, cfg.max_seq_len, cfg.rope_theta))


@tracing.part("project")
def sparse_project(layer, h, freqs, positions, cfg: SparseMoeConfig):
    """The attention half's projections of the normed ``h`` [B, T, D]:
    q [B, T, H, hd], k and v [B, T, KV, hd]; q and k normed per head, then
    rotated."""
    B, T, _ = h.shape
    cos, sin = freqs[0]
    q = (h @ layer["wq"]["kernel"]).reshape(B, T, cfg.n_heads, cfg.head_dim)
    k = (h @ layer["wk"]["kernel"]).reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    v = (h @ layer["wv"]["kernel"]).reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    q = rms_norm(q, layer["q_norm"]["scale"], cfg.rms_norm_eps)
    k = rms_norm(k, layer["k_norm"]["scale"], cfg.rms_norm_eps)
    return rope(q, cos, sin, positions), rope(k, cos, sin, positions), v


@tracing.part("indexer")
def sparse_index(layer, h, freqs, positions, cfg: SparseMoeConfig):
    """The indexer's projections of the normed ``h`` [B, T, D]: its queries
    qI [B, T, J, dk], its one key kI [B, T, dk] (what the cache keeps) and
    the heads' weights w [B, T, J] in float32."""
    B, T, _ = h.shape
    ix, (cos, sin) = layer["indexer"], freqs[1]
    J, dk = cfg.indexer_heads, cfg.indexer_head_dim
    qi = rope((h @ ix["wq"]["kernel"]).reshape(B, T, J, dk), cos, sin, positions)
    ki = layer_norm(h @ ix["wk"]["kernel"], ix["k_norm"]["scale"],
                    cfg.rms_norm_eps)
    ki = rope(ki[:, :, None, :], cos, sin, positions)[:, :, 0]
    w = (h @ ix["w"]["kernel"]).astype(jnp.float32) * (J * dk) ** -0.5
    return qi, ki, w


@tracing.part("indexer")
def indexer_scores(qi, w, ki):
    """``I[t, s] = sum_j w[t, j] . relu(qI[t, j] . kI[s])`` written out, in
    float32 from the inputs as they are: the plain form. qi: [B, Tq, J, dk];
    w: [B, Tq, J]; ki: [B, Tk, dk]. Returns [B, Tq, Tk] float32."""
    s = jnp.einsum("bqjd,bsd->bqjs", qi, ki.astype(qi.dtype),
                   preferred_element_type=jnp.float32)
    return (jax.nn.relu(s) * w[..., None]).sum(axis=2)


@tracing.part("select")
def sparse_select(scores, q_pos, cfg: SparseMoeConfig, dtype=jnp.int8):
    """``S_t`` as a 0 / 1 mask of ``dtype``: of the key positions ``0 ..
    q_pos`` the ``topk`` with the largest score, ties to the lower position;
    all of them where there are no more, none where ``q_pos`` < 0. scores:
    [B, Tq, Tk], key s at index s; q_pos: [B, Tq]. Returns [B, Tq, Tk]."""
    return topk_prefix_mask(scores, q_pos, cfg.topk, dtype)


@tracing.part("attn_out")
def sparse_attn_out(layer, att):
    """The attention half's output projection. att: [B, T, H * hd]."""
    return att @ layer["wo"]["kernel"]


@tracing.part("experts")
def sparse_experts(layer, h, cfg: SparseMoeConfig, valid=None):
    """The expert half on the normed ``h`` [B, T, D] -> (y [B, T, D], load
    [held experts])."""
    return moe_layer_chunked(
        h, layer["moe"], valid, k=cfg.n_experts_per_tok, scale=1.0,
        norm=cfg.norm_topk_prob, held=cfg.held, softmax=True)


@tracing.part("head")
def sparse_logits(params, x, cfg: SparseMoeConfig):
    """The untied head over the held rows. x: [..., D]."""
    x = rms_norm(x, params["norm"]["scale"], cfg.rms_norm_eps)
    return x @ params["lm_head"]["kernel"]


def sparse_moe_forward(params, tokens, cfg: SparseMoeConfig):
    """tokens: [B, T] int32 -> logits [B, T, held rows]: the whole model
    with no cache, every score written out."""
    B, T = tokens.shape
    freqs = sparse_rope_freqs(cfg)
    idx = jnp.arange(T)
    positions = jnp.broadcast_to(idx[None, :], (B, T))
    x = params["tok"]["embedding"][tokens]
    for i in range(cfg.n_layers):
        layer = params[f"layers_{i}"]
        h = rms_norm(x, layer["attn_norm"]["scale"], cfg.rms_norm_eps)
        q, k, v = sparse_project(layer, h, freqs, positions, cfg)
        qi, ki, w = sparse_index(layer, h, freqs, positions, cfg)
        picked = sparse_select(indexer_scores(qi, w, ki), positions, cfg) != 0
        x = x + sparse_attn_out(layer, masked_attention(q, k, v, picked))
        h = rms_norm(x, layer["ffn_norm"]["scale"], cfg.rms_norm_eps)
        y, _ = sparse_experts(layer, h, cfg)
        x = x + y
    return sparse_logits(params, x, cfg)

"""Sixth model family: a dense decoder whose attention is EVA's (``model_type:
evabyte``, ``attention_class: "eva"``) — exact keys inside the query's own
window, ONE pooled key/value pair for every chunk of every earlier window, one
softmax over both — with a float32 residual, a unit-offset RMSNorm, plain
multi-head attention (as many KV heads as query heads) and several next-token
heads over a vocabulary of bytes.

Same functional-pytree idiom as ``models/llama.py``. With ``W = window_size``,
``C = chunk_size``, position ``t`` in window ``t // W`` and chunk ``t // C``:

    x' = x + Attn(N(x));  x'' = x' + MLP(N(x'))            (x in float32)
    N(x) = x . rsqrt(mean(x^2) + eps) . (1 + g)            (in the model's dtype)

* **Projections.** ``q, k, v = h.Wq, h.Wk, h.Wv`` as H heads of ``head_dim``,
  q and k rotated over the whole head in the half-split form at the
  position's own index.
* **A chunk's pair** (head h, the C positions s of a chunk): ``pi_s =
  softmax_s(phi_h . k_s / sqrt(hd))``, ``k^ = sum_s pi_s k_s + mu_h``, ``v^ =
  sum_s pi_s v_s``, ``phi`` and ``mu`` two learned vectors a head; of the
  ROTATED keys, softmax and sums in float32.
* **Attention.** Query t attends exactly the positions ``s`` with ``W . (t //
  W) <= s <= t`` and the pairs of every chunk ``c < (W / C) . (t // W)`` —
  never a pair of its own window, never an exact key of an earlier one — under
  ONE softmax over the union, scores over ``sqrt(hd)``, in float32.
* **MLP.** SwiGLU (``ops/basic.py`` ``swiglu``).
* **Head.** Final norm, then an untied head of ``n_pred_heads x vocab_size``
  columns in float32, head-major: head j of position t scores token ``t + 1 +
  j``. Head 0 is the model's next-token distribution.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ray_tpu.ops.basic import dense_init, rms_norm, rope, rope_freqs, swiglu
from ray_tpu.utils import tracing

_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class EvaConfig:
    family = "eva"   # whose programs serve it: ray_tpu.llm.<family>
    vocab_size: int = 320
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    head_dim: int = 128
    d_ff: int = 11008
    window_size: int = 2048
    chunk_size: int = 16
    n_pred_heads: int = 8
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 32768
    rope_theta: float = 100000.0
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.window_size % self.chunk_size:
            raise ValueError(f"a window of {self.window_size} is not whole "
                             f"chunks of {self.chunk_size}")

    @property
    def chunks_per_window(self) -> int:
        return self.window_size // self.chunk_size

    @classmethod
    def tiny(cls, **kw) -> "EvaConfig":
        """The published shape's ratios kept: as many KV heads as query
        heads, a window of 8 chunks, a context of many windows."""
        base = dict(vocab_size=64, d_model=64, n_layers=2, n_heads=4,
                    head_dim=16, d_ff=128, window_size=32, chunk_size=4,
                    n_pred_heads=3, max_seq_len=256, dtype="float32")
        return cls(**{**base, **kw})


def eva_layer_init(key, cfg: EvaConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    D, H, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    k = jax.random.split(key, 9)

    def per_head(key):  # the model's own: a clamped normal over sqrt(hd)
        return (jax.random.truncated_normal(key, -2.0, 2.0, (H, hd))
                * hd ** -0.5).astype(dtype)

    return {
        "attn_norm": {"scale": jnp.zeros((D,), dtype)},  # N(x) . (1 + g)
        "wq": dense_init(k[0], D, H * hd, dtype),
        "wk": dense_init(k[1], D, H * hd, dtype),
        "wv": dense_init(k[2], D, H * hd, dtype),
        "wo": dense_init(k[3], H * hd, D, dtype),
        "phi": per_head(k[4]), "mu": per_head(k[5]),
        "ffn_norm": {"scale": jnp.zeros((D,), dtype)},
        "w_gate": dense_init(k[6], D, cfg.d_ff, dtype),
        "w_up": dense_init(k[7], D, cfg.d_ff, dtype),
        "w_down": dense_init(k[8], cfg.d_ff, D, dtype),
    }


def eva_init(key, cfg: EvaConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, cfg.n_layers + 2)
    params: dict = {"tok": {"embedding": jax.random.normal(
        keys[0], (cfg.vocab_size, cfg.d_model)).astype(dtype)}}
    for i in range(cfg.n_layers):
        params[f"layers_{i}"] = eva_layer_init(keys[1 + i], cfg)
    params["norm"] = {"scale": jnp.zeros((cfg.d_model,), dtype)}
    params["lm_head"] = dense_init(keys[-1], cfg.d_model,
                               cfg.n_pred_heads * cfg.vocab_size, dtype)
    return params


# ------------------------------------------------------------------ the halves
def eva_rope_freqs(cfg: EvaConfig):
    return rope_freqs(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)


def eva_norm(x, g, cfg: EvaConfig):
    """The unit-offset RMSNorm of the float32 residual ``x``, computed and
    given out in the model's dtype."""
    return rms_norm(x.astype(jnp.dtype(cfg.dtype)),
                    1.0 + g.astype(jnp.float32), cfg.rms_norm_eps)


@tracing.part("project")
def eva_project(layer, x, cos, sin, positions, cfg: EvaConfig):
    """The attention half's norm and projections of the residual ``x`` [B,
    T, D]: q, k, v [B, T, H, hd], q and k rotated."""
    B, T, _ = x.shape
    h = eva_norm(x, layer["attn_norm"]["scale"], cfg)
    q, k, v = ((h @ layer[n]["kernel"]).reshape(B, T, cfg.n_heads, cfg.head_dim)
               for n in ("wq", "wk", "wv"))
    return rope(q, cos, sin, positions), rope(k, cos, sin, positions), v


@tracing.part("summary")
def eva_summarize(layer, k, v, out_dtype=None):
    """Pool chunks of keys and values into one pair each. k, v: [..., C, H,
    hd], a chunk's rows (rotated, as the cache holds them). Returns (k^, v^)
    [..., H, hd] in ``out_dtype`` (k's): the softmax over the chunk's C
    positions and both sums in float32."""
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)
    phi, mu = (layer[n].astype(jnp.float32) for n in ("phi", "mu"))
    s = jnp.einsum("...chd,hd->...ch", k32, phi, precision=_HI
                   ) / jnp.sqrt(jnp.float32(k.shape[-1]))
    pi = jax.nn.softmax(s, axis=-2)[..., None]
    out_dtype = out_dtype or k.dtype
    return ((jnp.sum(pi * k32, axis=-3) + mu).astype(out_dtype),
            jnp.sum(pi * v32, axis=-3).astype(out_dtype))


def eva_reach(q_pos, k_pos, cfg: EvaConfig):
    """Which exact key positions a query position attends: causal, inside the
    query's own window."""
    return (k_pos <= q_pos) & (k_pos >= q_pos // cfg.window_size * cfg.window_size)


def eva_pairs_seen(q_pos, cfg: EvaConfig):
    """How many pairs a query position attends: those of every chunk of
    every window before its own."""
    return q_pos // cfg.window_size * cfg.chunks_per_window


@tracing.part("attention")
def eva_attend_plain(q, k, v, kh, vh, mask, mask_pairs):
    """Exact keys and pooled pairs under ONE softmax, the scores written out:
    the plain form (the no-cache forward, and the serving programs off the
    TPU). q: [B, Tq, H, hd]; k, v: [B, Tk, H, hd]; kh, vh: [B, Nc, H, hd];
    mask: [B, Tq, Tk]; mask_pairs: [B, Tq, Nc]. Returns [B, Tq, H * hd]."""
    B, Tq, H, d = q.shape
    keys = jnp.concatenate([k, kh.astype(k.dtype)], axis=1)
    vals = jnp.concatenate([v, vh.astype(v.dtype)], axis=1)
    ok = jnp.concatenate([mask, mask_pairs], axis=-1)
    s = jnp.einsum("bqhd,bshd->bhqs", q, keys,
                   preferred_element_type=jnp.float32)
    s = jnp.where(ok[:, None], s / jnp.sqrt(jnp.float32(d)), jnp.float32(-1e30))
    w = jax.nn.softmax(s, axis=-1).astype(vals.dtype)
    out = jnp.einsum("bhqs,bshd->bqhd", w, vals,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Tq, H * d).astype(q.dtype)


@tracing.part("attn_out")
def eva_attn_out(layer, x, att):
    """The output projection onto the float32 residual. att: [B, T, H * hd]."""
    return x + (att @ layer["wo"]["kernel"]).astype(jnp.float32)


# the most rows of a prompt that go through a layer's per-row work at once
_ROWS = 4096


def row_blocks(T: int) -> list:
    """The slices a prompt's ``T`` rows go through a layer's per-row work in:
    the largest whole blocks of 512 up to ``_ROWS`` that divide T (one slice
    where none does, or T is no more). The float32 copies that XLA keeps
    beside gate and up, the rotation and the pooling of 15,360 rows at once
    are 2 GB that no program needs whole."""
    rows = max((b for b in range(512, _ROWS + 1, 512) if T % b == 0),
               default=T)
    return [slice(i, i + rows) for i in range(0, T, rows)]


@tracing.part("ffn")
def eva_ffn(layer, x, cfg: EvaConfig):
    """The layer's second half on the float32 residual ``x`` [B, T, D]:
    norm, SwiGLU, add, a block of rows at a time (``row_blocks``)."""
    def half(x):
        h = eva_norm(x, layer["ffn_norm"]["scale"], cfg)
        y = swiglu(h, layer["w_gate"]["kernel"], layer["w_up"]["kernel"],
                   layer["w_down"]["kernel"])
        return x + y.astype(jnp.float32)

    return jnp.concatenate(
        [half(x[:, rows]) for rows in row_blocks(x.shape[1])], axis=1)


@tracing.part("head")
def eva_logits(params, x, cfg: EvaConfig, heads: int | None = None):
    """Final norm and the first ``heads`` next-token heads (None: all), in
    float32. x: [..., D] -> [..., heads, vocab_size]."""
    heads = cfg.n_pred_heads if heads is None else heads
    h = eva_norm(x, params["norm"]["scale"], cfg).astype(jnp.float32)
    w = params["lm_head"]["kernel"][:, :heads * cfg.vocab_size]
    logits = jnp.matmul(h, w.astype(jnp.float32), precision=_HI)
    return logits.reshape(*x.shape[:-1], heads, cfg.vocab_size)


def eva_forward(params, tokens, cfg: EvaConfig):
    """tokens: [B, T] int32 -> logits [B, T, n_pred_heads, vocab_size]: the
    whole model with no cache, plain masked attention. The pairs of every
    whole chunk of the T positions are made; a query sees those its window
    lets it."""
    B, T = tokens.shape
    C = cfg.chunk_size
    cos, sin = eva_rope_freqs(cfg)
    idx = jnp.arange(T)
    positions = jnp.broadcast_to(idx[None, :], (B, T))
    n_chunks = T // C
    mask = jnp.broadcast_to(eva_reach(idx[:, None], idx[None, :], cfg), (B, T, T))
    mask_pairs = jnp.broadcast_to(
        jnp.arange(n_chunks)[None, :] < eva_pairs_seen(idx, cfg)[:, None],
        (B, T, n_chunks))
    x = params["tok"]["embedding"][tokens].astype(jnp.float32)
    for i in range(cfg.n_layers):
        layer = params[f"layers_{i}"]
        q, k, v = eva_project(layer, x, cos, sin, positions, cfg)
        whole = n_chunks * C
        kh, vh = eva_summarize(
            layer, k[:, :whole].reshape(B, n_chunks, C, *k.shape[2:]),
            v[:, :whole].reshape(B, n_chunks, C, *v.shape[2:]))
        att = eva_attend_plain(q, k, v, kh, vh, mask, mask_pairs)
        x = eva_ffn(layer, eva_attn_out(layer, x, att), cfg)
    return eva_logits(params, x, cfg)

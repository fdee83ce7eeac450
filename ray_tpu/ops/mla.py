"""Multi-head latent attention (MLA, the DeepSeek-V2/V3 form with no query
bottleneck), the halves that more than one model family takes: the two
projections, and the same attention sum two ways over one cache of latent
rows — expanded to per-head keys and values (prefill, a plain forward) and
absorbed into the latent (decode).

``q = h.Wq -> [H, nope + rope]``; ``a = h.Wkva -> [kv_lora_rank + rope]``; the
latent ``c = rms_norm(a[:r])`` and ONE rotary key ``k_rope = rope(a[r:])`` for
all heads are what a cache holds; ``[k_nope, v] = c.Wkvb`` per head. Scores
``(q_nope.k_nope + q_rope.k_rope) / sqrt(nope + rope)``. ``cfg`` is any
config with ``n_heads``, ``kv_lora_rank``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``qk_head_dim`` and ``v_head_dim``; ``layer`` a tree with
``wq``, ``wkv_a``, ``kv_norm``, ``wkv_b``. ``gate`` [B, Tq, H], where a model
has one, scales each head's output before the output projection (a head-wise
output gate); None is no gate. This file imports no family.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops.basic import rms_norm, rope
from ray_tpu.utils import tracing


@tracing.part("project")
def mla_project(layer, h, cos, sin, positions, cfg):
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


def _wkv_b(layer, cfg):
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
def mla_attend_expanded(layer, q, latent, mask, cfg, gate=None):
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
    if gate is not None:
        out = out * gate[..., None].astype(out.dtype)
    return out.reshape(B, Tq, H * cfg.v_head_dim)


@tracing.part("attention")
def mla_absorb(layer, q, cfg):
    """The queries carried into the cache rows' space: ``q_nope`` through the
    K half of ``wkv_b`` per head, beside ``q_rope`` as it is. q: [B, Tq, H,
    nope + rope] -> [B, Tq, H, r + rope]."""
    n = cfg.qk_nope_head_dim
    return jnp.concatenate(
        [jnp.einsum("bqhn,rhn->bqhr", q[..., :n], _wkv_b(layer, cfg)[..., :n]),
         q[..., n:]], axis=-1)


@tracing.part("attention")
def mla_attend_window(q_lat, latent, mask, cfg):
    """Absorbed queries against cache rows as they lie: scores over the whole
    row, the probabilities sum the rows' latent part. q_lat: [B, Tq, H, r +
    rope]; latent: [B, Tk, r + rope]; mask: [B, Tq, Tk]. Returns [B, Tq, H,
    r]. The plain form of ``ops/paged_attention.py``'s latent kernel."""
    p = _softmax_scores(jnp.einsum("bqhc,bkc->bhqk", q_lat, latent), mask, cfg,
                        q_lat.dtype)
    return jnp.einsum("bhqk,bkr->bqhr", p, latent[..., :cfg.kv_lora_rank])


@tracing.part("attention")
def mla_expand(layer, o_lat, cfg, gate=None):
    """The V half of ``wkv_b`` applied once to the summed latents: [B, Tq, H,
    r] -> [B, Tq, H * v]."""
    out = jnp.einsum("bqhr,rhd->bqhd", o_lat,
                     _wkv_b(layer, cfg)[..., cfg.qk_nope_head_dim:])
    if gate is not None:
        out = out * gate[..., None].astype(out.dtype)
    return out.reshape(*o_lat.shape[:2], cfg.n_heads * cfg.v_head_dim)


def mla_attend_absorbed(layer, q, latent, mask, cfg, gate=None):
    """The same sum without expanding the cache: ``q_nope`` is carried into
    the latent space (``mla_absorb``), scored against the cache rows as they
    lie, the probabilities sum the latents (``mla_attend_window``), and the
    V half of ``wkv_b`` is applied once to the result (``mla_expand``). The
    form for few queries over a long cache (decode): the window is read
    twice and never rewritten to H heads. On a TPU the decode step keeps the
    two ends and lets a kernel attend the pool in place (``llm/mla_moe.py``, ``llm/kda_moe.py``).

    Shapes as ``mla_attend_expanded``."""
    return mla_expand(
        layer, mla_attend_window(mla_absorb(layer, q, cfg), latent, mask, cfg),
        cfg, gate)

"""Elementwise / normalization building blocks.

Kept as plain jnp compositions on purpose: XLA fuses these into the
surrounding matmuls (SURVEY's HBM-bandwidth guidance); pallas is reserved
for ops XLA can't fuse well (attention softmax streaming — see
ops/flash_attention.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps: float = 1e-6):
    """RMSNorm with fp32 accumulation, output in input dtype."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * scale).astype(x.dtype)


def rope_freqs(head_dim: int, max_len: int, theta: float = 10000.0):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)  # [T, D/2]
    return jnp.cos(freqs), jnp.sin(freqs)


def rope(x, cos, sin, positions=None):
    """Rotary position embedding. x: [B, T, H, D]; cos/sin: [T_max, D/2]."""
    B, T, H, D = x.shape
    if positions is None:
        c = cos[:T][None, :, None, :]  # [1, T, 1, D/2]
        s = sin[:T][None, :, None, :]
    else:
        c = cos[positions][:, :, None, :]
        s = sin[positions][:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return out.astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    """SwiGLU FFN: (silu(x@Wg) * (x@Wu)) @ Wd.

    The gate/up products carry a checkpoint name so remat policies can
    opt into saving them (they are the bulk of a block's recompute);
    inert unless a policy matches the name."""
    from jax.ad_checkpoint import checkpoint_name

    gate = checkpoint_name(x @ w_gate, "ffn_hidden")
    up = checkpoint_name(x @ w_up, "ffn_hidden")
    return (jax.nn.silu(gate) * up) @ w_down


def layer_norm(x, scale, eps: float = 1e-5):
    """LayerNorm without a bias (mean removed, unit variance, one gain) with
    fp32 accumulation, output in input dtype."""
    x32 = x.astype(jnp.float32)
    x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def rope_pairs(x, cos, sin, positions):
    """Rotary embedding in the adjacent-pair form (``rope_gptj``): lanes
    (2i, 2i + 1) rotate together by ``positions * theta^(-2i / D)``. x:
    [B, T, H, D]; cos/sin: [T_max, D/2] (``rope_freqs``); positions: [B, T].

    Lane-preserving on purpose, ``x * C + swap(x) * S`` with ``swap`` two
    one-lane shifts and a select on the lane's parity: every lane stays
    where the product ``h @ wq`` left it. The plain form (``x[..., 0::2]``,
    ``x[..., 1::2]``, stack) is a strided slice of a product, which XLA
    moves onto the product's weight: each window layer then re-laid out its
    whole ``wq`` (134 MB) on every decode step (ledger PR 50,
    ``reshape:bf16_128_64_2_4096``; ``tests/test_chip_compile.py``
    ``test_cohere2_moe_decode_lays_no_weight_out``). The shifts pad and
    slice rather than ``jnp.roll``: a roll's wrap-around is a concatenate
    that a 12,288-token prefill materialises (1.3 GB of temporaries)."""
    odd = jnp.arange(x.shape[-1]) % 2 == 1
    c = jnp.repeat(cos[positions], 2, axis=-1)[:, :, None, :]
    s = jnp.repeat(sin[positions], 2, axis=-1)[:, :, None, :]
    lead = ((0, 0),) * (x.ndim - 1)
    after = jnp.pad(x, lead + ((0, 1),))[..., 1:]     # lane j holds x[j + 1]
    before = jnp.pad(x, lead + ((1, 0),))[..., :-1]   # lane j holds x[j - 1]
    out = x * c + jnp.where(odd, before, after) * jnp.where(odd, s, -s)
    return out.astype(x.dtype)


def dense_init(key, d_in, d_out, dtype):
    """A seeded Glorot-scaled matrix as the tree's ``{"kernel": [d_in,
    d_out]}``: what every model family's init draws."""
    scale = (2.0 / (d_in + d_out)) ** 0.5
    kernel = jax.random.normal(key, (d_in, d_out)) * scale
    return {"kernel": kernel.astype(dtype)}


def experts_init(key, n, d_in, d_out, dtype):
    """``n`` experts' matrices ``[n, d_in, d_out]``, scaled as one."""
    scale = (2.0 / (d_in + d_out)) ** 0.5
    return (jax.random.normal(key, (n, d_in, d_out)) * scale).astype(dtype)


def rope_lanes(x, cos, sin, positions, lanes: int):
    """``rope`` over the FIRST ``lanes`` lanes of every head, the others
    passing as they are (``partial_rotary_factor``). x: [B, T, H, D];
    cos/sin: [T_max, lanes/2] (``rope_freqs(lanes, ...)``); positions:
    [B, T]."""
    return jnp.concatenate(
        [rope(x[..., :lanes], cos, sin, positions), x[..., lanes:]], axis=-1)

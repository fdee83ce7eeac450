"""Compressed Convolutional Attention's mixing (CCA, arXiv:2510.04476; the
ZAYA1 report, arXiv:2511.17127): what happens between a layer's projections
into its latent and its attention, in the form a prefill takes (whole
sequences) and in the form a decode step takes (one position of every slot,
from the slot's ROW). Plain XLA operations; both forms run the same lines
(``_mixed``), the sequence form on shifted copies and the step on the row.

The layer projects its normed input ``h`` once, ``z = h . W_in = [q~ | k~ |
v1 | v2]``: ``H`` query heads and ``KV`` key heads of ``hd`` lanes — the
latent, ``(H + KV) . hd`` lanes of a wider model — and the two halves of the
value. Then, in order (the letters are the configuration file's ``assumed``
readings each line rests on):

1. **the mean** (a): ``m_q[j] = (q~[j] + k~[j // G]) / 2`` a query head,
   ``m_k[i] = mean_{j // G = i} m_q[j]`` a key head, ``G = H / KV``; taken
   BEFORE the convolutions and added after them;
2. **two causal convolutions over the sequence** (b) on ``u = [q~ ; k~]``,
   zeros before position 0, a bias each, no activation: ``c0_t = w0[1] . u_t
   + w0[0] . u_{t-1} + b0`` depthwise (taps oldest first, as ``ops/ssm.py``
   has them), then ``c1_t[head] = c0_t[head] . M[1][head] + c0_{t-1}[head] .
   M[0][head] + b1`` grouped by head: each of the ``H + KV`` heads has its
   own ``[hd, hd]`` matrix a tap, so lanes mix inside a head and never
   across heads. Two taps after two taps reach back TWO positions;
3. ``q = c1[q part] + m_q``, ``k = c1[k part] + m_k``;
4. **norm and temperature** (c): ``q <- q / |q| . sqrt(hd)``, ``k <- k / |k|
   . sqrt(hd) . tau_i`` a key head (eps 1e-6, ``tau`` a float32 scalar a key
   head), in float32; the attention scales by ``hd ** -0.5``. The norm
   stands before the rotation, which keeps a head's norm: the other order
   is the same function;
5. **rotation of half a head**: the first ``rotary_dim`` lanes of every head
   of ``q`` and ``k`` (``ops/basic.py`` ``rope_lanes``), the others pass;
6. **the value shifted by half** (d): ``v_t = [v1_t ; v2_{t-1}]`` — the first
   half of the value's lanes (key head 0 of two) from this position, the
   second (key head 1) from the one before, zeros before position 0.

``k`` as it is attended (after all of 1-5) and ``v`` are what a cache holds
of a position. What the mixing needs of the PAST does not grow: ``u_{t-1}``
(``(H + KV) . hd`` numbers, for the first convolution), ``c0_{t-1}`` (as many,
for the second) and ``v2_{t-1}`` (``KV . hd / 2``, the value's late half) —
the ROW, 1,280 + 1,280 + 128 = 2,688 numbers at the published widths,
whatever the length. ``u`` and ``c0`` are held in the model's type in both
forms (``c0`` is rounded to it where it is made), so the row is exactly what
the next position reads.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops import ssm
from ray_tpu.ops.basic import rope_lanes

_F32 = jnp.float32


def cca_mean(qt, kt):
    """The q-k mean (step 1). qt: [..., H, hd]; kt: [..., KV, hd], float32.
    Returns (m_q [..., H, hd], m_k [..., KV, hd])."""
    H, KV = qt.shape[-2], kt.shape[-2]
    m_q = (qt + jnp.repeat(kt, H // KV, axis=-2)) * 0.5
    m_k = m_q.reshape(*m_q.shape[:-2], KV, H // KV, m_q.shape[-1]).mean(axis=-2)
    return m_q, m_k


def row_width(cfg) -> int:
    """Numbers of a slot's row of one layer: u, c0 and the value's late
    half of the position before."""
    return 2 * cfg.conv_width + cfg.v_half


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _conv0(layer, u, u_prev):
    """The depthwise convolution's position from this input and the one
    before, float32 accumulation, in u's type."""
    w = layer["conv0"]["kernel"].astype(_F32)
    return (layer["conv0"]["bias"].astype(_F32) + w[0] * u_prev.astype(_F32)
            + w[1] * u.astype(_F32)).astype(u.dtype)


def _conv1(layer, c0, c0_prev, heads: int):
    """The grouped convolution: a batched product ``[.., heads, hd] x [heads,
    hd, hd]`` a tap. Returns [B, T, heads, hd] float32."""
    w = layer["conv1"]["kernel"]
    B, T, _ = c0.shape

    def tap(a, m):  # float32 operands: a CPU has no bf16 x bf16 = f32 dot,
        # and on the chip one pass over inputs that ARE bf16 is the same product
        return jnp.einsum("bthd,hde->bthe",
                          a.reshape(B, T, heads, -1).astype(_F32),
                          m.astype(_F32))

    return (tap(c0_prev, w[0]) + tap(c0, w[1])
            + layer["conv1"]["bias"].astype(_F32).reshape(heads, -1))


def _mixed(layer, u, c0, c0_prev, v1, v2_prev, cos, sin, positions, cfg):
    """Steps 1 and 2b-6 for positions whose first convolution and whose
    predecessors' are in hand. u, c0, c0_prev: [B, T, C]; v1, v2_prev: [B, T,
    v_half]; positions: [B, T]. Returns q [B, T, H, hd], k, v [B, T, KV, hd]
    in u's type."""
    B, T, _ = u.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    c1 = _conv1(layer, c0, c0_prev, H + KV)
    latent = u.astype(_F32).reshape(B, T, H + KV, hd)
    m_q, m_k = cca_mean(latent[:, :, :H], latent[:, :, H:])
    tau = layer["temp"].astype(_F32)[:, None]
    q = _unit(c1[:, :, :H] + m_q) * hd ** 0.5
    k = _unit(c1[:, :, H:] + m_k) * (hd ** 0.5 * tau)
    q = rope_lanes(q, cos, sin, positions, cfg.rotary_dim)
    k = rope_lanes(k, cos, sin, positions, cfg.rotary_dim)
    v = jnp.concatenate([v1, v2_prev], axis=-1).reshape(B, T, KV, hd)
    return q.astype(u.dtype), k.astype(u.dtype), v


def _split(z, cfg):
    """z = h . W_in: [..., C + 2 . v_half] -> (u, v1, v2)."""
    C, half = cfg.conv_width, cfg.v_half
    return z[..., :C], z[..., C:C + half], z[..., C + half:]


def _before(a):
    """Each position's predecessor along axis 1, zeros before position 0."""
    return jnp.pad(a, ((0, 0), (1, 0), (0, 0)))[:, :-1]


def cca_tail(u, c0, v2, true_lens):
    """The row a prompt leaves AT ITS TRUE LENGTH: ``u``, ``c0`` and ``v2``
    of position ``true_lens - 1``, whatever the pad behind it holds. u, c0:
    [N, T, C]; v2: [N, T, v_half]; true_lens: [N] int32. Returns [N, row]."""
    return jnp.concatenate(
        [ssm.conv_tail(a, true_lens, 2)[:, 0] for a in (u, c0, v2)], axis=-1)


def cca_mix(layer, z, cos, sin, positions, cfg, tails=None):
    """The mixing over whole sequences from nothing before position 0. z:
    [N, T, C + 2 . v_half] (the projection); cos/sin: ``rope_freqs(rotary_dim,
    ...)``; positions: [N, T]; ``tails`` [N] int32: the true lengths at which
    to read the row. Returns (q [N, T, H, hd], k, v [N, T, KV, hd], row [N,
    row width] or None)."""
    u, v1, v2 = _split(z, cfg)
    c0 = _conv0(layer, u, _before(u))
    q, k, v = _mixed(layer, u, c0, _before(c0), v1, _before(v2), cos, sin,
                     positions, cfg)
    if tails is None:
        return q, k, v, None
    # read out beside the mixing and not whenever the scheduler likes: left
    # free, every layer's u and c0 may stay until the program's end for one
    # row of them (models/kda_moe.py ``kda_mixer`` has the measurement)
    q, row = jax.lax.optimization_barrier((q, cca_tail(u, c0, v2, tails)))
    return q, k, v, row


def cca_mix_step(layer, z, row, cos, sin, pos, cfg):
    """One position of every slot from its row. z: [B, 1, C + 2 . v_half];
    row: [B, row width] (what the position before left: zeros at position
    0); pos: [B] int32. Returns (q [B, 1, H, hd], k, v [B, 1, KV, hd], the
    row this position leaves [B, row width])."""
    C = cfg.conv_width
    u, v1, v2 = _split(z, cfg)
    u_prev, c0_prev, v2_prev = (row[:, None, :C], row[:, None, C:2 * C],
                                row[:, None, 2 * C:])
    c0 = _conv0(layer, u, u_prev)
    q, k, v = _mixed(layer, u, c0, c0_prev, v1, v2_prev, cos, sin,
                     pos[:, None], cfg)
    return q, k, v, jnp.concatenate([u, c0, v2], axis=-1)[:, 0]

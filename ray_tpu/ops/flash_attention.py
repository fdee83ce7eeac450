"""Pallas TPU flash attention — forward AND backward kernels.

The hot op the MXU guidance calls for: blockwise streaming softmax so the
[T, T] score matrix never materializes in HBM (no in-tree reference
counterpart — SURVEY §5.7 confirms the reference outsources attention to
torch/vLLM; this is first-class TPU work).

Forward: grid (batch*heads, q_blocks, k_blocks) with the k axis innermost;
online-softmax accumulators (m, l, acc) live in VMEM scratch and survive
across k steps; the output block and the per-row logsumexp (residual for the
backward) are written once on the last k step. Causal masking skips whole
blocks above the diagonal via @pl.when.

Backward (FlashAttention-2 style, two kernels so each output is written by
exactly one grid cell):
  - dq kernel: grid (B*H, q_blocks, k_blocks), k innermost; recomputes
    p = exp(s - lse), ds = p * (dp - delta), accumulates dq in VMEM.
  - dkv kernel: grid (B*H, k_blocks, q_blocks), q innermost; accumulates
    dk and dv.
delta = rowsum(dO * O) is precomputed in plain XLA (cheap elementwise).

Exposed via jax.custom_vjp so jax.grad / value_and_grad see a real kernel on
both sides — no autodiff-through-pallas (which the TPU lowering rejects).
"""

from __future__ import annotations

import functools
import logging
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

log = logging.getLogger(__name__)

_VMEM = pltpu.VMEM
_NEG_BIG = -1e30
_LANES = 128


# ------------------------------------------------------------------ forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                sm_scale: float, causal: bool, block_q: int, block_k: int):
    kj = pl.program_id(2)
    qi = pl.program_id(1)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute():
        q = q_ref[0]  # [block_q, D]
        k = k_ref[0]  # [block_k, D]
        v = v_ref[0]
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [block_q, block_k]
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
            cols = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
            mask = rows >= cols
            scores = jnp.where(mask, scores, _NEG_BIG)

        m_prev = m_scr[:, 0]  # [block_q]
        m_new = jnp.maximum(m_prev, scores.max(axis=1))
        p = jnp.exp(scores - m_new[:, None])
        if causal:
            p = jnp.where(mask, p, 0.0)
        correction = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, 0] * correction + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * correction[:, None] + jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    if causal:
        # skip blocks strictly above the diagonal
        @pl.when(kj * block_k <= qi * block_q + (block_q - 1))
        def _():
            _compute()
    else:
        _compute()

    @pl.when(kj == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[:, 0], 1e-30)
        o_ref[0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)
        # lse broadcast across the 128 lanes: TPU blocks need a (8k, 128)-
        # divisible tail, so per-row scalars ride a full lane dim (same
        # layout jax's own tpu flash kernel uses for its l/m residuals)
        lse = m_scr[:, 0] + jnp.log(denom)
        lse_ref[0] = jnp.broadcast_to(lse[:, None], lse_ref[0].shape)


def _flash_forward(qb, kb, vb, *, causal, sm_scale, block_q, block_k, interpret):
    """qb/kb/vb: [BH, T, D] → (out [BH, T, D], lse [BH, T])."""
    BH, T, D = qb.shape
    Tk = kb.shape[1]
    grid = (BH, T // block_q, Tk // block_k)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k,
    )
    scratch = [
        _VMEM((block_q, _LANES), jnp.float32),
        _VMEM((block_q, _LANES), jnp.float32),
        _VMEM((block_q, D), jnp.float32),
    ]
    out, lse = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct(qb.shape, qb.dtype),
            jax.ShapeDtypeStruct((BH, T, _LANES), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),
        ),
        scratch_shapes=scratch,
        interpret=interpret,
    )(qb, kb, vb)
    return out, lse


# ----------------------------------------------------------------- backward
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, sm_scale, causal, block_q, block_k):
    kj = pl.program_id(2)
    qi = pl.program_id(1)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, 0]  # [block_q] (lane-broadcast residual)
        delta = delta_ref[0][:, 0]  # [block_q]
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        p = jnp.exp(scores - lse[:, None])  # [block_q, block_k]
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, p.shape, 0)
            cols = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
            p = jnp.where(rows >= cols, p, 0.0)
        dp = jax.lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, block_k]
        ds = p * (dp - delta[:, None]) * sm_scale
        dq_scr[...] += jax.lax.dot_general(
            ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        @pl.when(kj * block_k <= qi * block_q + (block_q - 1))
        def _():
            _compute()
    else:
        _compute()

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *,
                    sm_scale, causal, block_q, block_k):
    qi = pl.program_id(2)  # q innermost here
    kj = pl.program_id(1)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, 0]
        delta = delta_ref[0][:, 0]
        # scores^T: [block_k, block_q]
        st = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        pt = jnp.exp(st - lse[None, :])
        if causal:
            krows = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, pt.shape, 0)
            qcols = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, pt.shape, 1)
            pt = jnp.where(qcols >= krows, pt, 0.0)
        dv_scr[...] += jax.lax.dot_general(
            pt, do, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        # dp^T = v @ do^T: [block_k, block_q]
        dpt = jax.lax.dot_general(
            v.astype(jnp.float32), do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dst = pt * (dpt - delta[None, :]) * sm_scale
        dk_scr[...] += jax.lax.dot_general(
            dst, q.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        # skip q blocks that end before this k block starts
        @pl.when(qi * block_q + (block_q - 1) >= kj * block_k)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_backward(qb, kb, vb, ob, lse, dob, *, causal, sm_scale, block_q,
                    block_k, interpret):
    BH, T, D = qb.shape
    Tk = kb.shape[1]
    delta = jnp.sum(dob.astype(jnp.float32) * ob.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, _LANES))

    q_spec = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0))
    k_spec_for_dq = pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0))
    row_spec = pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        out_shape=jax.ShapeDtypeStruct(qb.shape, qb.dtype),
        grid=(BH, T // block_q, Tk // block_k),
        in_specs=[q_spec, k_spec_for_dq, k_spec_for_dq, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        scratch_shapes=[_VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
    )(qb, kb, vb, dob, lse, delta)

    # dkv: grid is (BH, k_blocks, q_blocks) — q axis innermost
    q_spec2 = pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0))
    k_spec2 = pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0))
    row_spec2 = pl.BlockSpec((1, block_q, _LANES), lambda b, j, i: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        out_shape=(
            jax.ShapeDtypeStruct(kb.shape, kb.dtype),
            jax.ShapeDtypeStruct(vb.shape, vb.dtype),
        ),
        grid=(BH, Tk // block_k, T // block_q),
        in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, row_spec2, row_spec2],
        out_specs=(k_spec2, k_spec2),
        scratch_shapes=[
            _VMEM((block_k, D), jnp.float32),
            _VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
    )(qb, kb, vb, dob, lse, delta)
    return dq, dk, dv


# ------------------------------------------------------------ custom_vjp API
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(qb, kb, vb, causal, sm_scale, block_q, block_k, interpret):
    out, _ = _flash_forward(
        qb, kb, vb, causal=causal, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return out


def _flash_fwd_rule(qb, kb, vb, causal, sm_scale, block_q, block_k, interpret):
    out, lse = _flash_forward(
        qb, kb, vb, causal=causal, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    # Residuals carry the "attn_out" checkpoint name so the model's remat
    # policy can SAVE them: without this, rematerialized blocks re-run the
    # whole O(T^2) forward kernel just to regenerate lse — measured ~10
    # MFU points at 8k context. lse is saved in slim [BH, T] form (its
    # kernel layout is lane-broadcast x128) and re-broadcast in the bwd.
    from jax.ad_checkpoint import checkpoint_name

    out_r = checkpoint_name(out, "attn_out")
    lse_r = checkpoint_name(lse[:, :, 0], "attn_out")
    return out, (qb, kb, vb, out_r, lse_r)


def _flash_bwd_rule(causal, sm_scale, block_q, block_k, interpret, res, dout):
    qb, kb, vb, out, lse_slim = res
    lse = jnp.broadcast_to(lse_slim[..., None], (*lse_slim.shape, _LANES))
    dq, dk, dv = _flash_backward(
        qb, kb, vb, out, lse, dout, causal=causal, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return dq, dk, dv


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# default tile sizes, env-overridable for block sweeps (RT_FLASH_BLOCK_Q/K,
# read at import time).
# r5 sweep on v5e, 551M model, T=8192 train step (MFU): 512/512 54.2,
# 512/1024 59.4, 1024/512 55.9, **1024/1024 61.7**; bk=2048 overflows
# VMEM. Bigger tiles amortize the online-softmax rescale + mask overhead
# over 4x the MXU work per grid cell.
_BLOCK_Q = int(os.environ.get("RT_FLASH_BLOCK_Q", "1024"))
_BLOCK_K = int(os.environ.get("RT_FLASH_BLOCK_K", "1024"))


@functools.cache
def _log_interpreting_once(backend: str) -> None:
    log.warning("flash_attention: backend is %r, not 'tpu' — the Pallas "
                "kernels run in interpret mode", backend)


def flash_attention(q, k, v, *, causal: bool = True, sm_scale: float | None = None,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool | None = None):
    """q/k/v: [B, T, H, D] with equal head counts (GQA expanded upstream).

    Differentiable: backward runs the dedicated Pallas kernels above through
    jax.custom_vjp (autodiff through pallas_call is rejected by the TPU
    lowering, and a recompute-free bwd kernel is faster anyway)."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        # decided from the backend this process really has: the kernels
        # compile for the TPU and are interpreted anywhere else
        interpret = jax.default_backend() != "tpu"
        if interpret:
            _log_interpreting_once(jax.default_backend())
    B, T, H, D = q.shape
    Tk = k.shape[1]
    # DEFAULTED blocks clamp then halve until they divide the sequence
    # (the auto dispatch admits any T % 512 == 0, so the 1024 default
    # degrades to 512 for T = 1536, 2560, ... instead of raising);
    # EXPLICIT blocks stay strict — a tile sweep must fail loudly on a
    # mismatched T, never silently record results under the wrong label
    def resolve(requested, default, n):
        if requested is not None:
            return requested  # strict: validated below
        b = min(default, n)
        while b > 128 and n % b:
            b //= 2
        return b

    block_q = resolve(block_q, _BLOCK_Q, T)
    block_k = resolve(block_k, _BLOCK_K, Tk)
    if T % block_q or Tk % block_k:
        raise ValueError(f"seq lens ({T},{Tk}) must divide blocks ({block_q},{block_k})")

    def to_bhtd(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, x.shape[1], D)

    out = _flash(to_bhtd(q), to_bhtd(k), to_bhtd(v), causal, float(sm_scale),
                 block_q, block_k, bool(interpret))
    return out.reshape(B, H, T, D).transpose(0, 2, 1, 3)

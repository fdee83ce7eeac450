"""The paged decode attention kernel (``ops/paged_attention.py``), interpreted
on the CPU, against the plain reference it replaces on the chip: the window
``_kv_read`` gathers from the same pool and ``_gqa_attn`` over it; for the
latent pool, the window ``pool[layer][tables]`` and ``mla_attend_window``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.llama import _gqa_attn, _kv_read
from ray_tpu.models.mla_moe import MlaMoeConfig, mla_attend_window
from ray_tpu.ops.paged_attention import (paged_decode_attention,
                                         paged_latent_attention)

PS, MAXP, HD, L, P = 32, 20, 64, 3, 48  # a 640-token window: 3 blocks of 8 pages
WINDOW = MAXP * PS


def _reference(q, kpool, vpool, layer, tables, lengths):
    kb = _kv_read(kpool, layer, tables, q.dtype)
    vb = _kv_read(vpool, layer, tables, q.dtype)
    mask = jnp.arange(WINDOW)[None, None, :] < lengths[:, None, None]
    return _gqa_attn(q[:, None], kb, vb, mask)[:, 0]


def _tables(rng, lengths):
    """Non-contiguous tables whose entries past a slot's pages are the junk
    page 0; slots 1 and 2 share their first page (a shared prefix)."""
    tables = np.zeros((len(lengths), MAXP), np.int32)
    for b, n in enumerate(lengths):
        pages = min(-(-n // PS), MAXP)
        tables[b, :pages] = rng.permutation(np.arange(1, P))[:pages]
    tables[2, 0] = tables[1, 0]
    return tables


# every length the walk can stumble on, mixed in one batch: inactive, one
# token, a page less one, a page, a page and one, a block's edge, the whole
# window, and a slot that finished mid-block and decodes on past its window
MIXED = [0, 1, PS - 1, PS, PS + 1, 8 * PS, 8 * PS + 1, WINDOW, WINDOW + 5]

CASES = {
    # name: (query heads, KV heads, dtype, layer, lengths)
    "g1_f32": (4, 4, jnp.float32, 1, MIXED),
    "g4_f32": (8, 2, jnp.float32, 1, MIXED),
    "g8_f32": (8, 1, jnp.float32, 1, MIXED),
    "g1_bf16": (4, 4, jnp.bfloat16, 1, MIXED),
    "g4_bf16": (8, 2, jnp.bfloat16, 1, MIXED),
    "g8_bf16": (8, 1, jnp.bfloat16, 1, MIXED),
    "first_layer": (8, 2, jnp.float32, 0, [5, 0, 300]),
    "last_layer": (8, 2, jnp.float32, 2, [0, 0, 77, 0]),
    "all_inactive": (8, 2, jnp.bfloat16, 1, [0, 0, 0]),
    "all_full": (8, 2, jnp.bfloat16, 1, [WINDOW] * 3),
}


@pytest.mark.parametrize("name", list(CASES))
def test_paged_decode_attention_matches_gathered_window(name):
    H, KV, dtype, layer, lengths = CASES[name]
    rng = np.random.default_rng(len(name))
    kpool = jnp.asarray(rng.standard_normal((L, P, PS, KV, HD)), dtype)
    vpool = jnp.asarray(rng.standard_normal((L, P, PS, KV, HD)), dtype)
    q = jnp.asarray(rng.standard_normal((len(lengths), H, HD)), dtype)
    tables = jnp.asarray(_tables(rng, lengths))
    # a length past the slot's allocated pages reads the junk page there,
    # as the gathered window does
    lens = jnp.asarray(lengths, jnp.int32)

    got = jax.jit(paged_decode_attention)(q, kpool, vpool, layer, tables, lens)
    want = _reference(q, kpool, vpool, layer, tables, lens)

    assert got.shape == q.shape and got.dtype == q.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    active = np.asarray(lengths) > 0
    assert not got[~active].any(), "an inactive slot must read as zeros"
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got[active], want[active], atol=tol, rtol=tol)


def test_heads_must_group():
    pool = jnp.zeros((1, 2, PS, 3, HD))
    with pytest.raises(ValueError, match="do not group"):
        paged_decode_attention(jnp.zeros((1, 4, HD)), pool, pool, 0,
                               jnp.zeros((1, MAXP), jnp.int32),
                               jnp.zeros((1,), jnp.int32))


# ------------------------------------------------------------ the latent pool
# rows [c | k_rope] of r + 64 numbers at a small r: the values are not the
# whole row, the row is not whole lane tiles, and the scores' scale is the
# model's 1 / sqrt(nope + rope), which no shape the kernel sees gives away
# the latent form walks blocks of 512 tokens (16 of these pages): its edge too
MIXED_LATENT = MIXED + [16 * PS - 1, 16 * PS, 16 * PS + 1]
LATENT_CASES = {
    # name: (heads, r, rope, nope, dtype, layer, lengths)
    "f32": (8, 32, 64, 16, jnp.float32, 1, MIXED_LATENT),
    "bf16": (8, 32, 64, 16, jnp.bfloat16, 1, MIXED_LATENT),
    "f32_wide_rows": (4, 128, 64, 32, jnp.float32, 1, MIXED_LATENT),
    "bf16_wide_rows": (4, 128, 64, 32, jnp.bfloat16, 1, MIXED_LATENT),
    "first_layer": (8, 32, 64, 16, jnp.float32, 0, [5, 0, 300]),
    "last_layer": (8, 32, 64, 16, jnp.float32, 2, [0, 0, 77, 0]),
    "all_inactive": (8, 32, 64, 16, jnp.bfloat16, 1, [0, 0, 0]),
    "all_full": (8, 32, 64, 16, jnp.bfloat16, 1, [WINDOW] * 3),
    # nope + rope = 192 against a row of 96: 1 / sqrt(W) would be 1.41 x
    "scale_is_the_models": (8, 32, 64, 128, jnp.float32, 1, [1, 40, 333]),
}


@pytest.mark.parametrize("name", list(LATENT_CASES))
def test_paged_latent_attention_matches_gathered_window(name):
    H, r, rope, nope, dtype, layer, lengths = LATENT_CASES[name]
    cfg = MlaMoeConfig.tiny(n_heads=H, kv_lora_rank=r, qk_rope_head_dim=rope,
                            qk_nope_head_dim=nope)
    W = cfg.latent_width
    assert W % 128 and W != r and cfg.qk_head_dim != W
    rng = np.random.default_rng(len(name))
    pool = jnp.asarray(rng.standard_normal((L, P, PS, W)), dtype)
    q = jnp.asarray(rng.standard_normal((len(lengths), H, W)), dtype)
    tables = jnp.asarray(_tables(rng, lengths))
    lens = jnp.asarray(lengths, jnp.int32)

    got = jax.jit(lambda *a: paged_latent_attention(
        *a, v_width=r, sm_scale=cfg.qk_head_dim ** -0.5))(
            q, pool, layer, tables, lens)
    window = pool[layer][tables].reshape(len(lengths), WINDOW, W)
    mask = jnp.arange(WINDOW)[None, None, :] < lens[:, None, None]
    want = mla_attend_window(q[:, None], window, mask, cfg)[:, 0]

    assert got.shape == (len(lengths), H, r) and got.dtype == q.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    active = np.asarray(lengths) > 0
    assert not got[~active].any(), "an inactive slot must read as zeros"
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got[active], want[active], atol=tol, rtol=tol)

"""The paged decode attention kernel (``ops/paged_attention.py``), interpreted
on the CPU, against the plain reference it replaces on the chip: the window
``_kv_read`` gathers from the same pool and ``_gqa_attn`` over it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.llama import _gqa_attn, _kv_read
from ray_tpu.ops.paged_attention import paged_decode_attention

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

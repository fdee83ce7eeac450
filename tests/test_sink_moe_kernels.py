"""The kernels of the window-with-a-sink family interpreted on the CPU: the
chip's branch of both programs end to end, keys of 192 lanes beside values of
128 through every walk of ``ops/paged_attention.py``, the sink as one more
part of a walk, and the blocked prefill that starts at ``(sink, 1)``
(``ops/prefill_attention.py``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _sink_moe_common import (CASES, CFG, PS, RING, WINDOW, _engine,
                              _logit_gaps, _serve)
from ray_tpu.llm import sink_moe as programs
from ray_tpu.ops.attention import gathered_attention, masked_attention
from ray_tpu.ops.paged_attention import (kv_block, merge_attention_parts,
                                         paged_attention_part,
                                         paged_decode_attention)
from ray_tpu.ops.prefill_attention import gqa_prefill_attention
from ray_tpu.utils import metrics


def _interpreted(monkeypatch):
    monkeypatch.setattr(programs, "_reads_in_place", lambda: True)


def test_the_kernels_interpreted_serve_the_references_tokens(monkeypatch):
    """The chip's branch of both programs on the CPU: the walk as a part
    merged with the sink, the plain walk over 2 KV heads, and (a prompt of
    128, whole blocks) the blocked prefill from ``(sink, 1)``."""
    _interpreted(monkeypatch)
    eng = _engine(max_batch=2, block_buckets=(4,))
    assert eng.programs.decode_in_place(eng.cache)
    before = metrics.stage_totals()
    prompts, outs = _serve(eng, [CASES[4], (128, 6)])
    after = metrics.stage_totals()
    for p, o in zip(prompts, outs):
        assert float(_logit_gaps(CFG, p, o).max()) == 0.0

    def grown(name, tag):
        return (after[name][tag]["sum"]
                - before.get(name, {}).get(tag, {"sum": 0})["sum"])

    # in place a window slot fetches the pages its window touches and no
    # more: at most the ring's rows for its 16 live ones
    live = grown("rt_llm_decode_kv_tokens_live_total", "window")
    read = grown("rt_llm_decode_kv_tokens_read_total", "window")
    assert 0 < live <= read <= live * RING * PS / WINDOW


def _pools(key, KV, hk, hv, P=40, ps=8):
    ks = jax.random.split(key, 2)
    return (jax.random.normal(ks[0], (2, P, ps, KV, hk), jnp.float32),
            jax.random.normal(ks[1], (2, P, ps, KV, hv), jnp.float32))


@pytest.mark.parametrize("pad", [0, 64], ids=["k192", "k192_in_256"])
@pytest.mark.parametrize("KV", [4, 8])
def test_keys_of_192_beside_values_of_128_through_every_walk(KV, pad):
    """``paged_decode_attention``, its ``starts`` form and
    ``paged_attention_part`` (plain and ring) interpreted, against
    ``gathered_attention``: K rows of 192 lanes (or 192 kept in 256, the
    rest zeros) beside V rows of 128, at 4 and at 8 KV heads of 64 query
    heads."""
    H, hd, hv, ps, win, entries = 64, 192, 128, 8, 32, 5
    rng = np.random.default_rng(KV)
    lengths = np.array([5, 32, 33, 0, 47, 131], np.int32)
    B = len(lengths)
    q = jax.random.normal(jax.random.PRNGKey(KV), (B, H, hd), jnp.float32)
    kpool, vpool = _pools(jax.random.PRNGKey(KV + 1), KV, hd, hv)
    kpool = jnp.pad(kpool, ((0, 0),) * 4 + ((0, pad),))
    pos = jnp.asarray(np.maximum(lengths - 1, 0))
    live = (lengths > 0)[:, None, None]
    # the ring's walk
    ring = jnp.asarray(rng.permutation(np.arange(1, 31)).reshape(B, entries),
                       jnp.int32)
    starts = jnp.asarray(np.maximum(lengths - win, 0))
    want = gathered_attention(q[:, None], kpool[1], vpool[1], ring, pos, win
                              ).reshape(B, H, hv) * live
    got = paged_decode_attention(q, kpool, vpool, 1, ring, jnp.asarray(lengths),
                                 starts=starts, interpret=True)
    assert got.shape == (B, H, hv)
    assert float(jnp.abs(got - want).max()) < 2e-5
    o, m, l = paged_attention_part(q, kpool, vpool, 1, ring, jnp.asarray(lengths),
                                   starts=starts, interpret=True)
    assert float(jnp.abs(o - want).max()) < 2e-5 and float(l[3].max()) == 0.0
    # the plain walk, from page 0
    flat = jnp.asarray(rng.permutation(np.arange(1, 37)).reshape(B, 6), jnp.int32)
    short = jnp.asarray(np.minimum(lengths, 6 * ps))
    want = gathered_attention(q[:, None], kpool[0], vpool[0], flat,
                              jnp.maximum(short - 1, 0)).reshape(B, H, hv) * live
    got = paged_decode_attention(q, kpool, vpool, 0, flat, short, interpret=True)
    assert float(jnp.abs(got - want).max()) < 2e-5
    o, _, _ = paged_attention_part(q, kpool, vpool, 0, flat, short, interpret=True)
    assert float(jnp.abs(o - want).max()) < 2e-5
    # the block is sized from both rows: 4 heads of 256 | 128 lanes are 16 pages
    k16 = jax.ShapeDtypeStruct((2, 99, 16, KV, 256), jnp.bfloat16)
    v16 = jax.ShapeDtypeStruct((2, 99, 16, KV, 128), jnp.bfloat16)
    assert kv_block(k16, 1152, v16) == ((16, 8) if KV == 4 else (8, 8))
    assert kv_block(v16, 1152) == ((32, 8) if KV == 4 else (16, 8))


@pytest.mark.parametrize("b", [-5.0, -1.0, 0.0, 2.0, 5.0, -np.inf])
def test_a_sink_is_one_more_part_of_the_walk(b):
    """``merge_attention_parts`` with ``(0, b, 1)`` is a softmax with an
    appended, dropped column; a sink of -inf is the plain window walk; a
    slot with no rows gives zeros whatever the sink."""
    KV, H, hd, hv, ps, win, entries = 4, 8, 24, 16, 8, 16, 3
    lengths = np.array([3, 16, 17, 0, 40], np.int32)
    B = len(lengths)
    q = jax.random.normal(jax.random.PRNGKey(1), (B, H, hd), jnp.float32)
    kpool, vpool = _pools(jax.random.PRNGKey(2), KV, hd, hv, P=20)
    ring = jnp.asarray(np.random.default_rng(0).permutation(
        np.arange(1, 16)).reshape(B, entries), jnp.int32)
    starts = jnp.asarray(np.maximum(lengths - win, 0))
    sink = jnp.full((H,), b, jnp.float32) + jnp.arange(H) * 0.1
    part = paged_attention_part(q, kpool, vpool, 0, ring, jnp.asarray(lengths),
                                starts=starts, interpret=True)
    o, m, l = part
    got = merge_attention_parts(part, (
        jnp.zeros_like(o), jnp.broadcast_to(sink, m.shape), jnp.ones_like(l)))
    pos = jnp.asarray(np.maximum(lengths - 1, 0))
    live = (lengths > 0)[:, None, None]
    want = gathered_attention(q[:, None], kpool[0], vpool[0], ring, pos, win,
                              sink if np.isfinite(b) else None
                              ).reshape(B, H, hv) * live
    assert float(jnp.abs(got - want).max()) < 2e-5
    assert not np.asarray(got)[3].any()
    if not np.isfinite(b):  # no sink at all: the walk as it always was
        plain = paged_decode_attention(q, kpool, vpool, 0, ring,
                                       jnp.asarray(lengths), starts=starts,
                                       interpret=True)
        assert float(jnp.abs(got - plain).max()) < 2e-6
    else:  # the sink takes mass: every live row's output shrinks
        assert float(jnp.abs(got).sum()) < float(jnp.abs(o * live).sum())


@pytest.mark.parametrize("window,sink,KV", [
    (128, True, 8), (128, False, 8), (None, False, 4), (None, True, 4),
    (300, True, 8)])
def test_blocked_prefill_with_a_sink_matches_the_masked_form(window, sink, KV):
    """Heads of 192 against values of 128 through the blocked kernel,
    interpreted, against the masked form with the sink as a column."""
    N, T, H, hd, hv = 2, 512, 16, 192, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (N, T, H, hd))
    k = jax.random.normal(ks[1], (N, T, KV, hd))
    v = jax.random.normal(ks[2], (N, T, KV, hv))
    b = 3.0 + jax.random.normal(ks[3], (H,)) if sink else None
    idx = jnp.arange(T)
    ok = idx[:, None] >= idx[None, :]
    if window:
        ok &= idx[:, None] - idx[None, :] < window
    want = masked_attention(q, k, v, jnp.broadcast_to(ok, (N, T, T)), b)
    got = gqa_prefill_attention(q.reshape(N, T, -1), k.reshape(N, T, -1),
                                v.reshape(N, T, -1), n_kv_heads=KV,
                                window=window, sink=b, interpret=True)
    assert got.shape == (N, T, H * hv)
    assert float(jnp.abs(got - want).max()) < 2e-5
    if sink:  # and it is not the softmax without one
        plain = masked_attention(q, k, v, jnp.broadcast_to(ok, (N, T, T)))
        assert float(jnp.abs(got - plain).max()) > 1e-2

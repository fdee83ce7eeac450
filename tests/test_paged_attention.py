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
from ray_tpu.ops import paged_attention
from ray_tpu.ops.paged_attention import (kv_block, merge_attention_parts,
                                         paged_attention_part,
                                         paged_decode_attention,
                                         paged_latent_attention, run_lengths,
                                         walk_copies)

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


# ------------------------------------------- one landing for every K/V walk
# Blocks of 4 pages of 8 tokens with sub-runs of 2 under a table of 10
# entries (two blocks and a half): small enough to interpret, and every edge
# of the real walk is there — a run of the pool is ONE copy a block or a
# sub-run, the rest page by page, whatever the walk masks.
RPS, RMAXP, RHD, RWINDOW = 8, 10, 16, 72
HEADS = {"kv2_g16": (2, 16), "kv8_g4": (8, 4), "kv32_g1": (32, 1)}
# a block's and a sub-run's edge one under, at and one over (31 32 33, 15 16
# 17), a dead slot between live ones, the last block partly live, the table
# whole, and a slot that decoded on past it
PLAIN_LENGTHS = [15, 16, 17, 0, 31, 32, 33, 70, 80, 85]
# a ring: short of the window (no wrap), starts on a page's first position
# and in the middle of one, walks that wrap in their first block, in the
# middle of a later one and between two blocks, a dead slot between them
RING_LENGTHS = [9, 72, 80, 0, 100, 131, 147, 152, 203]


def _small_blocks(monkeypatch, KV, dtype):
    """4 pages a block, 2 a sub-run, for pools of ``KV`` heads."""
    token = 2 * KV * 128 * jnp.dtype(dtype).itemsize
    monkeypatch.setattr(paged_attention, "_BLOCK_BYTES", 4 * RPS * token)
    monkeypatch.setattr(paged_attention, "_RUN_PAGES", 2)


def _run_tables(kind, B):
    """[B, RMAXP] tables over distinct pages of a pool of ``1 + B * RMAXP``:
    ``runs`` — a slot's entries one after the other in the pool, as a free
    list's front hands them out; ``shuffled`` — no entry follows the one
    before it; ``broken`` — runs of 1, 5 and 4 entries: a break inside block
    0's first sub-run and one in the middle of block 1, between its
    sub-runs."""
    t = 1 + np.arange(B * RMAXP, dtype=np.int32).reshape(B, RMAXP)
    if kind == "shuffled":
        t = t[:, ::-1]
    elif kind == "broken":
        t = t[:, [9, 0, 1, 2, 3, 4, 5, 6, 7, 8]]
        t[:, 6:] = t[:, [8, 9, 6, 7]]
    return np.ascontiguousarray(t)


def _dense(q, kpool, vpool, layer, rows):
    """(o, m, l) of one slot's softmax over ``rows``, a list of (page,
    offset): the reference every walk is held to."""
    H, hd = q.shape
    G = H // kpool.shape[3]
    if not len(rows):
        return np.zeros((H, hd)), np.full((H,), -1e30), np.zeros((H,))
    page, off = np.array(rows).T
    k = np.repeat(np.asarray(kpool, np.float32)[layer, page, off], G, axis=1)
    v = np.repeat(np.asarray(vpool, np.float32)[layer, page, off], G, axis=1)
    sc = np.einsum("hd,nhd->hn", np.asarray(q, np.float32), k) / np.sqrt(hd)
    m = sc.max(-1)
    p = np.exp(sc - m[:, None])
    return np.einsum("hn,nhd->hd", p / p.sum(-1, keepdims=True), v), m, p.sum(-1)


def _plain_rows(table, length):
    n = min(length, RMAXP * RPS)
    return [(table[t // RPS], t % RPS) for t in range(n)]


def _ring_rows(table, start, length):
    return [(table[t // RPS % RMAXP], t % RPS) for t in range(start, length)]


def _walk_inputs(heads, kind, dtype, lengths):
    KV, G = HEADS[heads]
    B = len(lengths)
    rng = np.random.default_rng(KV)
    shape = (2, 1 + B * RMAXP, RPS, KV, RHD)
    kpool = jnp.asarray(rng.standard_normal(shape), dtype)
    vpool = jnp.asarray(rng.standard_normal(shape), dtype)
    q = jnp.asarray(rng.standard_normal((B, KV * G, RHD)), dtype)
    return q, kpool, vpool, _run_tables(kind, B)


WALK_CASES = [(h, k, w, jnp.float32) for h in HEADS
              for k in ("runs", "shuffled", "broken")
              for w in ("plain", "ring", "parts")]
# 16-bit rows: the blocks are read as the words they lie in
WALK_CASES += [("kv2_g16", "runs", w, jnp.bfloat16)
               for w in ("plain", "ring", "parts")]
WALK_CASES += [("kv8_g4", "broken", "plain", jnp.bfloat16)]


@pytest.mark.parametrize(
    "heads,kind,walk,dtype", WALK_CASES,
    ids=[f"{h}-{k}-{w}-{jnp.dtype(d).name}" for h, k, w, d in WALK_CASES])
def test_kv_walks_match_a_dense_softmax_whatever_the_tables_runs(
        monkeypatch, heads, kind, walk, dtype):
    """The plain walk, the ring's, and both as parts of one softmax, in the
    interpreter against a dense softmax over the same rows: tables that are
    all runs (a block, or a sub-run of a partly live one, is ONE copy), that
    have none (page by page), and whose runs break inside a block and inside
    a sub-run; ``runs=`` handed over as a program does and made by the call
    agree bit for bit."""
    KV, _ = HEADS[heads]
    _small_blocks(monkeypatch, KV, dtype)
    assert kv_block(jnp.zeros((1, 1, RPS, KV, RHD), dtype), RMAXP) == (4, 2)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    ring_len = np.array(RING_LENGTHS, np.int32)
    ring_start = np.maximum(ring_len - RWINDOW, 0).astype(np.int32)
    lengths = ring_len if walk == "ring" else np.array(PLAIN_LENGTHS, np.int32)
    q, kpool, vpool, tables = _walk_inputs(heads, kind, dtype, lengths)
    t, runs = jnp.asarray(tables), run_lengths(jnp.asarray(tables))
    B = len(lengths)

    if walk == "plain":
        call = lambda **kw: paged_decode_attention(  # noqa: E731
            q, kpool, vpool, 1, t, jnp.asarray(lengths), interpret=True, **kw)
        want = [_dense(q[b], kpool, vpool, 1, _plain_rows(tables[b], n))[0]
                for b, n in enumerate(lengths)]
    elif walk == "ring":
        call = lambda **kw: paged_decode_attention(  # noqa: E731
            q, kpool, vpool, 1, t, jnp.asarray(lengths),
            starts=jnp.asarray(ring_start), interpret=True, **kw)
        want = [_dense(q[b], kpool, vpool, 1,
                       _ring_rows(tables[b], ring_start[b], n))[0]
                for b, n in enumerate(lengths)]
    else:  # a ring over layer 1 and a plain walk over layer 0, merged
        n = min(B, len(ring_len))
        lengths, B = lengths[:n], n
        q, t, runs, tables = q[:n], t[:n], runs[:n], tables[:n]

        def call(**kw):
            a = paged_attention_part(
                q, kpool, vpool, 1, t, jnp.asarray(ring_len[:n]),
                starts=jnp.asarray(ring_start[:n]), interpret=True, **kw)
            b = paged_attention_part(q, kpool, vpool, 0, t,
                                     jnp.asarray(lengths), interpret=True, **kw)
            return jnp.concatenate([merge_attention_parts(a, b), *(
                x[..., None] for x in (*a[1:], *b[1:]))], axis=-1)

        want = []
        for b in range(n):
            ring = _dense(q[b], kpool, vpool, 1,
                          _ring_rows(tables[b], ring_start[b], ring_len[b]))
            flat = _dense(q[b], kpool, vpool, 0,
                          _plain_rows(tables[b], lengths[b]))
            m = np.maximum(ring[1], flat[1])
            w = [x[2] * np.exp(x[1] - m) for x in (ring, flat)]
            o = sum(x[0] * (wi / np.maximum(sum(w), 1e-30))[:, None]
                    for x, wi in zip((ring, flat), w))
            want.append(np.concatenate(
                [o, *(x[:, None] for x in (*ring[1:], *flat[1:]))], axis=-1))

    got = call(runs=runs)
    assert jnp.array_equal(got, call()), "runs= given and made here differ"
    got, want = np.asarray(got, np.float32), np.stack(want)
    live = np.flatnonzero(lengths > 0) if walk != "parts" else np.arange(B)
    if walk != "parts":
        assert not got[lengths == 0].any(), "a dead slot must read as zeros"
    # a part with no rows: l = 0, and m whatever the walk started from
    some = np.abs(want) < 1e29
    np.testing.assert_allclose(np.where(some, got, 0)[live],
                               np.where(some, want, 0)[live],
                               atol=tol, rtol=tol)


def test_run_lengths_and_walk_copies_against_a_hand_count():
    """Entry e of ``run_lengths``: how many entries just before it lie one
    after the other in the pool up to it — so entries [e, e + n) are one copy
    where its entry e + n - 1 is at least n - 1. ``walk_copies`` is the
    kernel's rule counted: units of pages fetched, and those whose pages all
    hold tokens and lie in one run."""
    t = jnp.asarray([[5, 6, 7, 8, 9, 10, 3, 4, 11, 12],
                     [1, 2, 4, 5, 0, 0, 0, 0, 20, 19],
                     [9, 8, 7, 6, 5, 4, 3, 2, 1, 0]], jnp.int32)
    runs = run_lengths(t)
    assert runs.tolist() == [[0, 1, 2, 3, 4, 5, 0, 1, 0, 1],
                             [0, 1, 0, 1, 0, 0, 0, 0, 0, 0],
                             [0] * 10]
    # units of 2 pages: slot 0 holds 7 pages (4 units, 3 whole: all runs),
    # slot 1 5 pages (3 units, 2 whole: both runs), slot 2 10 (5, none)
    live = jnp.asarray([7, 5, 10])
    assert [int(n) for n in walk_copies(runs, 2, live)] == [12, 5]
    # units of 4: whole [0, 4) of slot 0 a run, [4, 8) not; slot 1's [0, 4)
    # breaks at 2 | 4; a slot past its table counts the table
    assert [int(n) for n in walk_copies(runs, 4, jnp.asarray([8, 4, 99]))
            ] == [2 + 1 + 3, 1]
    assert [int(n) for n in walk_copies(runs, 4, jnp.asarray([0, 0, 0]))
            ] == [0, 0]


def test_a_block_follows_the_bytes_of_a_token():
    """1 MB of K and V in flight whatever the number of KV heads, and
    sub-runs of 8 pages where a block is whole ones."""
    def block(kv, hd=128, dtype=jnp.bfloat16, ps=16, maxp=4096):
        return kv_block(jnp.zeros((1, 1, ps, kv, hd), dtype), maxp)

    assert [block(kv) for kv in (2, 4, 8, 32)] == [
        (64, 8), (32, 8), (16, 8), (4, 4)]          # 1,024 512 256 64 tokens
    assert block(8, maxp=10) == (10, 10)            # a table under a block
    assert block(4, hd=64, dtype=jnp.float32, ps=32) == (8, 8)  # padded lanes


@pytest.mark.parametrize("kv,hk,hv,want", [
    (8, 128, 128, (16, 8)),    # equal rows: what it always was
    (8, 128, None, (16, 8)),   # no V pool given: like the K pool
    (8, 256, 128, (8, 8)),     # 8 heads of 256 | 128 lanes: 10 pages -> 8
    (4, 256, 128, (16, 8)),    # 4 heads: 21 pages by the bytes -> 16
    (4, 192, 128, (16, 8)),    # a 192-lane row lies in 256
    (2, 256, 128, (40, 8)),    # 42 -> 40: whole sub-runs of 8
])
def test_a_block_follows_both_pools_rows(kv, hk, hv, want):
    """Keys wider than values: the block is ``_BLOCK_BYTES`` of BOTH rows as
    they lie, in whole sub-runs where that is more than one."""
    k = jax.ShapeDtypeStruct((2, 9, 16, kv, hk), jnp.bfloat16)
    v = None if hv is None else jax.ShapeDtypeStruct((2, 9, 16, kv, hv), jnp.bfloat16)
    assert kv_block(k, 4096, v) == want
    assert kv_block(k, 9, v) == ((9, 9) if want[0] > 9 else want)   # a ring of 9


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

"""The windowed exact + pooled-pair attention family (``models/eva.py``,
``llm/eva.py``, the parts of ``ops/paged_attention.py``, the second key set of
``ops/prefill_attention.py``, the engine's strided and aligned kinds of pages)
against the benchmark's plain float32 reference
(``benchmarks/reference/eva.py``), at two tiny sizes that keep the published
shape's ratios: a window of 8 chunks of 4 on pages of 8, and a window of 4
chunks of 16 on pages of 16 (the page IS the chunk, as published). CPU,
float32, seeded weights."""
import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import weights_eva as W
from benchmarks.reference import eva as R
from ray_tpu.llm.engine import (ContinuousBatchingEngine, UnsupportedByModel,
                                serving_programs)
from ray_tpu.models.eva import EvaConfig, eva_forward, eva_init
from ray_tpu.ops.paged_attention import (
    merge_attention_parts, paged_attention_part, paged_decode_attention)
from ray_tpu.ops.prefill_attention import eva_blocks_for, eva_prefill_attention
from ray_tpu.utils import metrics

# name -> (config, page size): chunks of half a page, and the page a chunk
SIZES = {"w32c4": (EvaConfig.tiny(), 8),
         "w64c16": (EvaConfig.tiny(window_size=64, chunk_size=16,
                                   max_seq_len=512), 16)}
EOS = 2          # its head-0 column is zeroed: no request ends early
SEED = 5


def rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def test_tiny_keeps_the_published_shape():
    full = EvaConfig()
    assert (full.n_heads, full.head_dim, full.d_ff) == (32, 128, 11008)
    assert (full.vocab_size, full.n_pred_heads) == (320, 8)
    assert full.chunks_per_window == 128 and full.rope_theta == 1e5
    for cfg, _ in SIZES.values():
        assert cfg.window_size // cfg.chunk_size in (8, 4)
        params = eva_init(jax.random.PRNGKey(0), cfg)
        seeded = W.make_params(W.seed_key(0), cfg, EOS)
        assert (jax.tree.map(lambda x: (x.shape, x.dtype), params)
                == jax.tree.map(lambda x: (x.shape, x.dtype), seeded))
        assert not np.asarray(seeded["lm_head"]["kernel"])[:, EOS].any()
    with pytest.raises(ValueError, match="whole chunks"):
        EvaConfig.tiny(window_size=30)


# ------------------------------------------------- the engine and the reference
def _engine(size="w32c4", seed=SEED, cfg=None, **kw):
    base, ps = SIZES[size]
    cfg = cfg or base
    params = W.make_params(W.seed_key(seed), cfg, EOS)
    kw = {"max_batch": 3, "page_size": ps, "max_seq_len": cfg.max_seq_len,
          "n_pages": {"window": 13, "summary": 13}, "eos_id": None,
          "block_buckets": (4, 8), **kw}
    return ContinuousBatchingEngine(params, cfg, **kw)


def _serve(eng, cases, seed=0):
    vocab = eng.cfg.vocab_size

    async def run():
        await eng.start()
        rng = np.random.default_rng(seed)
        prompts = [rng.integers(3, vocab, n).tolist() for n, _ in cases]
        outs = await asyncio.wait_for(asyncio.gather(*(
            eng.generate(p, max_tokens=m) for p, (_, m) in zip(prompts, cases))),
            timeout=300)
        await eng.stop()
        return prompts, outs

    return asyncio.run(run())


def _logit_gaps(cfg, prompts, outs, seed=SEED, **ref_kw):
    """For each request, the reference's best head-0 logit less its logit for
    the token the program emitted, at every position, in logit spreads: zeros
    where the program's tokens are the reference's own."""
    gaps = []
    for p, o in zip(prompts, outs):
        logits = np.asarray(R.forward(
            seed, cfg, p + o[:-1], logits_from=len(p) - 1, zero_col=EOS,
            q_block=32, **ref_kw)["logits"])[:, 0]
        gaps.append((logits.max(-1) - logits[np.arange(len(o)), o])
                    / logits.std(-1))
    return np.concatenate(gaps)


def _cases(cfg):
    """Prompts under a chunk, under a window, at a window - 1, at it, past
    it by one, at two windows + 5; replies that cross one and two of a
    window's ends while they decode."""
    Wn = cfg.window_size
    return [(cfg.chunk_size - 1, 9), (Wn - 12, 5), (Wn - 1, 2 * Wn + 4),
            (Wn, 13), (Wn + 1, Wn + 3), (2 * Wn + 5, 21)]


@pytest.mark.parametrize("eos_id", [None, EOS])  # the planned, the reactive loop
@pytest.mark.parametrize("size", list(SIZES))
def test_prefill_then_decode_through_both_kinds_of_pages_is_the_reference(
        size, eos_id):
    eng = _engine(size, eos_id=eos_id)
    cases = _cases(eng.cfg)
    prompts, outs = _serve(eng, cases)
    assert [len(o) for o in outs] == [m for _, m in cases]
    assert float(_logit_gaps(eng.cfg, prompts, outs).max()) == 0.0
    assert [len(f) for f in eng.free] == [12, 12]   # every page of both kinds back


def _alone(size, prompt_len, max_tokens, **kw):
    """One request alone on an engine, its decode steps whole blocks (no step
    runs past its last token): (engine, pages drawn a kind, prompt, out)."""
    eng = _engine(size, **kw)
    prompt = np.random.default_rng(1).integers(
        3, eng.cfg.vocab_size, prompt_len).tolist()
    drawn = [list(f[:n]) for f, n in
             zip(eng.free, eng._pages_of(prompt_len + max_tokens))]
    assert (max_tokens - 1) % 4 == 0

    async def run():
        await eng.start()
        out = await asyncio.wait_for(
            eng.generate(prompt, max_tokens=max_tokens), 300)
        await eng.stop()
        return out

    return eng, drawn, prompt, asyncio.run(run())


def _left(eng, drawn, n_rows):
    """What a request of ``n_rows`` cached positions left: the exact rows its
    ring still holds ``{position: (k, v) [L, H * hd]}`` — entry e holds the
    latest page ``p <= last`` with ``p % entries == e`` — and the pairs of
    its whole chunks ``(kh, vh) [L, chunks, H * hd]``."""
    kw, vw, ks, vs = (np.asarray(c) for c in eng.cache)
    L, PS, C = kw.shape[0], eng.PS, eng.cfg.chunk_size
    entries, last = len(drawn[0]), (n_rows - 1) // PS
    rows = {}
    for p in range(max(0, last - entries + 1), last + 1):
        for r in range(PS):
            if p * PS + r < n_rows:
                at = (slice(None), drawn[0][p % entries], r)
                rows[p * PS + r] = (kw[at].reshape(L, -1), vw[at].reshape(L, -1))
    n_chunks = n_rows // C
    pages = drawn[1][:-(-n_chunks // PS)]
    pairs = tuple(pool[:, pages].reshape(L, len(pages) * PS, -1)[:, :n_chunks]
                  for pool in (ks, vs))
    return rows, pairs


# a prompt that ends mid-page and mid-chunk; a reply that crosses two of a
# window's ends, in whole blocks of 8
ALONE = {"w32c4": (27, 41), "w64c16": (53, 89)}


@pytest.fixture(scope="module", params=list(SIZES))
def alone(request):
    eng, drawn, prompt, out = _alone(request.param, *ALONE[request.param])
    n_rows = len(prompt) + len(out) - 1
    want = R.forward(SEED, eng.cfg, prompt + out[:-1], zero_col=EOS,
                     layers=tuple(range(eng.cfg.n_layers)), q_block=32)
    return eng, drawn, prompt, out, n_rows, want


def test_the_ring_holds_the_references_rows_across_two_window_ends(alone):
    """Of a prompt padded to its page, prefill wrote the rows of the window
    its NEXT position lies in at their true positions; decode wrote on,
    wrapped the ring at each window's end, and what the ring holds at the
    request's end is the reference's rows of its last pages."""
    eng, drawn, prompt, out, n_rows, want = alone
    rows, _ = _left(eng, drawn, n_rows)
    Wn, PS = eng.cfg.window_size, eng.PS
    assert len(drawn[0]) == Wn // PS and n_rows > 2 * Wn
    assert min(rows) == ((n_rows - 1) // PS - Wn // PS + 1) * PS
    for pos, (k, v) in rows.items():
        for layer in range(eng.cfg.n_layers):
            assert rel(k[layer], want["k"][layer][pos]) < 1e-5, (pos, layer)
            assert rel(v[layer], want["v"][layer][pos]) < 1e-5, (pos, layer)


def test_the_pairs_are_those_of_the_true_length_and_decode_completes_the_open_chunk(alone):
    """Every whole chunk's pair is the reference's: the chunks complete at
    the prompt's TRUE length from prefill, the chunk the prompt ended in from
    the rows prefill wrote and the ones decode added, and every later one
    from decode as it filled — in every layer."""
    eng, drawn, prompt, out, n_rows, want = alone
    _, (kh, vh) = _left(eng, drawn, n_rows)
    C = eng.cfg.chunk_size
    assert len(prompt) % C and kh.shape[1] == n_rows // C > len(prompt) // C + 2
    for layer in range(eng.cfg.n_layers):
        for c in range(kh.shape[1]):
            assert rel(kh[layer, c], want["kh"][layer][c]) < 1e-5, (layer, c)
            assert rel(vh[layer, c], want["vh"][layer][c]) < 1e-5, (layer, c)
    assert float(_logit_gaps(eng.cfg, [prompt], [out]).max()) == 0.0


@pytest.mark.parametrize("size", list(SIZES))
def test_a_prompt_padded_to_its_page_and_its_wave_leaves_no_pair_of_a_pad(size):
    """Prompts of one-token requests, two of them one wave of one pad bucket
    (both loops' waves; the planned loop once left such a request's stream
    open when two waves were synced before it was retired): each slot's
    pages of pairs hold the pairs of the chunks
    complete at ITS true length and, past the chunk the prompt ended in
    (which the block the loop runs on past a one-token request may fill),
    nothing."""
    cfg, PS = SIZES[size]
    eng = _engine(size)
    eng.cache = tuple(jnp.full_like(c, 7.0) for c in eng.cache)
    # the first two are one pad (a wave of two); the third a wave of its own
    lens = (2 * PS - 1, 2 * PS - 3, PS + 1)
    prompts, _ = _serve(eng, [(n, 1) for n in lens])
    ks = np.asarray(eng.cache[2])
    for slot, (n, prompt) in enumerate(zip(lens, prompts)):
        want = R.forward(SEED, cfg, prompt, layers=(0,), zero_col=EOS)["kh"][0]
        page = 1 + slot                                    # drawn in order
        done = n // cfg.chunk_size
        got = ks[0, page].reshape(PS, -1)
        assert rel(got[:done], want[:done]) < 1e-5
        assert (got[done + 2:] == 7.0).all()               # never written


@pytest.mark.parametrize("size", list(SIZES))
def test_no_row_that_the_request_did_not_write_reaches_it(size):
    """Every pool filled with a large value first — the junk pages, the pages
    of a slot that was released, the rows past a slot's length, the pairs of
    chunks not made yet: tokens still are the reference's, so nothing of it
    was attended; dead slots ran beside the live ones throughout."""
    eng = _engine(size)
    eng.cache = tuple(jnp.full_like(c, 50.0) for c in eng.cache)
    cases = _cases(eng.cfg)[2:]
    prompts, outs = _serve(eng, cases)
    assert float(_logit_gaps(eng.cfg, prompts, outs).max()) == 0.0


def test_a_slot_under_one_window_is_plain_causal_attention():
    cfg, _ = SIZES["w32c4"]
    params = W.make_params(W.seed_key(SEED), cfg, EOS)
    toks = jnp.asarray([np.random.default_rng(0).integers(3, 64, 32)])
    wide = dataclasses.replace(cfg, window_size=128)     # nothing pooled
    assert rel(eva_forward(params, toks, cfg),
               eva_forward(params, toks, wide)) < 1e-6
    longer = jnp.asarray([np.random.default_rng(0).integers(3, 64, 40)])
    assert rel(eva_forward(params, longer, cfg)[:, 32:],
               eva_forward(params, longer, wide)[:, 32:]) > 1e-3


@pytest.mark.parametrize("size", list(SIZES))
def test_the_no_cache_forward_gives_every_heads_logits(size):
    cfg, _ = SIZES[size]
    toks = np.random.default_rng(0).integers(3, cfg.vocab_size,
                                             2 * cfg.window_size + 13).tolist()
    params = W.make_params(W.seed_key(SEED), cfg, EOS)
    mine = eva_forward(params, jnp.asarray([toks]), cfg)[0]
    want = R.forward(SEED, cfg, toks, zero_col=EOS, q_block=32)["logits"]
    assert mine.shape == (len(toks), cfg.n_pred_heads, cfg.vocab_size)
    assert mine.dtype == jnp.float32
    for head in range(cfg.n_pred_heads):
        assert rel(mine[:, head], want[:, head]) < 1e-5


# ---------------------------------------------------------------- the controls
@pytest.mark.parametrize("variant", [
    {"pool": "mean"}, {"no_mu": True}, {"own_pairs": True}, {"sliding": True},
    {"two_softmax": True}, {"unrotated_pairs": True},
    {"pad": 32, "pad_from": 27}, {"residual": "bfloat16"},
    {"no_pairs_from": 27}], ids=lambda v: next(iter(v)))
def test_other_mathematics_fails_the_comparison_the_program_passes(variant):
    """The reference with each control's mathematics in the program's place:
    some row, pair or logit is off by far more than the program is."""
    eng, drawn, prompt, out = _alone("w32c4", *ALONE["w32c4"])
    cfg, n_rows = eng.cfg, len(prompt) + len(out) - 1
    layers = tuple(range(cfg.n_layers))
    kw = dict(zero_col=EOS, layers=layers, q_block=32,
              logits_from=len(prompt) - 1)
    seq = prompt + out[:-1]
    if "pad" in variant:   # only while no query sees the padded pairs
        seq, n_rows = seq[:31], 31
    want = R.forward(SEED, cfg, seq, **kw)
    wrong = R.forward(SEED, cfg, seq, variant=variant, **kw)
    rows, (kh, vh) = _left(eng, drawn, len(prompt) + len(out) - 1)
    last, n_chunks = cfg.n_layers - 1, n_rows // cfg.chunk_size
    at = [p for p in sorted(rows) if p < n_rows]   # none of a sequence cut short

    def errors(k_rows, pairs_k, pairs_v, logits):
        return max(([rel(k_rows, want["k"][last][at])] if at else []) + [
            rel(pairs_v, want["vh"][last][:n_chunks]),
            rel(logits, want["logits"][:, 0])] + [
            rel(pairs_k[c], want["kh"][last][c]) for c in range(n_chunks)])

    mine = errors(np.stack([rows[p][0][last] for p in at] or [0]),
                  kh[last, :n_chunks], vh[last, :n_chunks], want["logits"][:, 0])
    theirs = errors(wrong["k"][last][at], wrong["kh"][last][:n_chunks],
                    wrong["vh"][last][:n_chunks], wrong["logits"][:, 0])
    assert mine < 1e-5 < 1e-3 < theirs


def test_bf16_stays_within_its_tolerance():
    """The model's own dtype: bf16 weights, pools and matmuls under a float32
    residual, float32 softmax, pooling and head."""
    cfg = dataclasses.replace(SIZES["w32c4"][0], dtype="bfloat16")
    eng, drawn, prompt, out = _alone("w32c4", *ALONE["w32c4"], cfg=cfg)
    n_rows = len(prompt) + len(out) - 1
    want = R.forward(SEED, cfg, prompt + out[:-1], zero_col=EOS,
                     layers=(0, 1), logits_from=len(prompt) - 1, q_block=32)
    rows, (kh, vh) = _left(eng, drawn, n_rows)
    at = sorted(rows)
    assert eng.cache[0].dtype == jnp.bfloat16
    assert rel(np.stack([rows[p][0][0] for p in at]), want["k"][0][at]) < 0.01
    assert rel(kh[0], want["kh"][0][:kh.shape[1]]) < 0.01
    assert rel(np.stack([rows[p][1][1] for p in at]), want["v"][1][at]) < 0.04
    assert rel(vh[1], want["vh"][1][:kh.shape[1]]) < 0.04
    logits = np.asarray(want["logits"])[:, 0]
    gap = (logits.max(-1) - logits[np.arange(len(out)), out]) / logits.std(-1)
    assert float(np.median(gap)) == 0.0 and float(gap.max()) < 0.5


# ------------------------------------------------------------- the allocator
def _published_kinds():
    """An engine of tiny widths with the PUBLISHED window, chunk and page."""
    cfg = EvaConfig.tiny(window_size=2048, chunk_size=16, max_seq_len=8192)
    params = eva_init(jax.random.PRNGKey(0), cfg)
    return ContinuousBatchingEngine(
        params, cfg, max_batch=2, page_size=16, max_seq_len=8192,
        n_pages={"window": 140, "summary": 40}, eos_id=None)


def _grown(before, after, name, tag=""):
    return (after[name][tag]["sum"]
            - before.get(name, {}).get(tag, {"sum": 0})["sum"])


def test_pages_of_a_strided_kind_and_rows_within_an_aligned_reach():
    eng = _published_kinds()
    window, summary = eng.kinds
    assert (window.name, window.table, window.reach, window.stride,
            window.aligned) == ("window", 128, 2048, 1, True)
    assert (summary.name, summary.table, summary.reach, summary.stride,
            summary.aligned) == ("summary", 32, 2048, 16, True)
    hand = {1: ([1, 1], 1, 0), 2047: ([128, 8], 2047, 0),
            2048: ([128, 8], 2048, 0), 2049: ([128, 9], 1, 128),
            5000: ([128, 20], 904, 256)}
    for n, (pages, rows_w, rows_s) in hand.items():
        assert eng._pages_of(n) == pages, n
        lens = np.array([[n]])
        assert int(eng._rows_within(window, lens)) == rows_w, n
        assert int(eng._rows_within(summary, lens)) == rows_s, n
    # the kinds that were there read as they did
    from ray_tpu.llm.programs import PageKind
    lens = np.array([[5, 40, 4100]])
    assert eng._rows_within(PageKind("kv", 1, 9), lens).tolist() == [[5, 40, 4100]]
    assert eng._rows_within(PageKind("w", 1, 9, reach=32), lens).tolist() == [
        [5, 32, 32]]


@pytest.mark.parametrize("n,live,read", [
    (1, (1, 0), (16, 0)), (2047, (2047, 0), (2048, 0)),
    (2048, (2048, 0), (2048, 0)), (2049, (1, 128), (16, 128)),
    (5000, (904, 256), (912, 256))])
def test_the_tagged_read_counters_count_rows_attended_and_fetched(n, live, read):
    """A step at position n - 1, in place: the window kind attends the rows
    since the window's start and fetches their pages; the summary kind
    attends one row a chunk of the whole windows before it."""
    eng = _published_kinds()
    eng._kv_in_place = True

    class Req:
        prompt, emitted = [0] * (n - 1), 1

    eng.slot_req[0], eng.seq_lens[0] = Req, n - 1
    before = metrics.stage_totals()
    eng._observe_kv_reads(1, [Req, None])
    after = metrics.stage_totals()
    for kind, want_live, want_read in zip(("window", "summary"), live, read):
        assert _grown(before, after, "rt_llm_decode_kv_tokens_live_total",
                      kind) == want_live
        assert _grown(before, after, "rt_llm_decode_kv_tokens_read_total",
                      kind) == want_read
    assert _grown(before, after, "rt_llm_decode_kv_tokens_live_total"
                  ) == pytest.approx(sum(live) / 2)


def _held():
    g = metrics.stage_totals()["rt_llm_pages_held"]
    return {k: v["sum"] for k, v in g.items() if k in ("window", "summary")}


def test_both_kinds_pages_are_counted_drawn_held_and_given_back():
    before = metrics.stage_totals()
    eng, drawn, prompt, out = _alone("w32c4", *ALONE["w32c4"])
    after = metrics.stage_totals()
    n = len(prompt) + len(out)                       # 68 positions asked for
    assert [len(d) for d in drawn] == [4, 3] == [32 // 8, -(-n // 32)]
    assert _grown(before, after, "rt_llm_pages_drawn_total", "window") == 4
    assert _grown(before, after, "rt_llm_pages_drawn_total", "summary") == 3
    # the ring wrote over the pages of the windows it dropped: 9 reached, 4 held
    assert _grown(before, after, "rt_llm_window_pages_released_total") == 5
    assert _held() == {"window": 0, "summary": 0}
    assert [len(f) for f in eng.free] == [12, 12]
    # pairs decode wrote: the chunks complete at the end less the prompt's, a layer
    pairs = ((n - 1) // 4 - len(prompt) // 4) * eng.cfg.n_layers
    assert _grown(before, after, "rt_llm_eva_pairs_written_total") == pairs == 20


def test_admission_waits_for_whichever_kind_runs_out_and_starves_nobody():
    """Too few pages of PAIRS for every request at once: the head of the
    queue waits for its pages, the ones behind it wait for it, and every
    request finishes with the reference's tokens."""
    eng = _engine("w32c4", n_pages={"window": 13, "summary": 5})
    with pytest.raises(ValueError, match="'summary' kind"):
        _engine("w32c4", n_pages={"window": 13, "summary": 3}).submit(
            [1] * 90, max_tokens=10)
    peak = {}
    real = eng._count_pages

    def watch(i, drawn=0):
        real(i, drawn)
        name = eng.kinds[i].name
        peak[name] = max(peak.get(name, 0), eng.capacity[i] - len(eng.free[i]))

    eng._count_pages = watch
    cases = [(70, 12), (20, 4), (90, 10), (20, 4), (12, 4)]
    prompts, outs = _serve(eng, cases)
    assert [len(o) for o in outs] == [m for _, m in cases]
    assert float(_logit_gaps(eng.cfg, prompts, outs).max()) == 0.0
    assert 0 < peak["summary"] <= 4 and [len(f) for f in eng.free] == [12, 4]


def test_the_wave_limit_splits_the_cells_pads():
    eng = _published_kinds()
    assert serving_programs(eng.cfg).prefill_wave_limit == (8, 16384)
    assert serving_programs(eng.cfg).stats == ("eva_pairs",)
    for pad, waves in ((4608, [2, 2, 2, 2]), (7680, [2, 2, 2, 2]),
                       (12288, [1] * 8), (15360, [1] * 8), (512, [8])):
        assert [len(w) for w in eng._split_wave(pad, [None] * 8)] == waves


# ---------------------------------------------------------------- the kernels
def _two_tables(rng, B, H, hd, PS, P, W):
    entries = W // PS
    pools = [jnp.asarray(rng.normal(size=(2, P, PS, H, hd)), jnp.float32)
             for _ in range(4)]
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    ids = rng.permutation(np.arange(1, P))
    t_win = jnp.asarray(ids[:B * entries].reshape(B, entries), jnp.int32)
    t_sum = jnp.asarray(rng.permutation(np.arange(1, P))[:B * 4].reshape(B, 4),
                        jnp.int32)
    return q, pools, t_win, t_sum


@pytest.mark.parametrize("H", [4, 32])
def test_two_walks_joined_are_one_softmax_over_both_tables(H):
    """The window's walk from its aligned start and the plain walk of the
    pairs, each given out with its maximum and sum and merged, in the
    interpreter, against a dense softmax over the union of their rows: slots
    inside their first window (no pair), windows on, at a window's first
    position, and an inactive one; 4 heads, and 32 KV heads at G = 1."""
    hd, PS, P, Wn, C = 128, 8, 48, 64, 8
    rng = np.random.default_rng(H)
    pos = np.array([5, 2 * Wn + 17, 0, Wn, 3 * Wn + 63], np.int32)
    active = np.array([True, True, False, True, True])
    B = len(pos)
    q, (kw, vw, ks, vs), t_win, t_sum = _two_tables(rng, B, H, hd, PS, P, Wn)
    lengths = jnp.asarray(np.where(active, pos + 1, 0))
    starts = jnp.asarray(pos // Wn * Wn)
    n_pairs = jnp.asarray(np.where(active, pos // Wn * (Wn // C), 0))
    a = paged_attention_part(q, kw, vw, 1, t_win, lengths, starts=starts,
                             interpret=True)
    b = paged_attention_part(q, ks, vs, 1, t_sum, n_pairs, interpret=True)
    assert a[1].shape == a[2].shape == (B, H) and a[0].dtype == jnp.float32
    got = np.asarray(merge_attention_parts(a, b))
    assert not got[2].any() and not float(b[2][0].max())   # no row: l = 0
    entries = Wn // PS
    for s in np.flatnonzero(active):
        at = np.arange(int(starts[s]), int(lengths[s]))
        c = np.arange(int(n_pairs[s]))
        k = np.concatenate([np.asarray(kw)[1, t_win[s, at // PS % entries], at % PS],
                            np.asarray(ks)[1, t_sum[s, c // PS], c % PS]])
        v = np.concatenate([np.asarray(vw)[1, t_win[s, at // PS % entries], at % PS],
                            np.asarray(vs)[1, t_sum[s, c // PS], c % PS]])
        sc = np.einsum("hd,nhd->hn", np.asarray(q)[s], k) / np.sqrt(hd)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        want = np.einsum("hn,nhd->hd", p / p.sum(-1, keepdims=True), v)
        assert float(np.abs(got[s] - want).max()) < 2e-5, s


def test_paged_decode_attention_at_as_many_kv_heads_as_query_heads():
    """G = 1 at 32 KV heads, the plain walk that was there, in the
    interpreter: no cell had run it."""
    H, hd, PS = 32, 128, 16
    rng = np.random.default_rng(0)
    kp, vp = (jnp.asarray(rng.normal(size=(1, 12, PS, H, hd)), jnp.float32)
              for _ in range(2))
    q = jnp.asarray(rng.normal(size=(3, H, hd)), jnp.float32)
    tab = jnp.asarray([[3, 5, 7, 1], [2, 4, 6, 8], [9, 10, 11, 0]], jnp.int32)
    lens = jnp.asarray([37, 64, 0], jnp.int32)
    got = np.asarray(paged_decode_attention(q, kp, vp, 0, tab, lens,
                                            interpret=True))
    for s in range(2):
        k = np.asarray(kp)[0][tab[s]].reshape(-1, H, hd)[:int(lens[s])]
        v = np.asarray(vp)[0][tab[s]].reshape(-1, H, hd)[:int(lens[s])]
        sc = np.einsum("hd,nhd->hn", np.asarray(q)[s], k) / np.sqrt(hd)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        want = np.einsum("hn,nhd->hd", p / p.sum(-1, keepdims=True), v)
        assert float(np.abs(got[s] - want).max()) < 2e-5
    assert not got[2].any()


@pytest.mark.parametrize("T,Wn,C", [(512, 256, 16), (1024, 512, 16),
                                    (768, 128, 8)])
def test_blocked_prefill_attention_over_rows_and_pairs_matches_the_plain_form(
        T, Wn, C):
    from ray_tpu.models.eva import eva_attend_plain, eva_pairs_seen, eva_reach

    N, H, hd = 2, 2, 128
    cfg = EvaConfig.tiny(window_size=Wn, chunk_size=C, max_seq_len=T)
    assert eva_blocks_for(T, Wn) is not None
    assert eva_blocks_for(T, 96) is None and eva_blocks_for(200, Wn) is None
    ks = jax.random.split(jax.random.PRNGKey(T), 5)
    q, k, v = (jax.random.normal(ks[i], (N, T, H, hd)) for i in range(3))
    kh, vh = (jax.random.normal(ks[i], (N, T // C, H, hd)) for i in (3, 4))
    idx = jnp.arange(T)
    mask = jnp.broadcast_to(eva_reach(idx[:, None], idx[None, :], cfg), (N, T, T))
    seen = jnp.broadcast_to(
        jnp.arange(T // C)[None, :] < eva_pairs_seen(idx, cfg)[:, None],
        (N, T, T // C))
    want = eva_attend_plain(q, k, v, kh, vh, mask, seen)
    got = eva_prefill_attention(
        *(a.reshape(N, a.shape[1], -1) for a in (q, k, v, kh, vh)), n_heads=H,
        window=Wn, chunk=C, interpret=True)
    assert float(jnp.abs(got - want).max()) < 2e-5


# ---------------------------------------------------------------- refusals
@pytest.mark.parametrize("feature,make", [
    ("kv_dtype='int8'", lambda: _engine(kv_dtype="int8")),
    ("lora_adapters", lambda: _engine(lora_adapters={"a": {}})),
    ("spec_enable", lambda: _engine(spec_enable=True)),
    ("export_pages", lambda: _engine().export_pages(1)),
    ("submit_prefilled", lambda: _engine().submit_prefilled([1], None, None, 3)),
    ("a K or V pool", lambda: _engine().kpool),
])
def test_what_takes_a_prefix_of_pages_for_a_prefix_of_the_sequence_is_refused(
        feature, make):
    with pytest.raises(UnsupportedByModel, match=feature.split("(")[0]) as e:
        make()
    assert "'eva'" in str(e.value)
    assert "no prefix of the sequence" in str(e.value)   # what it caches instead


def test_pages_must_be_whole_chunks_that_fill_a_window():
    cfg, _ = SIZES["w32c4"]
    for page in (6, 12):      # no whole chunks; no whole window
        with pytest.raises(ValueError, match="whole chunks"):
            _engine(page_size=page)
    assert serving_programs(cfg).family == "eva"

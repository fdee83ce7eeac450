"""The learned-sparse-attention expert family (``models/sparse_moe.py``,
``llm/sparse_moe.py``, ``ops/select.py``, ``ops/paged_indexer.py``, the
``selected`` form of ``ops/paged_attention.py``, the ``picked`` form of
``ops/prefill_attention.py``, ``parallel/moe.py``'s softmax router) against
the benchmark's plain float32 reference (``benchmarks/reference/
sparse_moe.py``), at a tiny size that keeps the published shape's ratios:
eight query heads a KV head, an indexer of its own width, a ``topk`` (16) far
under the context, no shared expert. CPU, float32, seeded weights."""
import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import weights_sparse_moe as W
from benchmarks.reference import sparse_moe as R
from ray_tpu.llm import sparse_moe as programs
from ray_tpu.llm.engine import (ContinuousBatchingEngine, UnsupportedByModel,
                                serving_programs)
from ray_tpu.models.sparse_moe import (SparseMoeConfig, sparse_moe_forward,
                                       sparse_moe_init)
from ray_tpu.ops import paged_attention, paged_indexer, prefill_picks, select
from ray_tpu.ops.attention import masked_attention
from ray_tpu.ops.paged_attention import paged_decode_attention
from ray_tpu.ops.paged_indexer import (index_runs, pack_keys,
                                       paged_index_scores, table_runs,
                                       unpack_keys)
from ray_tpu.ops.prefill_attention import gqa_prefill_attention
from ray_tpu.ops.select import topk_mask
from ray_tpu.parallel.moe import routed_experts, softmax_topk_route
from ray_tpu.utils import metrics

CFG = SparseMoeConfig.tiny(experts_held=(4, 12), vocab_held=(256, 512))
PS = 8
SEEDS = [3, 2**31 + 7]


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def test_tiny_keeps_the_published_shape():
    full = SparseMoeConfig()
    assert full.n_heads // full.n_kv_heads == CFG.n_heads // CFG.n_kv_heads == 8
    assert (full.indexer_heads, full.indexer_head_dim, full.topk) == (16, 64, 2048)
    assert CFG.held == (4, 12) and CFG.vocab_size == 256 and CFG.topk == 16
    with pytest.raises(ValueError, match="one key head"):
        SparseMoeConfig.tiny(indexer_kv_heads=2)
    params = sparse_moe_init(jax.random.PRNGKey(0), CFG)
    seeded = W.make_params(W.seed_key(0), CFG)
    assert (jax.tree.map(lambda x: (x.shape, x.dtype), params)
            == jax.tree.map(lambda x: (x.shape, x.dtype), seeded))
    assert "shared" not in params["layers_0"]["moe"]
    assert serving_programs(CFG) is programs.PROGRAMS
    assert programs.PROGRAMS.page_kinds is None  # three pools, ONE kind of page


# ------------------------------------------------- the engine and the reference
def _engine(seed=5, cfg=CFG, **kw):
    params = W.make_params(W.seed_key(seed), cfg)
    kw = {"max_batch": 3, "page_size": PS, "max_seq_len": 96, "n_pages": 41,
          "eos_id": None, "block_buckets": (4, 8), **kw}
    return ContinuousBatchingEngine(params, cfg, **kw)


# prompts on both sides of the topk of 16; the first request's decode steps
# cross it, and page boundaries
CASES = [(10, 12), (40, 10), (15, 3)]


def _serve(eng, cases, seed=0):
    async def run():
        await eng.start()
        rng = np.random.default_rng(seed)
        prompts = [rng.integers(3, CFG.vocab_size, n).tolist() for n, _ in cases]
        outs = await asyncio.wait_for(asyncio.gather(*(
            eng.generate(p, max_tokens=m) for p, (_, m) in zip(prompts, cases))),
            timeout=240)
        await eng.stop()
        return prompts, outs

    return asyncio.run(run())


def _logit_gaps(seed, cfg, prompts, outs, **ref_kw):
    """For each request, the reference's best logit less its logit for the
    token the program emitted, at every position, in logit spreads: zeros
    where the program's tokens are the reference's own."""
    gaps = []
    for p, o in zip(prompts, outs):
        logits = np.asarray(R.forward(seed, cfg, p + o[:-1],
                                      logits_from=len(p) - 1, q_block=32,
                                      **ref_kw)["logits"])
        gaps.append((logits.max(-1) - logits[np.arange(len(o)), o])
                    / logits.std(-1))
    return np.concatenate(gaps)


@pytest.fixture(scope="module")
def served():
    return _serve(_engine(), CASES)


@pytest.mark.parametrize("eos_id", [None, 300])  # the planned, the reactive loop
def test_prefill_then_decode_through_the_three_pools_is_the_reference(eos_id):
    eng = _engine(eos_id=eos_id)
    prompts, outs = _serve(eng, CASES)
    assert [len(o) for o in outs] == [m for _, m in CASES]
    assert float(_logit_gaps(5, CFG, prompts, outs).max()) == 0.0
    assert len(eng.free[0]) == 40   # every page back: one list serves all three


@pytest.mark.parametrize("n,m", [(10, 12), (40, 10)])
def test_the_pools_hold_the_references_rows_on_both_sides_of_topk(n, m):
    """K, V and the indexer's keys (unpacked from their 128-lane rows) of
    every layer, prompt and decoded positions, under topk and past it."""
    eng = _engine()
    prompt = np.random.default_rng(1).integers(3, CFG.vocab_size, n).tolist()
    drawn = jnp.asarray(eng.free[0][:eng._pages_of(n + m)[0]])

    async def run():
        await eng.start()
        out = await asyncio.wait_for(eng.generate(prompt, max_tokens=m), 240)
        await eng.stop()
        return out

    out = asyncio.run(run())
    rows = n + m - 1
    want = R.forward(5, CFG, prompt + out[:-1], q_block=32)
    kpool, vpool, ipool = eng.cache
    assert ipool.shape == (3, 41, 1, 128)   # 8 keys of 16 lanes a row
    for name, pool in (("k", kpool[:, drawn]), ("v", vpool[:, drawn]), (
            "ki", unpack_keys(ipool[:, drawn], CFG.indexer_head_dim))):
        got = pool.reshape(CFG.n_layers, -1, want[name].shape[-1])
        assert rel(got[:, :rows], want[name][:, :rows]) < 1e-5, name
    assert np.asarray(want["attended"])[0].tolist() == [
        min(t + 1, CFG.topk) for t in range(rows)]


def test_a_slot_within_topk_is_plain_gqa_attention_to_the_bit():
    """While a slot holds at most ``topk`` positions the selection takes
    every one, and the program is the dense path bit for bit: the same
    request under a ``topk`` that never binds leaves the same tokens and the
    same pool rows."""
    case = [(9, 7)]   # 16 positions at the last step: exactly topk
    dense = dataclasses.replace(CFG, topk=4096)
    left = {}
    for name, cfg in (("picks", CFG), ("dense", dense)):
        eng = _engine(cfg=cfg)
        drawn = jnp.asarray(eng.free[0][:2])
        _, outs = _serve(eng, case)
        left[name] = (outs, [np.asarray(p[:, drawn]) for p in eng.cache])
    assert left["picks"][0] == left["dense"][0]
    for a, b in zip(left["picks"][1], left["dense"][1]):
        assert np.array_equal(a, b)
    # and one position more is another model: the controls below say so


@pytest.mark.parametrize("variant", [
    {"select": "none"}, {"topk": 8}, {"select": "recent"}, {"parallel": True},
    {"shared": True}, {"router": "sigmoid"}])
def test_a_reference_with_other_mathematics_fails_the_comparison(served, variant):
    """The controls: no selection, half the topk, the most recent topk
    positions instead of the learned pick, the expert half reading the
    layer's input (a parallel block), a shared expert added, a sigmoid
    router — each is a forward pass the program's tokens are not the greedy
    tokens of, by a wide margin."""
    prompts, outs = served
    assert float(_logit_gaps(5, CFG, prompts, outs).max()) == 0.0
    assert float(_logit_gaps(5, CFG, prompts, outs, variant=variant).max()) > 0.05


def test_bf16_programs_stay_within_a_stated_tolerance():
    """The same comparison in the type the cell serves. Near-tied picks and
    expert choices flip between bf16 and float32, so tokens are held to a
    fraction of a logit spread and the logits to 10 %."""
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    eng = _engine(cfg=cfg)
    prompts, outs = _serve(eng, CASES[1:2])
    gaps = _logit_gaps(5, cfg, prompts, outs)
    assert float(np.percentile(gaps, 50)) == 0.0 and float(gaps.max()) < 0.5
    low = R.forward(5, cfg, prompts[0] + outs[0][:-1], q_block=32)
    want = sparse_moe_forward(W.make_params(W.seed_key(5), cfg),
                              jnp.asarray([prompts[0] + outs[0][:-1]]), cfg)
    assert rel(want[0].astype(jnp.float32), low["logits"]) < 0.1


@pytest.mark.parametrize("seed", SEEDS)
def test_forward_logits_match_the_plain_reference(seed):
    params = W.make_params(W.seed_key(seed), CFG)
    tokens = np.random.default_rng(seed % 1000).integers(3, CFG.vocab_size, 70)
    want = R.forward(seed, CFG, tokens, q_block=32)
    got = sparse_moe_forward(params, jnp.asarray(tokens)[None], CFG)[0]
    assert rel(got, want["logits"]) < 1e-5
    assert want["chosen"].shape == (3, 70, CFG.n_experts_per_tok)


# -------------------------------------------------------------- the selection
def _top_k_mask(scores, valid, k):
    _, idx = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf),
                           min(k, scores.shape[-1]))
    want = np.zeros(scores.shape, bool)
    np.put_along_axis(want, np.asarray(idx), True, axis=-1)
    return want & np.asarray(valid)


@pytest.mark.parametrize("S,k", [(50, 8), (300, 64), (40, 64)])
def test_the_selected_set_is_lax_top_ks_with_ties_to_the_lower_position(S, k):
    rng = np.random.default_rng(S)
    s = rng.standard_normal((3, 5, S)).astype(np.float32)
    s[0, 0, :] = 0.0                       # a row of zeros: the first k
    s[0, 1, ::3] = 9.0                     # planted exact ties above the rest
    s[1, 2, :10], s[1, 2, 10:20] = -0.0, 0.0   # signed zeros are one value
    s[2, 1, :] = np.float32(-3e38)
    valid = rng.random((3, 5, S)) < 0.8
    valid[2, 0, :] = False                 # nothing to pick from
    got = np.asarray(jax.jit(topk_mask, static_argnums=2)(s, valid, k))
    assert np.array_equal(got, _top_k_mask(jnp.asarray(s), jnp.asarray(valid), k))
    assert np.array_equal(got.sum(-1), np.minimum(k, valid.sum(-1)))
    every = np.ones_like(valid)
    first = np.asarray(topk_mask(jnp.asarray(s), jnp.asarray(every), k))[0, 0]
    assert first[:k].all() and not first[k:].any()
    ties = np.asarray(topk_mask(jnp.asarray(s), jnp.asarray(every), 5))[0, 1]
    assert np.flatnonzero(ties).tolist() == [0, 3, 6, 9, 12]


@pytest.mark.parametrize("R,S,k,dtype", [(5, 256, 16, jnp.float32),
                                         (40, 384, 64, jnp.int8),
                                         (3, 128, 200, jnp.float32)])
def test_the_selection_kernel_is_the_plain_form(R, S, k, dtype):
    """``topk_prefix_mask``'s kernel in the interpreter against ``topk_mask``
    over the same prefix of candidates: planted ties, a row of zeros, signed
    zeros, rows with no candidate, fewer candidates than k, row counts that
    are not whole tiles."""
    from ray_tpu.ops.select import _topk_prefix_mask, topk_prefix_mask

    rng = np.random.default_rng(S)
    s = rng.standard_normal((R, S)).astype(np.float32)
    s[0, :] = 0.0
    s[1, ::3] = 9.0
    s[2, :10], s[2, 10:20] = -0.0, 0.0
    limit = rng.integers(-1, S, R).astype(np.int32)
    limit[0] = limit[1] = S - 1
    got = np.asarray(_topk_prefix_mask(jnp.asarray(s), jnp.asarray(limit), k=k,
                                       dtype=jnp.dtype(dtype), interpret=True))
    valid = np.arange(S)[None] <= limit[:, None]
    want = np.asarray(topk_mask(jnp.asarray(s), jnp.asarray(valid), k))
    assert got.dtype == np.dtype(dtype)
    assert np.array_equal(got.astype(bool), want)
    assert np.array_equal(got.sum(-1), np.minimum(k, valid.sum(-1)))
    # off the TPU the entry is the plain form itself
    plain = topk_prefix_mask(jnp.asarray(s), jnp.asarray(limit), k, dtype)
    assert np.array_equal(np.asarray(plain), got)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8])
@pytest.mark.parametrize("limits", [
    "none", "first", "last", "mixed", "short"])
def test_the_bounded_selection_walks_to_the_tiles_longest_row(limits, dtype):
    """The kernel's passes stop at the block of a tile's largest limit: rows
    with no candidate (-1), one (0), all (S - 1), mixed limits in one tile
    and a tile that walks 2 of its 5 blocks — the plain form's picks in every
    case, and whatever lies past the bound (huge, of either sign) changes
    nothing and reads 0."""
    from ray_tpu.ops.select import _topk_prefix_mask

    S, k = 640, 48                      # five blocks of 128 columns
    rows = 2 * select._TILE + 3
    rng = np.random.default_rng(11)
    s = rng.standard_normal((rows, S)).astype(np.float32)
    s[1, ::2] = 1.5                     # ties at the k-th across block edges
    limit = {"none": np.full(rows, -1), "first": np.zeros(rows),
             "last": np.full(rows, S - 1),
             "mixed": rng.integers(-1, S, rows),
             "short": rng.integers(60, 250, rows)}[limits].astype(np.int32)
    if limits == "mixed":
        limit[:4] = [-1, 0, S - 1, 127]
    valid = np.arange(S)[None] <= limit[:, None]
    want = np.asarray(topk_mask(jnp.asarray(s), jnp.asarray(valid), k))
    wild = np.where(np.arange(S)[None] > limit[:, None],
                    np.where(rng.random((rows, S)) < 0.5, 3e38, -3e38), s
                    ).astype(np.float32)
    for scores in (s, wild):
        got = np.asarray(_topk_prefix_mask(
            jnp.asarray(scores), jnp.asarray(limit), k=k,
            dtype=jnp.dtype(dtype), interpret=True))
        assert got.dtype == np.dtype(dtype)
        assert np.array_equal(got.astype(bool), want)


def test_columns_walked_against_a_hand_count(monkeypatch):
    """The one rule of the kernels' trip counts. Prefill, tiles of 32 queries
    and blocks of 128 keys under a topk of 48 over 256 positions: the first
    tile (ends at 31) walks nothing, the three ending at 63, 95, 127 one
    block, the four after them two. At the cell's sizes in the issue's
    tiling (512 x 512): 26 of 64 and 396 of 784 blocks of the square. Decode:
    a row walks to its tile's largest limit, a tile with no live row
    nothing."""
    assert [select.walk_blocks(e, 128, 48) for e in (31, 47, 48, 127, 128)
            ] == [0, 0, 1, 1, 2]
    assert prefill_picks.columns_walked(256, 48, 32, 128) == (
        3 * 32 * 128 + 4 * 32 * 256)
    assert prefill_picks.columns_walked(4096, 2048, 512, 512) == 26 * 512 * 512
    assert prefill_picks.columns_walked(14336, 2048, 512, 512) == (
        396 * 512 * 512)
    # at the kernel's own tiling a 14,336-token prompt walks half the square
    assert prefill_picks.picks_block(14336) == 512
    assert prefill_picks.columns_walked(14336, 2048) / 14336 ** 2 == (
        pytest.approx(0.506, abs=1e-3))
    assert prefill_picks.picks_block(40) is None    # not whole tiles
    assert prefill_picks.picks_block(1024 + 64) is None
    limit = jnp.asarray([-1] * 32 + [5, 300, -1, 1023] + [9] * 28
                        + [1024] + [-1] * 31 + [4095] * 3)
    # off the TPU the plain form walks the whole width of every row
    assert int(select.prefix_walked(limit, 4096)) == 99 * 4096
    monkeypatch.setattr(select, "_selects_in_kernel", lambda width: True)
    assert (select._TILE, select._block_for(4096)) == (32, 1024)
    assert int(select.prefix_walked(limit, 4096)) == (
        0 + 32 * 1024 + 32 * 2048 + 3 * 4096)


def _exact_indexer_inputs(rng, N, T, J=4, dk=16):
    """Inputs whose scores are small dyadic numbers: every order of the sum
    over the heads gives the same float32, and many scores tie."""
    qi = rng.integers(-2, 3, (N, T, J, dk)).astype(np.float32)
    ki = rng.integers(-2, 3, (N, T, dk)).astype(np.float32)
    w = (rng.integers(-4, 5, (N, T, J)) / 4).astype(np.float32)
    return jnp.asarray(qi), jnp.asarray(w), jnp.asarray(ki)


@pytest.mark.parametrize("N,T,k,block,rows", [
    (1, 256, 48, 128, 128),   # the first tile straddles topk; two blocks
    (1, 256, 48, 128, 32),    # tiles of 32: one under topk, one straddling
    (3, 128, 16, 128, 32),    # three prompts, every tile past topk
    (1, 128, 200, 128, 128),  # a prompt under topk: "all it sees", unscored
    (3, 256, 64, 128, 128),   # ties at the k-th value across the block edge
    (1, 384, 100, 128, 128),  # three blocks, the tiles walk one, two, three
])
def test_the_prefill_picks_kernel_is_the_plain_form_bit_for_bit(
        N, T, k, block, rows):
    """``ops/prefill_picks.py`` in the interpreter against the plain
    ``_prefill_picks`` (scored a block of queries at a time against all keys,
    selected by ``topk_mask``) on inputs whose float32 scores are the same in
    any order of the sum: the same bytes."""
    cfg = dataclasses.replace(CFG, topk=k, q_chunk=32)
    qi, w, ki = _exact_indexer_inputs(np.random.default_rng(T + k), N, T)
    want = np.asarray(programs._prefill_picks(qi, w, ki, cfg))
    got = np.asarray(prefill_picks._prefill_picks(
        qi, w, ki, k=k, block=block, interpret=True, rows=rows))
    assert got.dtype == np.int8 and got.shape == (N, T, T)
    assert np.array_equal(got, want)
    t = np.arange(T)
    assert np.array_equal(got.sum(-1), np.broadcast_to(
        np.minimum(t + 1, k), (N, T)))
    if k == 64:
        # the case is what it says: some query's k-th value has equal entries
        # on both sides of column 128, and not all of them are picks
        from ray_tpu.models.sparse_moe import indexer_scores
        sc = np.asarray(indexer_scores(qi, w, ki))
        cut = False
        for n, q in [(n, q) for n in range(N) for q in range(200, T, 7)]:
            row = sc[n, q, :q + 1]
            kth = np.sort(row)[-k]
            eq = np.flatnonzero(row == kth)
            cut |= bool(eq.min() < 128 <= eq.max()
                        and not got[n, q, eq].all() and got[n, q, eq].any())
        assert cut


def test_prefill_takes_the_kernel_for_whole_tiles_and_the_plain_form_else(
        monkeypatch):
    """What ``_prefill_picks`` runs is decided by what it sees: the kernel
    where the programs read in place and the prompt is whole tiles, the
    plain form for a prompt that is not (40 positions) — equal bytes."""
    cfg = dataclasses.replace(CFG, topk=16, q_chunk=8)
    calls = []
    real = prefill_picks.prefill_picks
    monkeypatch.setattr(programs, "prefill_picks", lambda *a, **kw: (
        calls.append(a[2].shape), real(*a, **kw))[1])
    for T, kernel in [(128, True), (40, False)]:
        qi, w, ki = _exact_indexer_inputs(np.random.default_rng(T), 2, T)
        want = np.asarray(programs._prefill_picks(qi, w, ki, cfg))
        assert not calls
        monkeypatch.setattr(programs, "_reads_in_place", lambda: True)
        got = np.asarray(programs._prefill_picks(qi, w, ki, cfg))
        monkeypatch.setattr(programs, "_reads_in_place", lambda: False)
        assert np.array_equal(got, want)
        assert bool(calls) == kernel
        calls.clear()


# ---------------------------------------------------------------- the share
@pytest.mark.parametrize("holders", [8, 2])
def test_holders_parts_add_up_to_the_uncut_layer(holders):
    """The chip's share of a deployment (model-configs guide, section 4): the
    16 experts of a layer divided over ``holders``; each routes over all of
    them and computes its own experts' part. There is no shared expert to
    count once: the parts alone are the uncut reference's layer output."""
    whole = dataclasses.replace(CFG, experts_held=None)
    key = W.layer_key(W.seed_key(5), 1)
    full = W.layer_from_seed(W.seed_key(5), whole, 1)["moe"]
    h = jax.random.normal(jax.random.PRNGKey(1), (37, CFG.d_model))
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), full)
    want, _ = R.moe(f32, h, whole, "float32")
    per = CFG.n_experts // holders
    total, loads = 0.0, []
    for r in range(holders):
        cfg = dataclasses.replace(CFG, experts_held=(r * per, (r + 1) * per))
        mine = {n: W.expert_stack(key, cfg, i)
                for i, n in enumerate(("w_gate", "w_up", "w_down"))}
        assert jnp.array_equal(mine["w_up"],
                               full["experts"]["w_up"][r * per:(r + 1) * per])
        idx, w = softmax_topk_route(h, full["router"]["kernel"],
                                    cfg.n_experts_per_tok)
        assert float(jnp.abs(w.sum(-1) - 1).max()) < 1e-6
        part, load = routed_experts(h, idx, w, mine, cfg.held)
        total = total + part
        loads.append(load)
    assert rel(total, want) < 1e-5
    assert int(jnp.concatenate(loads).sum()) == h.shape[0] * CFG.n_experts_per_tok


# ------------------------------------------------------ the allocator, the wave
def test_long_and_short_requests_in_one_queue_admit_in_order():
    """Too few pages for all at once: the head of the queue waits for its
    pages, the ones behind it wait for it, every request finishes with the
    reference's tokens and every page comes back."""
    eng = _engine(n_pages=13)   # 12 pages: the 70-position request takes 9
    order = []
    real = eng._reserve_slot

    def watch(req):
        slot = real(req)
        if slot is not None:
            order.append(len(req.prompt))
        return slot

    eng._reserve_slot = watch
    cases = [(60, 10), (12, 4), (40, 8), (10, 4)]
    prompts, outs = _serve(eng, cases)
    assert [len(o) for o in outs] == [m for _, m in cases]
    assert float(_logit_gaps(5, CFG, prompts, outs).max()) == 0.0
    assert order == [60, 12, 40, 10] and len(eng.free[0]) == 12


def test_the_wave_limit_is_the_familys_and_splits_a_group():
    eng = _engine(max_batch=8, n_pages=200)
    assert eng.programs.prefill_wave_limit == (8, 16384)
    assert [len(w) for w in eng._split_wave(4096, [0] * 7)] == [4, 3]
    assert [len(w) for w in eng._split_wave(8192, [0] * 3)] == [2, 1]
    assert [len(w) for w in eng._split_wave(14336, [0] * 2)] == [1, 1]
    assert [len(w) for w in eng._split_wave(1024, [0] * 8)] == [8]


# ---------------------------------------------------------------- the counters
def test_the_stats_columns_and_read_counters_against_a_hand_count():
    """A request of 20 + 13 tokens: 12 decode steps (blocks 8 + 4) at
    lengths 21..32 in 3 layers. Scored: every position; attended: 16 of
    them; fetched (the gathered form off the TPU): every slot's whole table
    a step; the selection's passes (the plain form off the TPU) walk the
    whole width of every slot's table."""
    eng = _engine()
    assert eng.programs.stats[-7:] == programs.SPARSE_STATS
    before = metrics.stage_totals()
    _serve(eng, [(20, 13)])
    after = metrics.stage_totals()

    def grown(name):
        return _grown(before, after, name)

    L, steps = CFG.n_layers, 12
    assert grown("rt_llm_sparse_positions_scored_total") == L * sum(range(21, 33))
    assert grown("rt_llm_sparse_rows_attended_total") == L * steps * CFG.topk
    assert grown("rt_llm_sparse_kv_positions_fetched_total") == (
        L * steps * eng.B * eng.MAXP * PS)
    # the gathered form walks no pool: no blocks, none of them one copy
    assert grown("rt_llm_sparse_walk_blocks_total") == 0
    assert grown("rt_llm_sparse_walk_run_blocks_total") == 0
    assert grown("rt_llm_sparse_select_columns_walked_total") == (
        L * steps * eng.B * eng.MAXP * PS)
    assert grown("rt_llm_sparse_select_columns_width_total") == (
        L * steps * eng.B * eng.MAXP * PS)
    assert grown("rt_llm_moe_expert_slots_total") == L * steps * 8
    # what the engine reckons itself says the same: attended = selected rows
    assert grown("rt_llm_decode_kv_tokens_live_total") == steps * CFG.topk
    assert grown("rt_llm_decode_kv_tokens_read_total") == (
        steps * eng.B * eng.MAXP * PS)
    assert eng._last_kv["kv_live"] == CFG.topk
    assert {"sparse_scored", "sparse_attended", "moe_passes"} <= set(eng._last_stats)


# ---------------------------------------------------------------- the kernels
def _tables(rng, B, entries, pages, runs=False):
    """Page tables over distinct pages: scattered, or — as an allocator that
    draws from the front of a free list leaves them — runs of consecutive
    pages: True, one run a slot with a break in the middle of slot 1's
    second block; a number, runs of that many pages in a shuffled order (7:
    shorter than a sub-run of 8; 23: they end inside blocks and sub-runs)."""
    if not runs:
        return rng.permutation(np.arange(1, pages))[:B * entries].reshape(
            B, entries).astype(np.int32)
    t = (1 + np.arange(B * entries)).astype(np.int32)
    if runs is True:
        t = t.reshape(B, entries)
        t[1, entries // 2:] = t[1, entries // 2:][::-1]
        return t
    pieces = np.split(t, np.arange(runs, len(t), runs))
    return np.concatenate([pieces[i] for i in rng.permutation(len(pieces))]
                          ).reshape(B, entries)


def test_table_runs_against_a_hand_count():
    """Blocks of 4 entries, sub-runs of 2: bit 0 the block, bits 1 and 2 its
    halves. A run that ends inside a block leaves the half before the break;
    entries past the table break the last block."""
    t = jnp.asarray([[5, 6, 7, 8, 9, 10, 3, 4, 11, 12],
                     [1, 2, 4, 5, 0, 0, 0, 0, 20, 19]], jnp.int32)
    assert table_runs(t, 4, 2).tolist() == [[7, 6, 2], [6, 0, 0]]
    assert table_runs(t, 4).tolist() == [[3, 0, 0], [0, 0, 0]]
    assert table_runs(t[:, :8], 8).tolist() == [[0], [0]]
    assert table_runs(t[:1, :6], 6, 3).tolist() == [[7]]
    runs, n_pages = index_runs(t)  # a table under a block: the table, whole
    assert (runs.shape, n_pages) == ((2, 1), 10)


@pytest.mark.parametrize("runs,entries,lengths", [
    (False, 37, [5, 290, 0, 131]), (True, 37, [5, 290, 0, 131]),
    # three blocks of 64 pages, the last of 22: the largest table, an
    # inactive slot between live ones, a last block partly live (3 tokens
    # into the second, the whole first), exactly two blocks
    (True, 150, [5, 1200, 0, 515, 1024]),
    (23, 150, [5, 1200, 0, 515, 1024]), (7, 150, [700, 0, 1200])])
def test_paged_selected_attention_matches_a_dense_masked_softmax_at_g8(
        runs, entries, lengths):
    """The masked walk in the interpreter, 8 query heads a KV head: slots
    under a block, over one, an inactive one; picks scattered over the live
    pages; a table that is not whole blocks; pages scattered over the pool
    (a copy a page) and in runs (a block that is one run is ONE copy)."""
    KV, G, hd, ps = 2, 8, 128, 8
    H = KV * G
    rng = np.random.default_rng(0)
    lengths = np.array(lengths, np.int32)
    B = len(lengths)
    pages = max(160, B * entries + 1)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    kpool = jax.random.normal(ks[1], (2, pages, ps, KV, hd), jnp.float32)
    vpool = jax.random.normal(ks[2], (2, pages, ps, KV, hd), jnp.float32)
    tables = jnp.asarray(_tables(rng, B, entries, pages, runs))
    picked = rng.random((B, entries * ps)) < 0.3
    picked[:, 0] = True
    got = paged_decode_attention(q, kpool, vpool, 1, tables,
                                 jnp.asarray(lengths),
                                 selected=jnp.asarray(picked), interpret=True)
    ok = picked & (np.arange(entries * ps)[None] < lengths[:, None])
    want = masked_attention(
        q[:, None], kpool[1][tables].reshape(B, -1, KV, hd),
        vpool[1][tables].reshape(B, -1, KV, hd), jnp.asarray(ok)[:, None])
    want = np.where(lengths[:, None] > 0, np.asarray(want)[:, 0], 0)
    assert float(np.abs(np.asarray(got).reshape(B, -1) - want).max()) < 2e-5
    assert not np.asarray(got)[lengths == 0].any()
    if entries > 37:
        return
    # every position picked is the kernel without a selection
    plain = paged_decode_attention(q, kpool, vpool, 1, tables,
                                   jnp.asarray(lengths), interpret=True)
    every = paged_decode_attention(q, kpool, vpool, 1, tables,
                                   jnp.asarray(lengths), selected=jnp.ones(
                                       (B, entries * ps), bool), interpret=True)
    assert float(jnp.abs(plain - every).max()) < 1e-6


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_block_of_the_ring_reads_as_the_rows_it_holds(dtype):
    """16-bit rows are read as the 32-bit words they lie in and taken apart
    in registers (``block_rows``): the same rows in the same order."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    x = jax.random.normal(jax.random.PRNGKey(0), (3, 32, 128)).astype(dtype)

    def kernel(x_ref, o_ref):
        o_ref[...] = paged_attention.block_rows(x_ref, 1)

    got = pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(
        (32, 128), dtype), interpret=True)(x)
    assert jnp.array_equal(got, x[1])


@pytest.mark.parametrize("ps,dk,runs,entries,lengths", [
    (16, 64, False, 70, [3, 0, 70 * 16, 64 * 16 + 1]),
    (16, 64, True, 70, [3, 0, 70 * 16, 64 * 16 + 1]),
    (8, 16, False, 70, [3, 0, 70 * 8, 64 * 8 + 1]),
    # more blocks of 64 pages than buffers in the ring, the last of 10: the
    # largest table, an inactive slot between live ones, last blocks partly
    # live, a slot of exactly two blocks; runs that end inside blocks and
    # sub-runs (23), runs shorter than a sub-run (7), one run a slot
    (16, 64, 23, 394, [394 * 16, 0, 5000, 17, 2048]),
    (16, 64, 7, 394, [0, 394 * 16, 0, 1031]),
    (16, 64, True, 394, [4000, 394 * 16, 0, 2048])])
def test_paged_index_scores_match_the_plain_form(ps, dk, runs, entries,
                                                 lengths):
    """The indexer's scores out of the packed pool, in the interpreter, at
    the published packing (two keys a row) and the tiny one (eight), over
    scattered pages and over runs."""
    from ray_tpu.models.sparse_moe import indexer_scores

    J, B = 4, len(lengths)
    pages = max(300, B * entries + 1)
    rng = np.random.default_rng(ps)
    ks = jax.random.split(jax.random.PRNGKey(ps), 3)
    rows = jax.random.normal(ks[0], (2, pages * ps, dk), jnp.float32)
    pool = pack_keys(rows, ps)
    assert pool.shape == (2, pages, ps * dk // 128, 128)
    assert jnp.array_equal(unpack_keys(pool, dk), rows)
    qi = jax.random.normal(ks[1], (B, J, dk), jnp.float32)
    w = jax.random.normal(ks[2], (B, J), jnp.float32)
    tables = jnp.asarray(_tables(rng, B, entries, pages, runs))
    lengths = jnp.asarray(lengths, jnp.int32)
    got = paged_index_scores(qi, w, pool, 1, tables, lengths, interpret=True)
    keys = rows[1].reshape(pages, ps, dk)[tables].reshape(B, entries * ps, dk)
    want = indexer_scores(qi[:, None], w[:, None], keys)[:, 0]
    live = jnp.arange(entries * ps)[None] < lengths[:, None]
    assert got.shape == want.shape
    assert float(jnp.abs(jnp.where(live, got - want, 0)).max()) < 1e-4
    assert bool(jnp.isfinite(got).all())


def test_blocked_prefill_attention_with_picks_matches_the_plain_form():
    N, T, H, KV, hd = 2, 512, 8, 1, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (N, T, H, hd))
    k = jax.random.normal(ks[1], (N, T, KV, hd))
    v = jax.random.normal(ks[2], (N, T, KV, hd))
    picked = (jax.random.uniform(ks[3], (N, T, T)) < 0.3) | jnp.eye(T, dtype=bool)
    ok = picked & (jnp.arange(T)[:, None] >= jnp.arange(T)[None, :])
    want = masked_attention(q, k, v, ok)
    got = gqa_prefill_attention(q.reshape(N, T, -1), k.reshape(N, T, -1),
                                v.reshape(N, T, -1), n_kv_heads=KV,
                                picked=picked, interpret=True)
    assert float(jnp.abs(got - want).max()) < 1e-5


def _grown(before, after, name):
    return after[name][""]["sum"] - before.get(name, {}).get("", {"sum": 0})["sum"]


@pytest.mark.parametrize("cases,small_blocks,fetched,blocks,run_blocks", [
    # lengths 21..24 are 3 pages of 8, in 3 layers; a table of 12 pages is
    # one block of either walk, never whole: 2 walks x 4 steps x 3 layers
    ([(20, 5)], False, 3 * 4 * 24, 3 * 4 * 2, 0),
    # blocks of 2 pages (sub-runs of 1) under both walks, two programs of 4
    # steps: the first slot's lengths 14..21 cross a page and a block (16 |
    # 17) inside the first program, the second's 22..29 a page inside it (24
    # | 25) and a block inside the second (32 | 33). The free list hands
    # both their pages in runs, so every block whose pages all hold tokens
    # is ONE copy: pages 2 2 2 3 3 3 3 3 + 3 3 3 4 4 4 4 4 a step (50), blocks
    # 1 1 1 2 2 2 2 2 + 2 2 2 2 2 2 2 2, whole 1 1 1 1 1 1 1 1 + 1 1 1 2 2
    # 2 2 2 — of each walk, in 3 layers
    ([(13, 9), (21, 9)], True, 3 * 8 * (21 + 29), 3 * 2 * (13 + 16),
     3 * 2 * (8 + 13))])
def test_engine_decode_through_the_kernels_matches_the_gathered_form(
        monkeypatch, cases, small_blocks, fetched, blocks, run_blocks):
    """The chip's branch without a chip: both decode kernels interpreted
    under the engine, against the gathered form's tokens and counters. The
    tables' runs are found once a program: they must stay right while the
    lengths grow over pages and blocks inside it."""
    _, want = _serve(_engine(block_buckets=(4,)), cases)
    monkeypatch.setattr(programs, "_reads_in_place", lambda: True)
    if small_blocks:
        monkeypatch.setattr(paged_indexer, "_BLOCK_PAGES", 2)
        monkeypatch.setattr(paged_indexer, "_RUN_PAGES", 1)
        # 2 pages of K and V rows as they lie: 2 KV heads of 128 float lanes
        monkeypatch.setattr(paged_attention, "_BLOCK_BYTES",
                            2 * PS * 2 * CFG.n_kv_heads * 128 * 4)
        monkeypatch.setattr(paged_attention, "_RUN_PAGES", 1)
    jits = (programs.sparse_moe_decode_multi,
            paged_indexer._paged_index_scores,
            paged_attention._paged_selected_attention)
    for f in jits:
        f.clear_cache()
    try:
        eng = _engine(block_buckets=(4,))
        assert eng._kv_in_place
        before = metrics.stage_totals()
        _, got = _serve(eng, cases)
        after = metrics.stage_totals()
    finally:
        for f in jits:
            f.clear_cache()
    assert got == want
    # whole pages walked; blocks of both walks, and those that were one copy
    assert _grown(before, after,
                  "rt_llm_sparse_kv_positions_fetched_total") == fetched
    assert _grown(before, after, "rt_llm_sparse_walk_blocks_total") == blocks
    assert _grown(before, after,
                  "rt_llm_sparse_walk_run_blocks_total") == run_blocks


# ---------------------------------------------------------------- refusals
@pytest.mark.parametrize("feature,make", [
    ("kv_dtype='int8'", lambda: _engine(kv_dtype="int8")),
    ("lora_adapters", lambda: _engine(lora_adapters={"a": {}})),
    ("spec_enable", lambda: _engine(spec_enable=True)),
    ("export_pages", lambda: _engine().export_pages(1)),
    ("submit_prefilled", lambda: _engine().submit_prefilled([1], None, None, 3)),
    ("a K or V pool", lambda: _engine().kpool),
])
def test_what_is_the_llama_familys_is_refused_by_name(feature, make):
    with pytest.raises(UnsupportedByModel, match=feature.split("(")[0]) as e:
        make()
    assert "sparse_moe" in str(e.value) and "Llama family" in str(e.value)
    assert "it assumes one K pool" not in str(e.value)

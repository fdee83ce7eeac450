"""The window + full attention sparse-expert family (``models/cohere2_moe.py``,
``llm/cohere2_moe.py``, the ring start of ``ops/paged_attention.py``,
``ops/prefill_attention.py``, the engine's kinds of pages and wave limit)
against the benchmark's plain float32 reference
(``benchmarks/reference/cohere2_moe.py``), at a tiny size that keeps the
published shape's ratios: three window layers and a full one, four query
heads a KV head, a window (32) much shorter than the context, shared experts
averaged. CPU, float32, seeded weights."""
import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import weights_cohere2_moe as W
from benchmarks.reference import cohere2_moe as R
from ray_tpu.llm.engine import (ContinuousBatchingEngine, UnsupportedByModel,
                                serving_programs)
from ray_tpu.models.cohere2_moe import (Cohere2MoeConfig, cohere2_moe_forward,
                                        cohere2_moe_init)
from ray_tpu.ops.basic import rope_freqs, rope_pairs
from ray_tpu.ops.paged_attention import paged_decode_attention
from ray_tpu.ops.prefill_attention import gqa_prefill_attention
from ray_tpu.parallel.moe import routed_experts, sigmoid_topk_route
from ray_tpu.utils import metrics

CFG = Cohere2MoeConfig.tiny(experts_held=(4, 12), vocab_held=(256, 512))
PS, RING = 8, 5          # pages of 8: a window of 32 touches at most 5
SEEDS = [3, 2**31 + 7]


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def test_tiny_keeps_the_published_shape():
    full = Cohere2MoeConfig()
    assert CFG.layer_types == full.layer_types[:4]
    assert CFG.layers_of(True) == (0, 1, 2) and CFG.layers_of(False) == (3,)
    assert full.n_heads // full.n_kv_heads == 16 and full.sliding_window == 4096
    assert CFG.held == (4, 12) and CFG.vocab_size == 256
    with pytest.raises(ValueError, match="layer_types"):
        Cohere2MoeConfig.tiny(n_layers=3)
    params = cohere2_moe_init(jax.random.PRNGKey(0), CFG)
    seeded = W.make_params(W.seed_key(0), CFG)
    assert (jax.tree.map(lambda x: (x.shape, x.dtype), params)
            == jax.tree.map(lambda x: (x.shape, x.dtype), seeded))


# ------------------------------------------------- the engine and the reference
def _engine(seed=5, cfg=CFG, **kw):
    params = W.make_params(W.seed_key(seed), cfg)
    kw = {"max_batch": 3, "page_size": PS, "max_seq_len": 160,
          "n_pages": {"full": 61, "window": 16}, "eos_id": None,
          "block_buckets": (4, 8), **kw}
    return ContinuousBatchingEngine(params, cfg, **kw)


# prompts on both sides of the window of 32; decode steps cross page
# boundaries and slide the window, past the ring's 40 positions too
CASES = [(20, 10), (50, 30), (70, 12)]


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
    prompts, outs = _serve(_engine(), CASES)
    return prompts, outs


@pytest.mark.parametrize("eos_id", [None, 300])  # the planned, the reactive loop
def test_prefill_then_decode_through_both_kinds_of_pages_is_the_reference(eos_id):
    eng = _engine(eos_id=eos_id)
    prompts, outs = _serve(eng, CASES)
    assert [len(o) for o in outs] == [m for _, m in CASES]
    assert float(_logit_gaps(5, CFG, prompts, outs).max()) == 0.0
    assert [len(f) for f in eng.free] == [60, 15]   # every page of both kinds back


def test_the_pools_hold_the_references_rows_on_both_sides_of_the_ring():
    """Logits and cache rows: a request past the ring's 40 positions leaves
    the full layer's rows whole and, of the window layers', the last pages,
    each at entry ``page % 5`` of the slot's table (the oldest of the five
    is left out: the last fused block decodes three steps past the last
    token, into the page after the last, which lies over the oldest)."""
    eng = _engine()
    prompt = np.random.default_rng(1).integers(3, CFG.vocab_size, 50).tolist()
    drawn = [list(f[:n]) for f, n in zip(eng.free, eng._pages_of(80))]
    assert [len(d) for d in drawn] == [10, RING]

    async def run():
        await eng.start()
        out = await asyncio.wait_for(eng.generate(prompt, max_tokens=30), 240)
        await eng.stop()
        return out

    out = asyncio.run(run())
    n_rows = 50 + 30 - 1
    want = R.forward(5, CFG, prompt + out[:-1], q_block=32)
    kf, vf, kw, vw = eng.cache
    got = kf[0][jnp.asarray(drawn[0])].reshape(-1, 32)[:n_rows]
    assert rel(got, want["k"][3, :n_rows]) < 1e-5
    last = (n_rows - 1) // PS
    for page in range(last - RING + 2, last + 1):
        rows = slice(page * PS, min((page + 1) * PS, n_rows))
        for layer in range(3):
            got = vw[layer][drawn[1][page % RING]].reshape(PS, 32)
            assert rel(got[:rows.stop - rows.start], want["v"][layer, rows]) < 1e-5


@pytest.mark.parametrize("variant", [
    {"window": 32 - PS}, {"window": 32 + PS}, {"rotate_full": True},
    {"shared": "sum"}, {"sequential": True}])
def test_a_reference_with_other_mathematics_fails_the_comparison(served, variant):
    """The controls: the window off by one page either way, rotation on the
    full layer, the shared experts summed and not averaged, the block made
    sequential — each is a forward pass the program's tokens are not the
    greedy tokens of, by a wide margin."""
    prompts, outs = served
    assert float(_logit_gaps(5, CFG, prompts, outs).max()) == 0.0
    assert float(_logit_gaps(5, CFG, prompts, outs, variant=variant).max()) > 0.05


def test_bf16_programs_stay_within_a_stated_tolerance():
    """The same comparison in the type the cell serves: rows within 2 % (bf16
    has 8 bits of mantissa: 0.4 % a rounding, a few roundings deep), tokens
    within a fifth of a logit spread of the reference's best."""
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    eng = _engine(cfg=cfg)
    prompts, outs = _serve(eng, CASES[1:2])
    gaps = _logit_gaps(5, cfg, prompts, outs)
    assert float(np.percentile(gaps, 50)) == 0.0 and float(gaps.max()) < 0.2
    low = R.forward(5, cfg, prompts[0] + outs[0][:-1], q_block=32)
    want = cohere2_moe_forward(W.make_params(W.seed_key(5), cfg),
                               jnp.asarray([prompts[0] + outs[0][:-1]]), cfg)
    assert rel(want[0].astype(jnp.float32), low["logits"]) < 0.05


@pytest.mark.parametrize("seed", SEEDS)
def test_forward_logits_match_the_plain_reference(seed):
    params = W.make_params(W.seed_key(seed), CFG)
    tokens = np.random.default_rng(seed % 1000).integers(3, CFG.vocab_size, 70)
    want = R.forward(seed, CFG, tokens, q_block=32)
    got = cohere2_moe_forward(params, jnp.asarray(tokens)[None], CFG)[0]
    assert rel(got, want["logits"]) < 1e-5
    assert want["chosen"].shape == (4, 70, CFG.n_experts_per_tok)


# ---------------------------------------------------------------- the share
@pytest.mark.parametrize("holders", [8, 2])
def test_holders_parts_add_up_to_the_uncut_layer(holders):
    """The chip's share of a deployment (model-configs guide, section 4): the
    16 experts of a layer divided over ``holders``; each routes over all of
    them and computes its own experts' part; the parts, with the shared
    experts' mean counted once, are the uncut reference's layer output."""
    whole = dataclasses.replace(CFG, experts_held=None)
    key = W.layer_key(W.seed_key(5), 1)
    full = W.layer_from_seed(W.seed_key(5), whole, 1)["moe"]
    h = jax.random.normal(jax.random.PRNGKey(1), (37, CFG.d_model))
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), full)
    want, _ = R.moe(f32, h, whole, "float32")
    only_shared = want - R.moe(f32, h, whole, "float32", shared=None)[0]
    per = CFG.n_experts // holders
    total, loads = 0.0, []
    for r in range(holders):
        cfg = dataclasses.replace(CFG, experts_held=(r * per, (r + 1) * per))
        mine = {n: W.expert_stack(key, cfg, i)
                for i, n in enumerate(("w_gate", "w_up", "w_down"))}
        assert mine["w_gate"].shape[0] == per
        assert jnp.array_equal(mine["w_up"],
                               full["experts"]["w_up"][r * per:(r + 1) * per])
        idx, w = sigmoid_topk_route(h, full["router"]["kernel"], None,
                                    cfg.n_experts_per_tok, 1.0)
        part, load = routed_experts(h, idx, w, mine, cfg.held)
        total = total + part
        loads.append(load)
    assert rel(total + only_shared, want) < 1e-5
    assert int(jnp.concatenate(loads).sum()) == h.shape[0] * CFG.n_experts_per_tok


# ------------------------------------------------------------- the allocator
def _held():
    # this family's kinds alone: the gauge is the process's, and under
    # ``--dist loadfile`` a worker that ran another family's file first still
    # holds that family's ("kv", "state", "summary")
    g = metrics.stage_totals()["rt_llm_pages_held"]
    return {k: v["sum"] for k, v in g.items() if k in ("full", "window")}


def test_a_slot_never_holds_more_window_pages_than_the_ring():
    eng = _engine()
    full, window = eng.kinds
    assert (full.name, full.table, full.reach) == ("full", 20, None)
    assert (window.name, window.table, window.reach) == ("window", RING, 32)
    for n in (1, 8, 9, 39, 40, 41, 100, 160):
        assert eng._pages_of(n) == [-(-n // PS), min(-(-n // PS), RING)]
    before = metrics.stage_totals()
    prompts, outs = _serve(eng, [(70, 13)])   # 12 decode steps: blocks 8 + 4
    after = metrics.stage_totals()

    def grown(name, tag=""):
        return (after[name][tag]["sum"]
                - before.get(name, {}).get(tag, {"sum": 0})["sum"])

    assert grown("rt_llm_pages_drawn_total", "full") == 11       # ceil(83 / 8)
    assert grown("rt_llm_pages_drawn_total", "window") == RING
    # the ring wrote over the pages its window slid past: 11 reached, 5 held
    assert grown("rt_llm_window_pages_released_total") == 11 - RING
    assert _held() == {"full": 0, "window": 0}
    # reads: a full layer's reach is the length, a window layer's 32 at most
    live_w = grown("rt_llm_decode_kv_tokens_live_total", "window")
    live_f = grown("rt_llm_decode_kv_tokens_live_total", "full")
    assert live_f == sum(range(71, 83)) and live_w == 12 * 32
    assert grown("rt_llm_decode_kv_tokens_live_total") == pytest.approx(
        (3 * live_w + live_f) / 4)


def test_admission_waits_for_whichever_kind_runs_out_and_starves_nobody():
    """One long and several short requests in one queue, with too few window
    pages for all at once: the head of the queue waits for its pages, the
    ones behind it wait for it, and every request finishes."""
    eng = _engine(n_pages={"full": 61, "window": 9})   # 8 window pages: 5 + 3
    with pytest.raises(ValueError, match="'full' kind"):
        _engine(n_pages={"full": 5, "window": 9}).submit([1] * 60, max_tokens=4)
    peak = {"window": 0}
    real = eng._count_pages

    def watch(i, drawn=0):
        real(i, drawn)
        name = eng.kinds[i].name
        peak[name] = max(peak.get(name, 0), eng.capacity[i] - len(eng.free[i]))

    eng._count_pages = watch
    cases = [(70, 12), (20, 4), (50, 10), (20, 4), (12, 4)]
    prompts, outs = _serve(eng, cases)
    assert [len(o) for o in outs] == [m for _, m in cases]
    assert float(_logit_gaps(5, CFG, prompts, outs).max()) == 0.0
    assert 0 < peak["window"] <= 8 and [len(f) for f in eng.free] == [60, 8]


# ------------------------------------------------------------ the wave limit
def _waves_of(limit, lens):
    """The prefill programs ``_admit_dispatch`` builds for prompts of these
    lengths waiting on an idle engine: the prompts a program holds, and
    each program's (pad, rows)."""
    eng = _engine(max_batch=8, n_pages={"full": 200, "window": 60})
    eng.programs = dataclasses.replace(eng.programs, prefill_wave_limit=limit)
    seen = []
    real = eng.programs.prefill_batch

    def spy(params, loras, aids, toks, *rest):
        seen.append((toks.shape[1], toks.shape[0]))
        return real(params, loras, aids, toks, *rest)

    spy.__name__ = "spy"
    spy.lower = real.lower
    eng.programs = dataclasses.replace(eng.programs, prefill_batch=spy)

    async def run():
        for n in lens:
            eng.submit([5] * n, max_tokens=2)
        groups = await eng._admit_dispatch()
        return [len(reqs) for reqs, _ in groups]

    sizes = asyncio.run(asyncio.wait_for(run(), 240))
    return sizes, sorted(seen)


def test_the_wave_limit_splits_a_group_and_no_limit_builds_todays_waves():
    lens = [24] * 5 + [40] * 3
    # no limit: one program a pad, 5 prompts in a wave of 8, 3 in one of 4
    assert _waves_of(None, lens) == ([5, 3], [(24, 8), (40, 4)])
    # at most 2 prompts: 24 -> 2 + 2 + 1, 40 -> 2 + 1
    assert _waves_of((2, 10**6), lens) == ([2, 2, 1, 2, 1],
                                           [(24, 1), (24, 2), (24, 2), (40, 1), (40, 2)])
    # at most 100 tokens: four prompts of 24, two of 40
    assert _waves_of((8, 100), lens) == ([4, 1, 2, 1],
                                         [(24, 1), (24, 4), (40, 1), (40, 2)])
    assert serving_programs(CFG).prefill_wave_limit == (8, 16384)
    for other in ("llama", "mla_moe"):
        mod = __import__(f"ray_tpu.llm.{other}", fromlist=["PROGRAMS"])
        assert mod.PROGRAMS.prefill_wave_limit is None
        assert mod.PROGRAMS.page_kinds is None


# ---------------------------------------------------------------- the kernels
def _dense_reference(q, kpool, vpool, layer, tables, lengths, starts, ring):
    """Masked softmax over every position of the sequence, gathered page by
    page: position p lies in page ``p // PS`` at table entry ``page %
    entries`` (a ring) or ``page``."""
    B, H, hd = q.shape
    ps, KV = kpool.shape[2], kpool.shape[3]
    out = np.zeros((B, H, hd), np.float32)
    for b in range(B):
        lo, hi = int(starts[b]), int(lengths[b])
        if hi <= lo:
            continue
        pos = np.arange(lo, hi)
        entry = pos // ps % tables.shape[1] if ring else pos // ps
        k = np.asarray(kpool)[layer, tables[b, entry], pos % ps]   # [n, KV, hd]
        v = np.asarray(vpool)[layer, tables[b, entry], pos % ps]
        for h in range(H):
            s = k[:, h // (H // KV)] @ np.asarray(q)[b, h] / np.sqrt(hd)
            w = np.exp(s - s.max())
            out[b, h] = (w / w.sum()) @ v[:, h // (H // KV)]
    return out


@pytest.mark.parametrize("G", [16, 4])
def test_paged_attention_with_a_start_matches_a_dense_masked_softmax(G):
    """The walk from a slot's first live page over a ring table, in the
    interpreter: slots short of the window, at it, past it and wrapped
    several times; an inactive slot; 16 and 4 query heads a KV head."""
    KV, hd, ps, W, entries = 2, 128, 8, 32, 5
    H = KV * G
    rng = np.random.default_rng(G)
    lengths = np.array([5, 32, 33, 0, 47, 131], np.int32)
    starts = np.maximum(lengths - W, 0).astype(np.int32)
    B = len(lengths)
    ks = jax.random.split(jax.random.PRNGKey(G), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    kpool = jax.random.normal(ks[1], (2, 40, ps, KV, hd), jnp.float32)
    vpool = jax.random.normal(ks[2], (2, 40, ps, KV, hd), jnp.float32)
    tables = rng.permutation(np.arange(1, 31)).reshape(B, entries).astype(np.int32)
    got = paged_decode_attention(q, kpool, vpool, 1, jnp.asarray(tables),
                                 jnp.asarray(lengths), starts=jnp.asarray(starts),
                                 interpret=True)
    want = _dense_reference(q, kpool, vpool, 1, tables, lengths, starts, True)
    assert float(np.abs(np.asarray(got) - want).max()) < 2e-5
    assert not np.asarray(got)[3].any()
    # without a start the same kernel walks from page 0, as it always has
    flat = rng.permutation(np.arange(1, 37)).reshape(B, 6).astype(np.int32)
    short = np.minimum(lengths, 6 * ps).astype(np.int32)
    got = paged_decode_attention(q, kpool, vpool, 0, jnp.asarray(flat),
                                 jnp.asarray(short), interpret=True)
    want = _dense_reference(q, kpool, vpool, 0, flat, short, 0 * short, False)
    assert float(np.abs(np.asarray(got) - want).max()) < 2e-5


@pytest.mark.parametrize("window", [None, 300, 512])
def test_blocked_prefill_attention_matches_the_plain_form(window):
    from ray_tpu.ops.attention import masked_attention

    N, T, H, KV, hd = 2, 1024, 4, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (N, T, H, hd))
    k = jax.random.normal(ks[1], (N, T, KV, hd))
    v = jax.random.normal(ks[2], (N, T, KV, hd))
    idx = jnp.arange(T)
    ok = idx[:, None] >= idx[None, :]
    if window:
        ok &= idx[:, None] - idx[None, :] < window
    want = masked_attention(q, k, v, jnp.broadcast_to(ok, (N, T, T)))
    got = gqa_prefill_attention(q.reshape(N, T, -1), k.reshape(N, T, -1),
                                v.reshape(N, T, -1), n_kv_heads=KV,
                                window=window, interpret=True)
    assert float(jnp.abs(got - want).max()) < 1e-5


# ------------------------------------------------------- the pair rotation
def _rope_pairs_deinterleaved(x, cos, sin, positions):
    """The form ``ops/basic.py`` ``rope_pairs`` had until PR 51, kept as its
    plain reference: the lanes split into (even, odd) and stacked back."""
    c = cos[positions][:, :, None, :]
    s = sin[positions][:, :, None, :]
    pairs = x.reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _rotation_case(shape, dtype, max_seq_len=13312):
    B, T, _, D = shape
    rng = np.random.default_rng(B * 1000 + D)
    x = jnp.asarray(rng.standard_normal(shape), dtype)
    positions = rng.integers(0, max_seq_len, (B, T))
    positions[0, 0], positions[-1, -1] = 0, max_seq_len - 1
    return x, *rope_freqs(D, max_seq_len, 50000.0), jnp.asarray(positions)


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [(2, 5, 8, 16), (48, 1, 128, 128),
                                   (1, 7, 8, 128)])
def test_rope_pairs_is_the_deinterleaved_rotation_lane_for_lane(shape, dtype,
                                                                jit):
    """Evaluated eagerly the lane-preserving form is the replaced one bit for
    bit (``a - b * s`` and ``a + b * (-s)`` are one float operation); under
    ``jit`` the compiler contracts the two forms' multiply-adds differently:
    one unit in the last place of the output's dtype at most, and where the
    two products cancel one float32 unit of the larger product."""
    args = _rotation_case(shape, jnp.dtype(dtype))
    if not jit:
        with jax.disable_jit():
            got, want = rope_pairs(*args), _rope_pairs_deinterleaved(*args)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        return
    got = np.asarray(jax.jit(rope_pairs)(*args), np.float32)
    want = np.asarray(jax.jit(_rope_pairs_deinterleaved)(*args), np.float32)
    assert got.shape == shape
    # a bfloat16's last place is 2**16 of the float32's that holds it
    ulp = np.spacing(np.abs(want)) * (1 if dtype == "float32" else 2.0**16)
    pair = np.abs(np.asarray(args[0], np.float32)).reshape(*shape[:-1], -1, 2)
    product = np.repeat(pair.max(-1), 2, axis=-1)  # |cos|, |sin| <= 1
    bound = np.maximum(ulp, np.spacing(product))
    assert (np.abs(got - want) <= bound).all(), float(np.abs(got - want).max())


def test_rope_pairs_is_the_references_rotation():
    """Against ``benchmarks/reference/cohere2_moe.py`` ``_rotate_pairs``,
    which makes its own angles from the position: float32, to 1e-6."""
    T, H, D = 300, 8, 128
    x = jax.random.normal(jax.random.PRNGKey(7), (T, H, D))
    got = rope_pairs(x[None], *rope_freqs(D, 512, 50000.0),
                     jnp.arange(T)[None])[0]
    assert float(jnp.abs(got - R._rotate_pairs(x, 50000.0)).max()) < 1e-6


# ---------------------------------------------------------------- refusals
@pytest.mark.parametrize("feature,make", [
    ("kv_dtype='int8'", lambda: _engine(kv_dtype="int8")),
    ("lora_adapters", lambda: _engine(lora_adapters={"a": {}})),
    ("spec_enable", lambda: _engine(spec_enable=True)),
    ("export_pages", lambda: _engine().export_pages(1)),
    ("submit_prefilled", lambda: _engine().submit_prefilled([1], None, None, 3)),
    ("a K or V pool", lambda: _engine().kpool),
])
def test_what_assumes_one_k_and_one_v_pool_is_refused_by_name(feature, make):
    with pytest.raises(UnsupportedByModel, match=feature.split("(")[0]) as e:
        make()
    assert "cohere2_moe" in str(e.value)

"""The compressed-latent convolved attention + top-1 expert family
(``models/cca_moe.py``, ``llm/cca_moe.py``, ``ops/cca.py``, the MLP router of
``parallel/moe.py``) against the benchmark's plain float32 reference
(``benchmarks/reference/cca_moe.py``), at a tiny size that keeps the
published shape's ratios: 4 query heads on 2 key heads so that the mean
averages a group, half a head rotated, 4 experts of which 1, a router narrower
than the model, 3 layers so that the router's carry crosses two. CPU, float32,
seeded weights."""
import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import weights_cca_moe as W
from benchmarks.reference import cca_moe as R
from ray_tpu.llm import cca_moe as programs
from ray_tpu.llm.engine import (ContinuousBatchingEngine, UnsupportedByModel,
                                serving_programs)
from ray_tpu.models.cca_moe import (CcaMoeConfig, cca_moe_forward,
                                    cca_moe_init)
from ray_tpu.ops import cca
from ray_tpu.parallel import moe
from ray_tpu.parallel.moe import mlp_top1_route, routed_experts
from ray_tpu.utils import metrics

CFG = CcaMoeConfig.tiny()
PS = 8
EOS = 1
SEEDS = [3, 2**31 + 7]
L, C, ROW = CFG.n_layers, CFG.conv_width, cca.row_width(CFG)
KVW = CFG.n_kv_heads * CFG.head_dim


def rel(a, b):
    return float(jnp.linalg.norm(jnp.asarray(a) - jnp.asarray(b))
                 / jnp.linalg.norm(jnp.asarray(b)))


def test_tiny_keeps_the_published_shape():
    full = CcaMoeConfig()
    assert (full.n_layers, full.n_heads, full.n_kv_heads, full.head_dim) == (
        40, 8, 2, 128)
    assert (full.conv_width, full.v_half, full.in_width) == (1280, 128, 1536)
    assert cca.row_width(full) == 2688
    assert (full.rotary_dim, CFG.rotary_dim) == (64, 8)      # half a head
    assert 2 * CFG.rotary_dim == CFG.head_dim
    assert CFG.n_heads // CFG.n_kv_heads == 2 and CFG.n_kv_heads == 2
    assert CFG.router_hidden < CFG.d_model and full.router_hidden == 256
    assert (full.n_experts, CFG.n_experts) == (16, 4) and CFG.n_layers == 3
    with pytest.raises(ValueError, match="rotated in pairs"):
        CcaMoeConfig.tiny(rotary_dim=7)
    params = cca_moe_init(jax.random.PRNGKey(0), CFG)
    seeded = W.make_params(W.seed_key(0), CFG, EOS)
    assert (jax.tree.map(lambda x: (x.shape, x.dtype), params)
            == jax.tree.map(lambda x: (x.shape, x.dtype), seeded))
    assert set(params) == {"tok", "norm", "layers_0", "layers_1", "layers_2"}
    assert set(params["layers_0"]) == {
        "attn_norm", "ffn_norm", "w_in", "conv0", "conv1", "temp", "wo", "res",
        "moe"}
    assert set(params["layers_0"]["moe"]["router"]) == {
        "down", "gamma", "norm", "w1", "b1", "w2", "b2", "w3", "bias"}
    assert not np.asarray(seeded["tok"]["embedding"][EOS]).any()
    assert serving_programs(CFG) is programs.PROGRAMS
    kv, row = programs.page_kinds(CFG, PS, 96)
    assert (kv.name, kv.layers, kv.table, kv.positions) == ("kv", L, 12, True)
    assert (row.name, row.layers, row.table, row.positions) == (
        "row", L, 1, False)


# ------------------------------------------------- the engine and the reference
def _engine(seed=5, cfg=CFG, **kw):
    params = W.make_params(W.seed_key(seed), cfg, EOS)
    kw = {"max_batch": 3, "page_size": PS, "max_seq_len": 96,
          "n_pages": {"kv": 41, "row": 4}, "eos_id": None,
          "block_buckets": (4, 8), **kw}
    return ContinuousBatchingEngine(params, cfg, **kw)


# prompts that fill no page, one of several pages, one as short as the two
# positions the convolutions reach back; decode steps cross pages
CASES = [(10, 13), (40, 9), (2, 5)]


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


def _serve_one(eng, prompt, m):
    async def run():
        await eng.start()
        out = await asyncio.wait_for(eng.generate(prompt, max_tokens=m), 240)
        await eng.stop()
        return [prompt], [out]

    return asyncio.run(run())


def _ref(seed, cfg, seq, **kw):
    return R.forward(seed, cfg, seq, zero_row=EOS, **kw)


def _logit_gaps(seed, cfg, prompts, outs, **ref_kw):
    """For each request, the reference's best logit less its logit for the
    token the program emitted, at every position, in logit spreads: zeros
    where the program's tokens are the reference's own."""
    gaps = []
    for p, o in zip(prompts, outs):
        logits = np.asarray(_ref(seed, cfg, p + o[:-1],
                                 logits_from=len(p) - 1, **ref_kw)["logits"])
        gaps.append((logits.max(-1) - logits[np.arange(len(o)), o])
                    / logits.std(-1))
    return np.concatenate(gaps)


@pytest.fixture(scope="module")
def served():
    return _serve(_engine(), CASES)


@pytest.mark.parametrize("eos_id", [None, EOS])  # the planned, the reactive loop
def test_prefill_then_decode_through_pages_and_rows_is_the_reference(eos_id):
    eng = _engine(eos_id=eos_id)
    prompts, outs = _serve(eng, CASES)
    assert [len(o) for o in outs] == [m for _, m in CASES]
    assert float(_logit_gaps(5, CFG, prompts, outs).max()) == 0.0
    # every page and every row back
    assert (len(eng.free[0]), len(eng.free[1])) == (40, 3)
    held = metrics.stage_totals()["rt_llm_pages_held"]
    assert held["row"]["sum"] == 0 and held["kv"]["sum"] == 0


@pytest.mark.parametrize("n,m", [(10, 13), (21, 5), (2, 13)])
def test_the_cache_holds_the_references_keys_values_and_rows(n, m):
    """Every layer's K and V pages and its row after a request whose decode
    steps end with its last token (blocks 8 + 4, or 4): the row stands after
    ``n + m - 1`` positions."""
    eng = _engine()
    prompt = np.random.default_rng(1).integers(3, CFG.vocab_size, n).tolist()
    drawn = jnp.asarray(eng.free[0][:eng._pages_of(n + m)[0]])
    row = eng.free[1][0]
    _, (out,) = _serve_one(eng, prompt, m)
    assert eng.steps == m - 1
    at = n + m - 1
    want = _ref(5, CFG, prompt + out[:-1], state_at=(at,))
    kp, vp, rows = eng.cache
    assert kp.shape == vp.shape == (L, 41, PS, 2, 16)
    assert rows.shape == (L, 4, ROW)
    for pool, name in ((kp, "k"), (vp, "v")):
        got = pool[:, drawn].reshape(L, -1, KVW)
        assert rel(got[:, :at], want[name][:, :at]) < 1e-5, name
    assert rel(rows[:, row], want["row"][:, 0]) < 1e-5
    # the other rows, the junk row among them, belong to nobody
    assert not np.asarray(rows[:, [r for r in range(1, 4) if r != row]]).any()


# ----------------------------------------------------- padding, rows, the wave
def _wave(lens, plant=0.0, seed=None):
    """One prefill wave as the engine builds it: prompts padded to whole
    pages, the wave to a bucket of 4 with dummy rows whose pages and row are
    the junk ones; ``plant`` what a released slot left in every row."""
    pad = -(-max(lens) // PS) * PS
    rng = np.random.default_rng(sum(lens) if seed is None else seed)
    prompts = [rng.integers(3, CFG.vocab_size, n).tolist() for n in lens]
    params = W.make_params(W.seed_key(5), CFG, EOS)
    kp, vp, rows = programs.make_pools(CFG, PS, {"kv": 20, "row": 6}, None)
    toks = np.zeros((4, pad), np.int32)
    pages = np.zeros((4, pad // PS), np.int32)
    at = np.zeros((4, 1), np.int32)
    true_lens = np.ones(4, np.int32)
    for j, p in enumerate(prompts):
        toks[j, :len(p)] = p
        pages[j] = 1 + j * (pad // PS) + np.arange(pad // PS)
        at[j], true_lens[j] = 1 + j, len(p)
    out = programs.cca_moe_prefill_batch(
        params, None, jnp.zeros(4, jnp.int32), jnp.asarray(toks),
        (jnp.asarray(pages), jnp.asarray(at)), kp + plant, vp + plant,
        rows + plant, jnp.asarray(true_lens), jnp.zeros(4),
        jax.random.PRNGKey(0), CFG)
    return prompts, pages, out


@pytest.mark.parametrize("lens", [[1, 2, 3], [5, 17], [7, 8, 9, 16]])
def test_a_padded_prompt_leaves_the_row_of_its_true_length(lens):
    """Each prompt's row is the reference's after its TRUE length — ``u``,
    ``c0`` and ``v2`` of position ``len - 1``, nothing of the pad behind it —
    and rows nobody drew keep what was planted."""
    prompts, pages, (first, kp, vp, rows) = _wave(lens, plant=7.0)
    for j, p in enumerate(prompts):
        want = _ref(5, CFG, p, state_at=(len(p),), logits_from=len(p) - 1)
        assert float(jnp.abs(rows[:, 1 + j] - want["row"][:, 0]).max()) < 1e-5
        for pool, name in ((kp, "k"), (vp, "v")):
            mine = pool[:, jnp.asarray(pages[j])].reshape(L, -1, KVW)
            assert rel(mine[:, :len(p)], want[name]) < 1e-5
        assert int(first[j]) == int(jnp.argmax(want["logits"][0]))
    untouched = [r for r in range(1, 6) if r > len(lens)]
    assert np.all(np.asarray(rows[:, untouched]) == 7.0)


def test_a_row_drawn_again_is_overwritten_and_never_read():
    """The leak this family could have: ``v2`` of a dead request's last
    position reaching position 0's value. Plant every row and every page
    first: the outputs are bit for bit those from a zero cache, and position
    0's value has a ZERO shifted half in every layer."""
    lens = [5, 17]
    _, pages, clean = _wave(lens, plant=0.0)
    _, _, planted = _wave(lens, plant=7.0)
    np.testing.assert_array_equal(clean[0], planted[0])
    for j in range(len(lens)):
        np.testing.assert_array_equal(clean[3][:, 1 + j], planted[3][:, 1 + j])
        at = jnp.asarray(pages[j])
        np.testing.assert_array_equal(clean[1][:, at], planted[1][:, at])
        v0 = planted[2][:, pages[j][0], 0]                   # [L, KV, hd]
        assert not np.asarray(v0[:, 1]).any()
        assert np.asarray(v0[:, 0]).any()


def test_a_dead_slot_and_the_junk_row_never_reach_a_live_slot():
    """Decode with one live slot of three: the dead slots' steps go to the
    junk row and the junk page (planted non-zero, as every other row); the
    live slot's row is the reference's, and rows nobody holds keep what was
    planted — the junk row too: no live slot owns it, so it stays."""
    eng = _engine()
    kp, vp, rows = eng.cache
    eng.cache = (kp, vp, rows + 3.0)
    prompt = np.random.default_rng(2).integers(3, CFG.vocab_size, 12).tolist()
    row = eng.free[1][0]
    prompts, outs = _serve_one(eng, prompt, 13)
    assert float(_logit_gaps(5, CFG, prompts, outs).max()) == 0.0
    want = _ref(5, CFG, prompt + outs[0][:-1], state_at=(24,))
    assert rel(eng.cache[2][:, row], want["row"][:, 0]) < 1e-5
    others = [r for r in range(1, 4) if r != row]
    assert np.all(np.asarray(eng.cache[2][:, others]) == 3.0)
    assert np.all(np.asarray(eng.cache[2][:, 0]) == 3.0)   # junk: no live slot's


def test_a_slot_reused_after_a_release_sees_none_of_the_old_row():
    """Two rows for five requests: every row is drawn again after a release
    with its last holder's row in it, and every request is the reference's."""
    eng = _engine(n_pages={"kv": 41, "row": 3}, max_batch=2)
    cases = [(9, 6), (17, 5), (3, 9), (24, 4), (11, 5)]
    prompts, outs = _serve(eng, cases)
    assert float(_logit_gaps(5, CFG, prompts, outs).max()) == 0.0
    drawn = metrics.stage_totals()["rt_llm_pages_drawn_total"]["row"]["sum"]
    assert drawn >= 5 and len(eng.free[1]) == 2


@pytest.mark.parametrize("n_pages,free", [
    ({"kv": 13, "row": 4}, (12, 3)),    # pages run out first
    ({"kv": 41, "row": 2}, (40, 1))])   # the one row does
def test_admission_waits_for_whichever_kind_runs_out(n_pages, free):
    eng = _engine(n_pages=n_pages)
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
    assert order == [60, 12, 40, 10]
    assert (len(eng.free[0]), len(eng.free[1])) == free


def test_the_wave_limit_is_the_familys_and_splits_a_group():
    eng = _engine(max_batch=8, n_pages={"kv": 200, "row": 9})
    assert eng.programs.prefill_wave_limit == (8, 8192)
    assert [len(w) for w in eng._split_wave(1024, [0] * 8)] == [8]
    assert [len(w) for w in eng._split_wave(2048, [0] * 7)] == [4, 3]
    assert [len(w) for w in eng._split_wave(256, [0] * 8)] == [8]


# ---------------------------------------------------------------- the controls
CONTROLS = {
    "no convolution": {"conv": "none"},
    "the depthwise convolution alone": {"conv": "depthwise"},
    "the second convolution depthwise too": {"conv": "depthwise2"},
    "no mean term": {"mean": "none"},
    "the mean without the group average": {"mean": "first"},
    "no value shift": {"vshift": "none"},
    "the shift on key head 0": {"vshift": "head0"},
    "q, k not normalised": {"qknorm": False},
    "tau one": {"temp": "one"},
    "the whole head rotated": {"rope": "whole"},
    "theta 1e4": {"theta": 1e4},
    "k cached before tau": {"temp": "after"},
    "no carry": {"carry": False},
    "the router on x": {"router_in": "x"},
    "the bias in the weight": {"bias": "weights"},
    "top-1 weighs one": {"weight": "one"},
    "residual gains one": {"gains": "one"}}
# what changes nothing of layer 0's cache: the router and the residual gains
# stand behind layer 0's mixer
ROUTER_ONLY = ("carry", "router_in", "bias", "weight", "gains")


@pytest.mark.parametrize("name", list(CONTROLS))
def test_a_reference_with_other_mathematics_fails_the_comparison(served, name):
    """The controls: each is a forward pass whose cache is not the program's
    — layer 0's keys, values or row where the mixer changed, the last
    layer's (behind two expert sublayers) where the router or the gains
    did — and, but for the key cached before its temperature, whose logits
    are not the program's either."""
    variant = CONTROLS[name]
    prompts, outs = served
    seq = prompts[1] + outs[1][:-1]
    want = _ref(5, CFG, seq, state_at=(len(seq),))
    low = _ref(5, CFG, seq, state_at=(len(seq),), variant=variant)
    if set(variant) & set(ROUTER_ONLY):
        np.testing.assert_array_equal(low["k"][0], want["k"][0])  # before any
        if "carry" in variant:                 # layer 0 has no carry to lose
            np.testing.assert_array_equal(low["chosen"][0], want["chosen"][0])
            assert not np.array_equal(low["chosen"][1:], want["chosen"][1:])
        deep = max(rel(low[n][-1], want[n][-1]) for n in ("k", "v"))
        assert deep > 0.02, deep
    else:
        first = max(rel(low[n][0], want[n][0]) for n in ("k", "v"))
        assert first > (0.02 if "theta" not in variant else 2e-3), first
    if variant.get("temp") != "after":
        gaps = _logit_gaps(5, CFG, prompts, outs, variant=variant)
        assert rel(low["logits"], want["logits"]) > 0.01
        assert float(gaps.max()) > 0.0
    else:
        assert rel(low["logits"], want["logits"]) < 1e-5


@pytest.mark.parametrize("mode,least", [("fp8", 0.01), ("bfloat16", 1e-3)])
def test_the_reference_at_a_lower_precision_reads_apart(served, mode, least):
    prompts, outs = served
    seq = prompts[1] + outs[1][:-1]
    want = _ref(5, CFG, seq)
    low = _ref(5, CFG, seq, mode=mode)
    assert rel(low["k"][0], want["k"][0]) > least
    # the router in bf16: its products rounded, the stream with them
    if mode == "bfloat16":
        rounded = _ref(5, CFG, seq, variant={"router": "bfloat16"})
        assert rel(rounded["r"][0], want["r"][0]) > 1e-3


@pytest.mark.parametrize("n", [5, 17])
def test_pad_positions_advancing_the_row_fail_the_comparison(n):
    """The control for the true-length rule: a prefill that ran on to the
    prompt's pad leaves another row."""
    prompt = np.random.default_rng(n).integers(3, CFG.vocab_size, n).tolist()
    pad = -(-n // PS) * PS
    want = _ref(5, CFG, prompt, state_at=(n,))
    low = _ref(5, CFG, prompt, state_at=(n,),
               variant={"pad": pad, "pad_from": n})
    assert rel(low["row"], want["row"]) > 0.05
    assert rel(low["k"], want["k"]) < 1e-6   # the true positions' rows are kept


def test_bf16_programs_stay_within_a_stated_tolerance():
    """The same comparison in the type the cell serves. With ONE expert a
    token a flipped choice replaces the whole sublayer's output, so tokens
    are held to a fraction of a logit spread, a position's logits to 6 % at
    the median, and layer 0's keys, values and row (before any routing) to
    1.5 %."""
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    eng = _engine(cfg=cfg)
    assert all(c.dtype == jnp.bfloat16 for c in eng.cache)
    row = eng.free[1][0]
    drawn = jnp.asarray(eng.free[0][:eng._pages_of(49)[0]])
    prompts, outs = _serve(eng, [(40, 9)])
    gaps = _logit_gaps(5, cfg, prompts, outs)
    assert float(np.percentile(gaps, 50)) == 0.0 and float(gaps.max()) < 0.5
    seq = prompts[0] + outs[0][:-1]
    low = _ref(5, cfg, seq, state_at=(len(seq),))
    got = cca_moe_forward(W.make_params(W.seed_key(5), cfg, EOS),
                          jnp.asarray([seq]), cfg)[0].astype(jnp.float32)
    by_position = (jnp.linalg.norm(got - low["logits"], axis=-1)
                   / jnp.linalg.norm(low["logits"], axis=-1))
    assert float(jnp.median(by_position)) < 0.06
    kp, vp, rows = (a.astype(jnp.float32) for a in eng.cache)
    assert rel(rows[0, row], low["row"][0, 0]) < 0.015
    for pool, name in ((kp, "k"), (vp, "v")):
        mine = pool[0, drawn].reshape(-1, KVW)[:len(seq)]
        assert rel(mine, low[name][0]) < 0.015, name


@pytest.mark.parametrize("seed", SEEDS)
def test_forward_logits_match_the_plain_reference(seed):
    params = W.make_params(W.seed_key(seed), CFG, EOS)
    tokens = np.random.default_rng(seed % 1000).integers(3, CFG.vocab_size, 70)
    want = _ref(seed, CFG, tokens)
    got = cca_moe_forward(params, jnp.asarray(tokens)[None], CFG)[0]
    assert rel(got, want["logits"]) < 1e-5
    assert want["chosen"].shape == (L, 70) and want["p"].shape == (L, 70, 4)
    assert len(np.unique(np.asarray(want["chosen"]))) > 1
    assert not np.asarray(want["logits"][:, EOS]).any()   # the zeroed row


# ------------------------------------------------------------------ the router
def _router(E=4, R=6, gamma=0.5, bias=None):
    """A router whose matrices are identities: the scores are its stream's
    first E lanes through the norm and two gelus."""
    eye = jnp.eye(R)
    return {"down": eye, "gamma": jnp.float32(gamma),
            "norm": {"scale": jnp.ones((R,))}, "w1": eye,
            "b1": jnp.zeros((R,)), "w2": eye, "b2": jnp.zeros((R,)),
            "w3": eye[:, :E],
            "bias": jnp.zeros((E,)) if bias is None else jnp.asarray(bias)}


def test_the_carry_changes_the_next_layers_choice():
    """Layer 1's own input points at expert 1; what layer 0's router saw
    points at expert 2 more strongly, and at gamma 0.9 it wins: the router
    of layer l sees what the routers before it saw."""
    h0 = jnp.asarray([[0.0, 0.0, 3.0, 0.0, 0.0, 0.0]])
    h1 = jnp.asarray([[0.0, 2.0, 0.0, 0.0, 0.0, 0.0]])
    idx0, _, r0 = mlp_top1_route(h0, None, _router())
    assert int(idx0[0, 0]) == 2 and np.array_equal(r0, h0)
    alone, _, r1 = mlp_top1_route(h1, None, _router())
    assert int(alone[0, 0]) == 1 and np.array_equal(r1, h1)
    carried, _, r = mlp_top1_route(h1, r0, _router(gamma=0.9))
    assert int(carried[0, 0]) == 2
    np.testing.assert_allclose(r, h1 + 0.9 * h0)
    faint, _, _ = mlp_top1_route(h1, r0, _router(gamma=0.1))
    assert int(faint[0, 0]) == 1


def test_the_bias_chooses_and_never_weighs_and_ties_go_to_the_lower_index():
    h = jnp.asarray([[0.0, 2.0, 1.5, 0.0, 0.0, 0.0],
                     [1.0, 0.0, 0.0, 1.0, 0.0, 0.0]])
    idx, w, _ = mlp_top1_route(h, None, _router())
    assert idx[:, 0].tolist() == [1, 0]           # a tie: the lower index
    _, _, r = mlp_top1_route(h, None, _router())
    p = jax.nn.softmax(jax.nn.gelu(jax.nn.gelu(r * jax.lax.rsqrt(
        jnp.mean(r * r, -1, keepdims=True) + 1e-5)))[:, :4], axis=-1)
    np.testing.assert_allclose(w[:, 0], [p[0, 1], p[1, 0]], rtol=1e-6)
    biased, wb, _ = mlp_top1_route(h, None, _router(bias=[0, 0, 0.9, 0]))
    assert biased[:, 0].tolist() == [2, 2]        # the bias chose
    np.testing.assert_allclose(wb[:, 0], [p[0, 2], p[1, 2]], rtol=1e-6)
    assert idx.dtype == jnp.int32 and w.dtype == jnp.float32
    assert idx.shape == w.shape == (2, 1)


def test_the_program_router_is_the_references(served):
    """The program's router and the reference's on the same hidden states and
    the same stream of the layer before: the same expert, weight and stream."""
    layer = jax.tree.map(lambda a: a.astype(jnp.float32),
                         W.layer_from_seed(W.seed_key(5), CFG, 1)["moe"])
    h = jax.random.normal(jax.random.PRNGKey(9), (64, CFG.d_model))
    r_prev = jax.random.normal(jax.random.PRNGKey(8), (64, CFG.router_hidden))
    chosen, combine, r_ref, p = R.route(h, r_prev, layer["router"], CFG,
                                        "float32", {})
    idx, w, r = mlp_top1_route(h, r_prev, layer["router"], CFG.rms_norm_eps)
    assert np.array_equal(np.asarray(idx)[:, 0], np.asarray(chosen))
    mine = jnp.zeros_like(combine).at[jnp.arange(64)[:, None], idx].set(w)
    assert rel(mine, combine) < 1e-6 and rel(r, r_ref) < 1e-6
    assert not np.array_equal(np.asarray(chosen), np.argmax(np.asarray(p), -1))


def test_the_routers_stream_is_not_carried_from_one_step_to_the_next():
    """Two fused steps are two single steps, cache and tokens bit for bit:
    nothing but the pools and the last token passes from a step to the next,
    and the cache has no member for a stream."""
    eng = _engine()
    assert len(eng.cache) == 3
    B = eng.B
    rng = np.random.default_rng(4)
    kp, vp, rows = eng.cache
    start = (kp, vp, jnp.asarray(rng.normal(size=rows.shape), rows.dtype))
    tables = (jnp.asarray(rng.permutation(np.arange(1, 37)).reshape(B, 12),
                          jnp.int32), jnp.asarray([[2], [1], [3]], jnp.int32))
    args = (jnp.asarray([True, True, False]), jnp.zeros(B),
            jax.random.PRNGKey(0))

    def run(blocks):
        tok, pos = jnp.asarray([7, 9, 11], jnp.int32), jnp.asarray(
            [5, 8, 17], jnp.int32)
        cache, out = tuple(jnp.copy(a) for a in start), []
        for n in blocks:
            rows_, tok, pos, *cache = programs.cca_moe_decode_multi(
                eng.params, None, jnp.zeros(B, jnp.int32), tok, pos, tables,
                *cache, *args, cfg=CFG, n_steps=n)
            out.append(np.asarray(rows_)[:, :B])
        return np.concatenate(out), cache

    fused, c2 = run([2])
    single, c1 = run([1, 1])
    np.testing.assert_array_equal(fused, single)
    np.testing.assert_array_equal(c2[2], c1[2])
    # the dead slot's row (3) and the junk row (0) stay bit for bit
    np.testing.assert_array_equal(c2[2][:, [0, 3]], start[2][:, [0, 3]])
    assert not np.array_equal(c2[2][:, 1], start[2][:, 1])


# ------------------------------------------------------------------ the experts
def test_two_holders_parts_add_up_to_the_uncut_layer():
    """The chip's share of a deployment (model-configs guide, section 4):
    the experts of a layer divided over two holders; each routes over all
    of them and computes its own experts' part — a token's ONE expert is on
    one of the two — and the parts are the uncut reference's layer output."""
    key = W.layer_key(W.seed_key(5), 1)
    full = W.layer_from_seed(W.seed_key(5), CFG, 1)["moe"]
    h = jax.random.normal(jax.random.PRNGKey(1), (37, CFG.d_model))
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), full)
    zeros = jnp.zeros((37, CFG.router_hidden))
    want, chosen, _, _ = R.moe(f32, h, h, zeros, CFG, "float32")
    idx, w, _ = mlp_top1_route(h, None, full["router"], CFG.rms_norm_eps)
    total, loads = 0.0, []
    for lo, hi in ((0, 2), (2, 4)):
        cfg = dataclasses.replace(CFG, experts_held=(lo, hi))
        mine = {n: W.expert_stack(key, cfg, i)
                for i, n in enumerate(("w_gate", "w_up", "w_down"))}
        assert jnp.array_equal(mine["w_up"], full["experts"]["w_up"][lo:hi])
        part, load = routed_experts(h, idx, w, mine, cfg.held)
        ref_part, _, _, _ = R.moe({**f32, "experts": mine}, h, h, zeros, CFG,
                                  "float32", held=cfg.held)
        assert rel(part, ref_part) < 1e-5
        here = (np.asarray(chosen) >= lo) & (np.asarray(chosen) < hi)
        assert not np.asarray(part)[~here].any()   # the other holder's tokens
        total = total + part
        loads.append(load)
    assert rel(total, want) < 1e-5
    assert int(jnp.concatenate(loads).sum()) == h.shape[0]


@pytest.mark.parametrize("cell,rows,streams", [
    ("kanana2_gen_closed decode", 32 * 6, True),
    ("keyevl2_longctx_closed decode", 32 * 8, True),
    ("commandaplus_mixed_closed decode", 48 * 8, True),
    ("ling3flashvl_think_closed decode", 96 * 8, True),
    ("commandaplus_mixed_closed smallest prefill", 384 * 8, False),
    ("kanana2_gen_closed smallest prefill", 512 * 6, False),
    ("ling3flashvl_think_closed smallest prefill", 1024 * 8, False),
    ("zaya1_cot_closed decode", 80, True),
    ("zaya1_cot_closed smallest prefill", 256, True),
    ("zaya1_cot_closed a wave of 1,024 tokens", 1024, True),
    ("zaya1_cot_closed a wave of 2,048 tokens", 2048, False),
    ("zaya1_cot_closed largest prefill", 8192, False)])
def test_the_row_rule_at_one_expert_a_token(monkeypatch, cell, rows, streams):
    """``_streams_experts`` by the rows HANDED to the routed product. At one
    expert a token those are the tokens: a step and a wave of up to 1,024
    tokens stream their touched experts on a TPU, longer waves keep
    ``ragged_dot``; anywhere else nothing streams."""
    assert not moe._streams_experts(rows)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe._streams_experts(rows) == streams, cell


# ---------------------------------------------------------------- the counters
def _grown(before, after, name, tag=""):
    return (after[name].get(tag, {"sum": 0})["sum"]
            - before.get(name, {}).get(tag, {"sum": 0})["sum"])


def test_the_stats_column_and_read_counters_against_a_hand_count():
    """A request of 20 + 13 tokens: 12 decode steps (blocks 8 + 4) at lengths
    21..32. ``cca_row_updates``: one live slot x 3 layers a step. The read
    counters count the kv kind's positions only: the row kind holds none."""
    eng = _engine()
    assert eng.programs.stats == programs.MOE_STATS + ("cca_row_updates",)
    before = metrics.stage_totals()
    prompts, outs = _serve(eng, [(20, 13)])
    after = metrics.stage_totals()

    def grown(name, tag=""):
        return _grown(before, after, name, tag)

    steps = 12
    assert grown("rt_llm_cca_row_updates_total") == steps * L
    assert grown("rt_llm_moe_expert_slots_total") == steps * L * 4
    assert grown("rt_llm_moe_assignments_total") == steps * L   # one a token
    assert grown("rt_llm_moe_experts_touched_total") == steps * L
    assert grown("rt_llm_decode_kv_tokens_live_total") == sum(range(21, 33))
    assert grown("rt_llm_decode_kv_tokens_live_total", "kv") == sum(range(21, 33))
    assert grown("rt_llm_decode_kv_tokens_live_total", "row") == 0
    assert grown("rt_llm_decode_kv_tokens_read_total", "row") == 0
    assert grown("rt_llm_decode_kv_tokens_read_total") == steps * eng.B * eng.MAXP * PS
    assert grown("rt_llm_pages_drawn_total", "row") == 1
    assert grown("rt_llm_pages_drawn_total", "kv") == 5          # ceil(33 / 8)
    assert eng._last_stats["cca_row_updates"] == L
    assert {"moe_passes", "cca_row_updates"} <= set(eng._last_stats)


def test_the_kernels_interpreted_under_the_engine_give_the_same_tokens(monkeypatch):
    """The chip's branch without a chip: the K/V pools attended by
    ``paged_decode_attention``, interpreted, under the engine's own loop —
    the plain form's tokens and rows."""
    from ray_tpu.ops import paged_attention

    cases = [(13, 9), (21, 6)]
    plain = _engine(block_buckets=(4,))
    _, want = _serve(plain, cases)
    monkeypatch.setattr(programs, "_reads_in_place", lambda: True)
    monkeypatch.setattr(paged_attention, "_BLOCK_BYTES",
                        4 * PS * 2 * CFG.n_kv_heads * 128 * 4)
    monkeypatch.setattr(paged_attention, "_RUN_PAGES", 2)
    programs.cca_moe_decode_multi.clear_cache()
    try:
        eng = _engine(block_buckets=(4,))
        assert eng.programs.decode_in_place(eng.cache)
        _, got = _serve(eng, cases)
    finally:
        programs.cca_moe_decode_multi.clear_cache()
    assert got == want
    assert rel(eng.cache[2], plain.cache[2]) < 1e-5


def test_both_programs_name_the_new_part():
    """``mix`` is a part of the vocabulary, and both programs carry it
    beside the parts a layer had on their operations (what the part table
    joins a trace to)."""
    from ray_tpu.utils import tracing

    assert "mix" in tracing.PARTS
    eng = _engine()
    _serve(eng, [(20, 6)])
    parts = eng.program_parts()
    for program in ("jit_cca_moe_decode_multi", "jit_cca_moe_prefill_batch"):
        found = set(parts[program]["parts"].values())
        assert {"embed", "project", "mix", "kv_write", "attention", "attn_out",
                "router", "experts", "head", "sample"} <= found, (
                    program, sorted(found))
        assert found <= set(tracing.PARTS) | {tracing.SCAN, tracing.AMBIGUOUS}


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
    assert "'cca_moe'" in str(e.value)
    assert "one row of every layer" in str(e.value)   # what stands beside them

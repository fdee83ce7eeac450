"""The delta-rule + latent-attention + group-routed expert family
(``models/kda_moe.py``, ``llm/kda_moe.py``, ``ops/kda.py``, ``ops/mla.py``, the
group-limited router of ``parallel/moe.py``) against the benchmark's plain
float32 reference (``benchmarks/reference/kda_moe.py``, whose delta rule runs
one position at a time), at a tiny size that keeps the published shape's
ratios: 8 routing groups of which 4 are chosen, more experts a token than
groups chosen, a held group that is not group 0, two leading dense layers,
two periods of the pattern, four sub-blocks a chunk. CPU, float32, seeded
weights."""
import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import weights_kda_moe as W
from benchmarks.reference import kda_moe as R
from ray_tpu.llm import kda_moe as programs
from ray_tpu.llm.engine import (ContinuousBatchingEngine, UnsupportedByModel,
                                serving_programs)
from ray_tpu.models.kda_moe import (KDA, MLA, KdaMoeConfig, kda_moe_forward,
                                    kda_moe_init)
from ray_tpu.parallel import moe
from ray_tpu.parallel.moe import (routed_experts, sigmoid_topk_route,
                                  tokens_here)
from ray_tpu.utils import metrics

CFG = KdaMoeConfig.tiny(experts_held=(8, 12), vocab_held=(256, 512))
PS = 8
SEEDS = [3, 2**31 + 7]
N_K, N_A = len(CFG.layers_of(KDA)), len(CFG.layers_of(MLA))


def rel(a, b):
    return float(jnp.linalg.norm(jnp.asarray(a) - jnp.asarray(b))
                 / jnp.linalg.norm(jnp.asarray(b)))


def test_tiny_keeps_the_published_shape():
    full = KdaMoeConfig()
    assert (full.n_layers, len(full.layers_of(KDA)), len(full.layers_of(MLA)),
            full.n_moe_layers) == (42, 35, 7, 40)
    assert full.layers_of(MLA) == (5, 11, 17, 23, 29, 35, 41)
    assert (full.d_inner, full.conv_width, full.latent_width) == (4096, 12288, 576)
    assert CFG.layers_of(MLA) == (2, 5) and CFG.first_dense_layers == 2
    assert (full.n_group, full.topk_group) == (CFG.n_group, CFG.topk_group) == (8, 4)
    assert full.n_experts_per_tok > full.topk_group
    assert CFG.n_experts_per_tok > CFG.topk_group
    assert CFG.qk_rope_head_dim < CFG.qk_nope_head_dim
    assert CFG.chunk_size // CFG.sub_chunk == full.chunk_size // full.sub_chunk == 4
    assert CFG.held == (8, 12) and CFG.held[0] // (CFG.n_experts // CFG.n_group) == 2
    with pytest.raises(ValueError, match="leave float32"):
        KdaMoeConfig.tiny(sub_chunk=4, chunk_size=8, kda_lower_bound=-25.0)
    params = kda_moe_init(jax.random.PRNGKey(0), CFG)
    seeded = W.make_params(W.seed_key(0), CFG)
    assert (jax.tree.map(lambda x: (x.shape, x.dtype), params)
            == jax.tree.map(lambda x: (x.shape, x.dtype), seeded))
    assert set(params["layers_0"]) == {
        "attn_norm", "ffn_norm", "in_proj", "conv", "A_log", "a_bias",
        "o_norm", "wo", "w_gate", "w_up", "w_down"}
    assert set(params["layers_2"]) == {
        "attn_norm", "ffn_norm", "wq", "wkv_a", "kv_norm", "wkv_b", "wg", "wo",
        "moe"}
    assert set(params["layers_3"]["moe"]["experts"]) == {"w_gate", "w_up", "w_down"}
    assert serving_programs(CFG) is programs.PROGRAMS
    latent, state = programs.page_kinds(CFG, PS, 96)
    assert (latent.name, latent.layers, latent.table, latent.positions) == (
        "latent", N_A, 12, True)
    assert (state.name, state.layers, state.table, state.positions) == (
        "state", N_K, 1, False)


# ------------------------------------------------- the engine and the reference
def _engine(seed=5, cfg=CFG, **kw):
    params = W.make_params(W.seed_key(seed), cfg)
    kw = {"max_batch": 3, "page_size": PS, "max_seq_len": 96,
          "n_pages": {"latent": 41, "state": 4}, "eos_id": None,
          "block_buckets": (4, 8), **kw}
    return ContinuousBatchingEngine(params, cfg, **kw)


# prompts that fill neither a page nor a chunk, one of several chunks, one
# shorter than the convolution's 3 saved inputs; decode steps cross pages
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
def test_prefill_then_decode_through_pages_and_state_rows_is_the_reference(eos_id):
    eng = _engine(eos_id=eos_id)
    prompts, outs = _serve(eng, CASES)
    assert [len(o) for o in outs] == [m for _, m in CASES]
    assert float(_logit_gaps(5, CFG, prompts, outs).max()) == 0.0
    # every page and every state row back
    assert (len(eng.free[0]), len(eng.free[1])) == (40, 3)
    held = metrics.stage_totals()["rt_llm_pages_held"]
    assert held["state"]["sum"] == 0 and held["latent"]["sum"] == 0


@pytest.mark.parametrize("n,m", [(10, 13), (21, 5), (2, 13)])
def test_the_cache_holds_the_references_rows_and_state(n, m):
    """The MLA layers' latent rows, and every KDA layer's state and conv
    row, after a request whose decode steps end with its last token (blocks
    8 + 4, or 4): the state stands after ``n + m - 1`` positions."""
    eng = _engine()
    prompt = np.random.default_rng(1).integers(3, CFG.vocab_size, n).tolist()
    drawn = jnp.asarray(eng.free[0][:eng._pages_of(n + m)[0]])
    row = eng.free[1][0]
    _, (out,) = _serve_one(eng, prompt, m)
    assert eng.steps == m - 1
    rows = n + m - 1
    want = R.forward(5, CFG, prompt + out[:-1], q_block=32, state_at=(rows,))
    pool, states, convs = eng.cache
    assert states.shape == (N_K, 4, 4, 16, 16) and states.dtype == jnp.float32
    assert convs.shape == (N_K, 4, 3 * CFG.conv_width)
    got = pool[:, drawn].reshape(N_A, -1, CFG.latent_width)
    assert rel(got[:, :rows], want["rows"][:, :rows]) < 1e-5
    assert rel(states[:, row], want["state"][:, 0]) < 1e-5
    assert rel(convs[:, row].reshape(N_K, 3, -1), want["conv"][:, 0]) < 1e-5
    # the other rows, the junk row among them, belong to nobody
    assert not np.asarray(states[:, [r for r in range(1, 4) if r != row]]).any()


# ----------------------------------------------------- padding, rows, the wave
@pytest.mark.parametrize("lens", [[1, 2, 3], [5, 17], [7, 8, 9, 16]])
def test_a_padded_prompt_leaves_the_state_of_its_true_length(lens):
    """One prefill wave as the engine builds it: prompts padded to whole
    pages (page 8 = chunk 8 here, the scan pads again inside), the wave to a
    bucket of 4 with dummy rows whose pages and row are the junk ones. Each
    prompt's state and conv row are the reference's after its TRUE length —
    zeros in the conv row where the prompt is shorter than 3 — and the junk
    row takes the dummies'."""
    pad = -(-max(lens) // PS) * PS
    rng = np.random.default_rng(sum(lens))
    prompts = [rng.integers(3, CFG.vocab_size, n).tolist() for n in lens]
    params = W.make_params(W.seed_key(5), CFG)
    cache = programs.make_pools(CFG, PS, {"latent": 20, "state": 6}, None)
    # a non-zero row planted where a released slot left it: never read
    cache = (cache[0], cache[1] + 7.0, cache[2] + 7.0)
    toks = np.zeros((4, pad), np.int32)
    pages = np.zeros((4, pad // PS), np.int32)
    rows = np.zeros((4, 1), np.int32)
    true_lens = np.ones(4, np.int32)
    for j, p in enumerate(prompts):
        toks[j, :len(p)] = p
        pages[j] = 1 + j * (pad // PS) + np.arange(pad // PS)
        rows[j], true_lens[j] = 1 + j, len(p)
    first, pool, states, convs = programs.kda_moe_prefill_batch(
        params, None, jnp.zeros(4, jnp.int32), jnp.asarray(toks),
        (jnp.asarray(pages), jnp.asarray(rows)), *cache,
        jnp.asarray(true_lens), jnp.zeros(4), jax.random.PRNGKey(0), CFG)
    for j, p in enumerate(prompts):
        want = R.forward(5, CFG, p, q_block=32, state_at=(len(p),),
                         logits_from=len(p) - 1)
        assert rel(states[:, 1 + j], want["state"][:, 0]) < 1e-5, len(p)
        got = convs[:, 1 + j].reshape(N_K, 3, -1)
        assert float(jnp.abs(got - want["conv"][:, 0]).max()) < 1e-5, len(p)
        if len(p) < 3:
            assert not np.asarray(got[:, :3 - len(p)]).any()
        mine = pool[:, jnp.asarray(pages[j])].reshape(N_A, -1, CFG.latent_width)
        assert rel(mine[:, :len(p)], want["rows"]) < 1e-5
        assert int(first[j]) == int(jnp.argmax(want["logits"][0]))
    # rows nobody drew keep what was planted; the junk row took the dummies'
    untouched = [r for r in range(1, 6) if r > len(lens)]
    assert np.all(np.asarray(states[:, untouched]) == 7.0)


def test_a_dead_slot_and_the_junk_row_never_reach_a_live_slot():
    """Decode with one live slot of three: the dead slots' steps go to the
    junk row (planted non-zero, as every other row) — its conv row stays,
    its state takes ``beta`` 0 at decay 1 and stays bit for bit —
    the live slot's row is the reference's, and rows nobody holds keep what
    was planted."""
    eng = _engine()
    pool, states, convs = eng.cache
    eng.cache = (pool, states + 3.0, convs + 3.0)
    prompt = np.random.default_rng(2).integers(3, CFG.vocab_size, 12).tolist()
    row = eng.free[1][0]
    prompts, outs = _serve_one(eng, prompt, 13)
    assert float(_logit_gaps(5, CFG, prompts, outs).max()) == 0.0
    want = R.forward(5, CFG, prompt + outs[0][:-1], q_block=32, state_at=(24,))
    assert rel(eng.cache[1][:, row], want["state"][:, 0]) < 1e-5
    others = [r for r in range(1, 4) if r != row]
    assert np.all(np.asarray(eng.cache[1][:, others]) == 3.0)
    assert np.all(np.asarray(eng.cache[1][:, 0]) == 3.0)       # unchanged
    assert np.all(np.asarray(eng.cache[2][:, others]) == 3.0)
    assert np.all(np.asarray(eng.cache[2][:, 0]) == 3.0)       # junk: no live slot's


def test_a_slot_reused_after_a_release_sees_none_of_the_old_state():
    """Two rows for five requests: every row is drawn again after a release
    with its last holder's state in it, and every request is the
    reference's."""
    eng = _engine(n_pages={"latent": 41, "state": 3}, max_batch=2)
    cases = [(9, 6), (17, 5), (3, 9), (24, 4), (11, 5)]
    prompts, outs = _serve(eng, cases)
    assert float(_logit_gaps(5, CFG, prompts, outs).max()) == 0.0
    drawn = metrics.stage_totals()["rt_llm_pages_drawn_total"]["state"]["sum"]
    assert drawn >= 5 and len(eng.free[1]) == 2


@pytest.mark.parametrize("n_pages,free", [
    ({"latent": 13, "state": 4}, (12, 3)),    # pages run out first
    ({"latent": 41, "state": 2}, (40, 1))])   # the one state row does
def test_admission_waits_for_whichever_kind_runs_out(n_pages, free):
    """Too few latent pages, or one state row, for all at once: the head of
    the queue waits for what it lacks, the ones behind it wait for it, every
    request finishes with the reference's tokens and every page and row
    comes back."""
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
    eng = _engine(max_batch=8, n_pages={"latent": 200, "state": 9})
    assert eng.programs.prefill_wave_limit == (8, 16384)
    assert [len(w) for w in eng._split_wave(2048, [0] * 8)] == [8]
    assert [len(w) for w in eng._split_wave(4096, [0] * 7)] == [4, 3]
    assert [len(w) for w in eng._split_wave(1024, [0] * 8)] == [8]


# ---------------------------------------------------------------- the controls
CONTROLS = {
    "no decay": {"decay": "none"}, "a decay a head": {"decay": "head"},
    "no delta term": {"delta": False}, "beta one": {"beta": "one"},
    "q, k not normalised": {"qknorm": False},
    "no silu after the convolution": {"silu": False},
    "the gate before the norm": {"gate": "before"},
    "a bf16 state": {"state": "bfloat16"},
    "no group chosen": {"groups": "none"},
    "a group's score its largest alone": {"groups": "max"},
    "the bias in the weights": {"bias": "weights"},
    "no rotation": {"rope": False}}


@pytest.mark.parametrize("name", list(CONTROLS))
def test_a_reference_with_other_mathematics_fails_the_comparison(served, name):
    """The controls: each is a forward pass whose cache rows are not the
    program's — the state where the recurrence changed, the first MLA
    layer's rows behind it where the mixer's output did, the last MLA
    layer's rows (behind the expert layers) where the routing did."""
    variant = CONTROLS[name]
    prompts, outs = served
    seq = prompts[1] + outs[1][:-1]
    want = R.forward(5, CFG, seq, q_block=32, state_at=(len(seq),))
    low = R.forward(5, CFG, seq, q_block=32, state_at=(len(seq),),
                    variant=variant)
    assert max(rel(low[n], want[n]) for n in ("state", "rows")) > 2e-3
    if "state" not in variant:  # by a wide margin, and the tokens say so too
        assert rel(low["rows"][-1], want["rows"][-1]) > 0.02
        assert float(_logit_gaps(5, CFG, prompts, outs, variant=variant).max()) > (
            0.01 if "rope" in variant else 0.05)


@pytest.mark.parametrize("n", [5, 17])
def test_pad_positions_advancing_the_state_fail_the_comparison(n):
    """The control for the true-length rule: a prefill that ran on to the
    prompt's pad leaves another state and other conv rows."""
    prompt = np.random.default_rng(n).integers(3, CFG.vocab_size, n).tolist()
    pad = -(-n // PS) * PS
    want = R.forward(5, CFG, prompt, q_block=32, state_at=(n,))
    low = R.forward(5, CFG, prompt, q_block=32, state_at=(n,),
                    variant={"pad": pad, "pad_from": n})
    assert rel(low["state"], want["state"]) > 0.05
    assert rel(low["conv"], want["conv"]) > 0.05
    assert rel(low["rows"], want["rows"]) < 1e-6   # the true positions' rows are kept


def test_bf16_programs_stay_within_a_stated_tolerance():
    """The same comparison in the type the cell serves. Near-tied expert
    choices flip between bf16 and float32, and a flipped position carries
    another expert's output: tokens are held to a fraction of a logit spread,
    a position's logits to 5 % at the median and 20 % over all, and layer
    0's state (before any routing; float32 in the pool whatever the model's
    type) to 1 %."""
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    eng = _engine(cfg=cfg)
    assert eng.cache[1].dtype == jnp.float32 and eng.cache[2].dtype == jnp.bfloat16
    assert eng.cache[0].dtype == jnp.bfloat16
    row = eng.free[1][0]
    prompts, outs = _serve(eng, [(40, 9)])
    gaps = _logit_gaps(5, cfg, prompts, outs)
    assert float(np.percentile(gaps, 50)) == 0.0 and float(gaps.max()) < 0.5
    seq = prompts[0] + outs[0][:-1]
    low = R.forward(5, cfg, seq, q_block=32, state_at=(len(seq),))
    want = kda_moe_forward(W.make_params(W.seed_key(5), cfg),
                           jnp.asarray([seq]), cfg)
    got = want[0].astype(jnp.float32)
    by_position = (jnp.linalg.norm(got - low["logits"], axis=-1)
                   / jnp.linalg.norm(low["logits"], axis=-1))
    assert float(jnp.median(by_position)) < 0.05
    assert rel(got, low["logits"]) < 0.2
    assert rel(eng.cache[1][0, row], low["state"][0, 0]) < 0.01


@pytest.mark.parametrize("seed", SEEDS)
def test_forward_logits_match_the_plain_reference(seed):
    params = W.make_params(W.seed_key(seed), CFG)
    tokens = np.random.default_rng(seed % 1000).integers(3, CFG.vocab_size, 70)
    want = R.forward(seed, CFG, tokens, q_block=32)
    got = kda_moe_forward(params, jnp.asarray(tokens)[None], CFG)[0]
    assert rel(got, want["logits"]) < 1e-5
    assert want["chosen"].shape == (4, 70, CFG.n_experts_per_tok)
    # every token's experts lie inside topk_group routing groups
    groups = np.asarray(want["chosen"]) // (CFG.n_experts // CFG.n_group)
    assert max(len(set(g)) for g in groups.reshape(-1, CFG.n_experts_per_tok)
               ) <= CFG.topk_group


# ------------------------------------------------------------------ the router
def _route(scores, bias=None, k=3, n_group=4, topk_group=2, norm=True, scale=1.0):
    """The router on hand-set scores: logit(score) through an identity."""
    s = jnp.asarray(scores, jnp.float32)
    h = jnp.log(s / (1 - s))
    return sigmoid_topk_route(h, jnp.eye(s.shape[1]), bias, k, scale, norm,
                              n_group, topk_group)


def test_a_token_whose_largest_scores_lie_in_more_groups_loses_the_rest():
    """8 experts in 4 groups of 2, 2 groups kept, 3 experts a token. The three
    largest scores lie in groups 0, 1 and 3; the groups' top-2 sums are 1.0,
    0.85, 0.3, 0.8: groups 0 and 1 stay, expert 6 (0.7, the third largest) is
    lost and expert 1 (0.1)... is not taken either: the third is expert 3."""
    scores = [[0.9, 0.1, 0.8, 0.05, 0.2, 0.1, 0.7, 0.1]]
    idx, w = _route(scores)
    assert sorted(np.asarray(idx)[0].tolist()) == [0, 1, 2]
    idx, w = _route([[0.9, 0.1, 0.8, 0.15, 0.2, 0.1, 0.7, 0.1]])
    assert np.asarray(idx)[0].tolist() == [0, 2, 3]
    np.testing.assert_allclose(np.asarray(w)[0], np.array([0.9, 0.8, 0.15]) / 1.85,
                               rtol=1e-5)
    # with no group chosen the third is expert 6
    assert np.asarray(_route(scores, n_group=1, topk_group=1)[0])[0].tolist() == [0, 2, 6]


def test_a_groups_score_is_the_sum_of_its_two_largest():
    """Group 1 holds the single largest score and nothing else; groups 0 and
    2 hold two middling ones each and win on their sums."""
    scores = [[0.5, 0.45, 0.6, 0.01, 0.4, 0.35, 0.1, 0.1]]
    idx, _ = _route(scores, k=4)
    assert sorted(np.asarray(idx)[0].tolist()) == [0, 1, 4, 5]


def test_equal_group_scores_go_to_the_lower_group_and_equal_experts_too():
    scores = [[0.5, 0.25, 0.25, 0.5, 0.5, 0.25, 0.5, 0.25]]
    idx, _ = _route(scores, k=3)
    assert np.asarray(idx)[0].tolist() == [0, 3, 1]   # groups 0 and 1; 1 before 2


def test_the_bias_chooses_groups_and_experts_and_never_weighs():
    scores = [[0.5, 0.4, 0.45, 0.44, 0.3, 0.3, 0.2, 0.2]]
    bias = jnp.asarray([0, 0, 0, 0, 0, 0, 0.5, 0.5], jnp.float32)
    idx, w = _route(scores, bias=bias, k=2, norm=False, scale=2.5)
    # groups 3 (0.2 + 0.5 twice = 1.4) and 0 (0.9) stay; the two of group 3
    # are the largest biased scores, and weigh by their own 0.2
    assert sorted(np.asarray(idx)[0].tolist()) == [6, 7]
    np.testing.assert_allclose(np.asarray(w)[0], [0.5, 0.5], rtol=1e-5)


def test_one_group_is_todays_router_bit_for_bit():
    """``n_group`` 1 takes no other path than before the groups: the same
    operations in the same order, and so the same lowered text as a router
    with no such argument."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(ks[0], (37, 16))
    w = jax.random.normal(ks[1], (16, 32))
    b = 0.1 * jax.random.normal(ks[2], (32,))

    def before(h, router_w, bias, k, scale, norm=True):  # PR 43's, verbatim
        s = jax.nn.sigmoid(jnp.matmul(
            h.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, idx = jax.lax.top_k(
            s if bias is None else s + bias.astype(jnp.float32), k)
        ws = jnp.take_along_axis(s, idx, axis=-1)
        if norm:
            ws = ws / (jnp.sum(ws, axis=-1, keepdims=True) + 1e-20)
        return idx.astype(jnp.int32), ws * scale

    for bias in (b, None):
        want = before(h, w, bias, 6, 2.5)
        got = sigmoid_topk_route(h, w, bias, 6, 2.5, True, 1, 1)
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])
    text = [jax.jit(f).lower(h, w, b).as_text() for f in (
        lambda h, w, b: before(h, w, b, 6, 2.5),
        lambda h, w, b: sigmoid_topk_route(h, w, b, 6, 2.5))]
    assert text[0] == text[1]


def test_the_program_router_is_the_references(served):
    """Group-limited choices of the program's router and the reference's own
    (k rounds of the largest left over sorted group sums) on the same hidden
    states: the same experts, the same weights."""
    layer = jax.tree.map(lambda a: a.astype(jnp.float32),
                         W.layer_from_seed(W.seed_key(5), CFG, 3)["moe"])
    h = jax.random.normal(jax.random.PRNGKey(9), (64, CFG.d_model))
    chosen, combine = R.route(h, layer["router"], CFG, "float32")
    idx, w = sigmoid_topk_route(
        h, layer["router"]["kernel"], layer["router"]["bias"],
        CFG.n_experts_per_tok, CFG.routed_scaling_factor, True, CFG.n_group,
        CFG.topk_group)
    assert np.array_equal(np.sort(np.asarray(idx)), np.sort(np.asarray(chosen)))
    mine = jnp.zeros_like(combine).at[jnp.arange(64)[:, None], idx].set(w)
    assert rel(mine, combine) < 1e-6
    held = tokens_here(idx, CFG.held)
    assert int(held) == int(((np.asarray(chosen) >= 8)
                             & (np.asarray(chosen) < 12)).any(-1).sum())


# ------------------------------------------------------------------ the experts
@pytest.mark.parametrize("holders", [8, 2])
def test_holders_parts_add_up_to_the_uncut_layer(holders):
    """The chip's share of a deployment (model-configs guide, section 4): the
    32 experts of a layer divided over ``holders`` — 8: one routing group a
    holder, as the stated deployment; each routes over all of them inside
    the token's groups and computes its own experts' part, and every holder
    computes the shared expert alike — counted ONCE, the parts are the uncut
    reference's layer output; a token reaches at most ``topk_group`` of the
    8 holders."""
    whole = dataclasses.replace(CFG, experts_held=None)
    key = W.layer_key(W.seed_key(5), 3)
    full = W.layer_from_seed(W.seed_key(5), whole, 3)["moe"]
    h = jax.random.normal(jax.random.PRNGKey(1), (37, CFG.d_model))
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), full)
    want, _ = R.moe(f32, h, whole, "float32")
    shared_only = want - R.moe(f32, h, whole, "float32", shared=False)[0]
    per = CFG.n_experts // holders
    total, loads, reached = shared_only, [], np.zeros(37, int)
    for r in range(holders):
        cfg = dataclasses.replace(CFG, experts_held=(r * per, (r + 1) * per))
        mine = {n: W.expert_stack(key, cfg, i)
                for i, n in enumerate(("w_gate", "w_up", "w_down"))}
        assert jnp.array_equal(mine["w_up"],
                               full["experts"]["w_up"][r * per:(r + 1) * per])
        idx, w = sigmoid_topk_route(
            h, full["router"]["kernel"], full["router"]["bias"],
            cfg.n_experts_per_tok, cfg.routed_scaling_factor, True,
            cfg.n_group, cfg.topk_group)
        part, load = routed_experts(h, idx, w, mine, cfg.held)
        # the reference's own share of the same holder, shared expert apart
        ref_part, _ = R.moe({**f32, "experts": jax.tree.map(
            lambda a: a.astype(jnp.float32), mine)}, h, whole, "float32",
            held=cfg.held, shared=False)
        assert rel(part, ref_part) < 1e-5
        total = total + part
        loads.append(load)
        reached += np.asarray(((idx >= cfg.held[0]) & (idx < cfg.held[1])).any(-1))
    assert rel(total, want) < 1e-5
    assert int(jnp.concatenate(loads).sum()) == h.shape[0] * CFG.n_experts_per_tok
    if holders == CFG.n_group:
        assert reached.max() <= CFG.topk_group


@pytest.mark.parametrize("cell,rows,streams", [
    ("kanana2_gen_closed decode", 32 * 6, True),
    ("keyevl2_longctx_closed decode", 32 * 8, True),
    ("commandaplus_mixed_closed decode", 48 * 8, True),
    ("ling3flashvl_think_closed decode", 96 * 8, True),
    ("commandaplus_mixed_closed smallest prefill", 384 * 8, False),
    ("kanana2_gen_closed smallest prefill", 512 * 6, False),
    ("ling3flashvl_think_closed smallest prefill", 1024 * 8, False)])
def test_the_row_rule_at_every_cells_rows(monkeypatch, cell, rows, streams):
    """``_streams_experts`` by the rows HANDED to the routed product: every
    decode step streams its touched experts on a TPU, every prefill program
    keeps ``ragged_dot``; anywhere else nothing streams."""
    assert not moe._streams_experts(rows)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe._streams_experts(rows) == streams, cell


# ---------------------------------------------------------------- the counters
def _grown(before, after, name, tag=""):
    return (after[name].get(tag, {"sum": 0})["sum"]
            - before.get(name, {}).get(tag, {"sum": 0})["sum"])


def test_the_stats_column_and_read_counters_against_a_hand_count():
    """A request of 20 + 13 tokens: 12 decode steps (blocks 8 + 4) at
    lengths 21..32. ``delta_updates``: one live slot x 4 KDA layers a step.
    ``moe_tokens_here``: of one live token x 4 expert layers a step, those
    that chose a held expert. The read counters count the latent kind's
    positions only: the state kind holds none."""
    eng = _engine()
    assert eng.programs.stats == programs.MOE_STATS + (
        "delta_updates", "moe_tokens_here")
    before = metrics.stage_totals()
    prompts, outs = _serve(eng, [(20, 13)])
    after = metrics.stage_totals()

    def grown(name, tag=""):
        return _grown(before, after, name, tag)

    steps = 12
    assert grown("rt_llm_delta_state_updates_total") == steps * N_K
    assert grown("rt_llm_moe_expert_slots_total") == steps * 4 * 4
    chosen = np.asarray(R.forward(5, CFG, prompts[0] + outs[0][:-1],
                                  q_block=32)["chosen"])[:, 20:]
    here = ((chosen >= 8) & (chosen < 12))
    assert grown("rt_llm_moe_tokens_here_total") == int(here.any(-1).sum())
    assert grown("rt_llm_moe_assignments_total") == int(here.sum())
    assert 0 < grown("rt_llm_moe_tokens_here_total") <= steps * 4
    assert grown("rt_llm_decode_kv_tokens_live_total") == sum(range(21, 33))
    assert grown("rt_llm_decode_kv_tokens_live_total", "latent") == sum(range(21, 33))
    assert grown("rt_llm_decode_kv_tokens_live_total", "state") == 0
    assert grown("rt_llm_decode_kv_tokens_read_total", "state") == 0
    assert grown("rt_llm_decode_kv_tokens_read_total") == steps * eng.B * eng.MAXP * PS
    assert grown("rt_llm_pages_drawn_total", "state") == 1
    assert grown("rt_llm_pages_drawn_total", "latent") == 5          # ceil(33 / 8)
    assert eng._last_stats["delta_updates"] == N_K
    assert {"moe_passes", "delta_updates", "moe_tokens_here"} <= set(eng._last_stats)


def test_the_kernels_interpreted_under_the_engine_give_the_same_tokens(monkeypatch):
    """The chip's branch without a chip: the state pool advanced by
    ``kda_pool_step`` and the latent pool attended by
    ``paged_latent_attention``, both interpreted, under the engine's own
    loop — the plain form's tokens and states."""
    cases = [(13, 9), (21, 6)]
    plain = _engine(block_buckets=(4,))
    _, want = _serve(plain, cases)
    monkeypatch.setattr(programs, "_reads_in_place", lambda: True)
    programs.kda_moe_decode_multi.clear_cache()
    try:
        eng = _engine(block_buckets=(4,))
        assert eng.programs.decode_in_place(eng.cache)
        _, got = _serve(eng, cases)
    finally:
        programs.kda_moe_decode_multi.clear_cache()
    assert got == want
    assert rel(eng.cache[1], plain.cache[1]) < 1e-5


def test_both_programs_name_the_new_part():
    """``delta`` is a part of the vocabulary, and both programs carry it,
    ``conv`` and the MLA layers' parts on their operations (what the part
    table joins a trace to)."""
    from ray_tpu.utils import tracing

    assert "delta" in tracing.PARTS
    eng, _ = _engine(), None
    _serve(eng, [(20, 6)])
    parts = eng.program_parts()
    for program in ("jit_kda_moe_decode_multi", "jit_kda_moe_prefill_batch"):
        found = set(parts[program]["parts"].values())
        assert {"embed", "project", "conv", "delta", "kv_write", "attention",
                "attn_out", "ffn", "router", "experts", "head", "sample"
                } <= found, (program, sorted(found))
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
    assert "'kda_moe'" in str(e.value)
    assert "one state row" in str(e.value)   # what it caches instead

"""The state-space + attention + ungated-expert family (``models/ssm_moe.py``,
``llm/ssm_moe.py``, ``ops/ssm.py``, the two-matrix experts of
``parallel/moe.py``, a ``PageKind`` that holds no positions) against the
benchmark's plain float32 reference (``benchmarks/reference/ssm_moe.py``,
whose recurrence runs one position at a time), at a tiny size that keeps the
published shape's ratios: 8 Mamba-2 heads a group, a shared expert twice a
routed one's width, one period of the pattern (``MEM*EME``) and a chunk of 8
far under the context. CPU, float32, seeded weights."""
import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import weights_ssm_moe as W
from benchmarks.reference import ssm_moe as R
from ray_tpu.llm import ssm_moe as programs
from ray_tpu.llm.engine import (ContinuousBatchingEngine, UnsupportedByModel,
                                serving_programs)
from ray_tpu.models.ssm_moe import (ATTENTION, EXPERTS, MAMBA, SsmMoeConfig,
                                    mamba_decay, mamba_dt, mamba_in,
                                    split_conv, ssm_moe_forward, ssm_moe_init)
from ray_tpu.ops import ssm
from ray_tpu.ops.paged_attention import paged_decode_attention
from ray_tpu.ops.ssm_pool import ssm_pool_step
from ray_tpu.parallel import moe
from ray_tpu.parallel.moe import (expert_passes, routed_experts,
                                  sigmoid_topk_route)
from ray_tpu.utils import metrics

CFG = SsmMoeConfig.tiny(experts_held=(4, 12), vocab_held=(256, 512))
PS = 8
SEEDS = [3, 2**31 + 7]
N_M = len(CFG.blocks_of(MAMBA))


def rel(a, b):
    return float(jnp.linalg.norm(jnp.asarray(a) - jnp.asarray(b))
                 / jnp.linalg.norm(jnp.asarray(b)))


def test_tiny_keeps_the_published_shape():
    full = SsmMoeConfig()
    assert (full.n_layers, len(full.blocks_of(MAMBA)),
            len(full.blocks_of(EXPERTS)), len(full.blocks_of(ATTENTION))
            ) == (52, 23, 23, 6)
    assert (full.d_inner, full.conv_width) == (4096, 6144)
    assert full.mamba_heads // full.n_groups == CFG.mamba_heads // CFG.n_groups == 8
    assert full.d_shared // full.d_expert == CFG.d_shared // CFG.d_expert == 2
    assert CFG.held == (4, 12) and CFG.vocab_size == 256
    assert set(CFG.pattern) == {MAMBA, EXPERTS, ATTENTION}
    with pytest.raises(ValueError, match="pattern"):
        SsmMoeConfig.tiny(pattern="MEX")
    params = ssm_moe_init(jax.random.PRNGKey(0), CFG)
    seeded = W.make_params(W.seed_key(0), CFG)
    assert (jax.tree.map(lambda x: (x.shape, x.dtype), params)
            == jax.tree.map(lambda x: (x.shape, x.dtype), seeded))
    # ONE mixer a block, and two matrices an expert, routed and shared alike
    assert set(params["layers_0"]) == {"norm", "in_proj", "conv", "A_log",
                                       "dt_bias", "D", "gate_norm", "out_proj"}
    assert set(params["layers_1"]) == {"norm", "moe"}
    assert set(params["layers_1"]["moe"]["experts"]) == {"w_up", "w_down"}
    assert set(params["layers_1"]["moe"]["shared"]) == {"w_up", "w_down"}
    assert serving_programs(CFG) is programs.PROGRAMS
    kv, state = programs.page_kinds(CFG, PS, 96)
    assert (kv.name, kv.layers, kv.table, kv.positions) == ("kv", 1, 12, True)
    assert (state.name, state.layers, state.table, state.positions) == (
        "state", N_M, 1, False)


# ------------------------------------------------- the engine and the reference
def _engine(seed=5, cfg=CFG, **kw):
    params = W.make_params(W.seed_key(seed), cfg)
    kw = {"max_batch": 3, "page_size": PS, "max_seq_len": 96,
          "n_pages": {"kv": 41, "state": 4}, "eos_id": None,
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
    assert held["state"]["sum"] == 0 and held["kv"]["sum"] == 0


@pytest.mark.parametrize("n,m", [(10, 13), (21, 5), (2, 13)])
def test_the_cache_holds_the_references_rows_and_state(n, m):
    """K and V of the attention block, and every Mamba-2 block's state and
    conv row, after a request whose decode steps end with its last token
    (blocks 8 + 4, or 4): the state stands after ``n + m - 1`` positions."""
    eng = _engine()
    prompt = np.random.default_rng(1).integers(3, CFG.vocab_size, n).tolist()
    drawn = jnp.asarray(eng.free[0][:eng._pages_of(n + m)[0]])
    row = eng.free[1][0]
    _, (out,) = _serve_one(eng, prompt, m)
    assert eng.steps == m - 1
    rows = n + m - 1
    want = R.forward(5, CFG, prompt + out[:-1], q_block=32, state_at=(rows,))
    kp, vp, states, convs = eng.cache
    assert states.shape == (N_M, 4, 16, 8, 16) and states.dtype == jnp.float32
    assert convs.shape == (N_M, 4, 3 * CFG.conv_width)
    for name, pool in (("k", kp), ("v", vp)):
        got = pool[:, drawn].reshape(1, -1, want[name].shape[-1])
        assert rel(got[:, :rows], want[name][:, :rows]) < 1e-5, name
    assert rel(states[:, row], want["state"][:, 0]) < 1e-5
    assert rel(convs[:, row].reshape(N_M, 3, -1), want["conv"][:, 0]) < 1e-5
    # the other rows, the junk row among them, belong to nobody
    assert not np.asarray(states[:, [r for r in range(1, 4) if r != row]]).any()


def _serve_one(eng, prompt, m):
    async def run():
        await eng.start()
        out = await asyncio.wait_for(eng.generate(prompt, max_tokens=m), 240)
        await eng.stop()
        return [prompt], [out]

    return asyncio.run(run())


# ------------------------------------------------------------------ the scan
def _scan_inputs(T, seed=0, N=2):
    H, P, G, S = CFG.mamba_heads, CFG.mamba_head_dim, CFG.n_groups, CFG.ssm_state
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (N, T, H, P))
    dt = jax.random.uniform(k[1], (N, T, H), jnp.float32, 1e-3, 0.3)
    A = -jax.random.uniform(k[2], (H,), jnp.float32, 1.0, 16.0)
    Bm = jax.random.normal(k[3], (N, T, G, S))
    Cm = jax.random.normal(k[4], (N, T, G, S))
    D = jax.random.normal(k[5], (H,))
    return x, dt, A, Bm, Cm, D


def _one_step_scan(x, dt, A, Bm, Cm, D):
    S = jnp.zeros((x.shape[0], *x.shape[2:], Bm.shape[-1]), jnp.float32)
    ys = []
    for t in range(x.shape[1]):
        S, y = ssm.ssm_step(S, x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], D)
        ys.append(y)
    return jnp.stack(ys, axis=1), S


@pytest.mark.parametrize("T", [1, 5, 7, 8, 9, 16, 23])  # chunk 8: under, at, over
def test_the_chunked_scan_is_the_one_step_recurrence(T):
    args = _scan_inputs(T, seed=T)
    want_y, want_S = _one_step_scan(*args)
    got_y, got_S = ssm.ssm_chunked(*args, chunk=8)
    assert got_y.shape == want_y.shape and got_S.dtype == jnp.float32
    assert rel(got_y, want_y) < 2e-6 and rel(got_S, want_S) < 2e-6
    # the tiling changes no result
    other_y, other_S = ssm.ssm_chunked(*args, chunk=4)
    assert rel(other_y, want_y) < 2e-6 and rel(other_S, want_S) < 2e-6


def test_a_position_whose_dt_is_zero_advances_no_state():
    """How padding is kept out: positions 11.. of a sequence of 19 with
    ``dt = 0`` leave the state of the first 11."""
    x, dt, A, Bm, Cm, D = _scan_inputs(19, seed=1)
    _, want = _one_step_scan(x[:, :11], dt[:, :11], A, Bm[:, :11], Cm[:, :11], D)
    dt = dt.at[:, 11:].set(0.0)
    _, got = ssm.ssm_chunked(x, dt, A, Bm, Cm, D, chunk=8)
    assert rel(got, want) < 2e-6


def test_the_convolution_step_is_the_whole_convolution():
    K, C = CFG.conv_kernel, CFG.conv_width
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    u = jax.random.normal(k[0], (2, 11, C))
    w, b = jax.random.normal(k[1], (K, C)), jax.random.normal(k[2], (C,))
    whole = ssm.causal_conv(u, w, b)
    for t in (0, 1, 2, 3, 10):  # zeros before position 0
        tail = ssm.conv_tail(u, jnp.asarray([t, t]), K)
        assert tail.shape == (2, K - 1, C)
        assert not np.asarray(tail[:, :max(0, K - 1 - t)]).any()
        window = jnp.concatenate([tail, u[:, t:t + 1]], axis=1)
        assert rel(ssm.conv_step(window, w, b), whole[:, t]) < 1e-6
    # a row a prompt: each reads its own last inputs
    tail = ssm.conv_tail(u, jnp.asarray([2, 11]), K)
    assert not np.asarray(tail[0, 0]).any()
    np.testing.assert_array_equal(tail[0, 1:], u[0, :2])
    np.testing.assert_array_equal(tail[1], u[1, 8:11])


# ----------------------------------------------------- padding, rows, the wave
@pytest.mark.parametrize("lens", [[1, 2, 3], [5, 17], [7, 8, 9, 16]])
def test_a_padded_prompt_leaves_the_state_of_its_true_length(lens):
    """One prefill wave as the engine builds it: prompts padded to whole
    pages (and so past whole chunks: page 8 = chunk 8 here, the scan pads
    again inside), the wave to a bucket of 4 with dummy rows whose pages and
    row are the junk ones. Each prompt's state and conv row are the
    reference's after its TRUE length — zeros in the conv row where the
    prompt is shorter than 3 — and the junk row takes the dummies'."""
    pad = -(-max(lens) // PS) * PS
    rng = np.random.default_rng(sum(lens))
    prompts = [rng.integers(3, CFG.vocab_size, n).tolist() for n in lens]
    params = W.make_params(W.seed_key(5), CFG)
    cache = programs.make_pools(CFG, PS, {"kv": 20, "state": 6}, None)
    # a non-zero row planted where a released slot left it: never read
    cache = (*cache[:2], cache[2] + 7.0, cache[3] + 7.0)
    toks = np.zeros((4, pad), np.int32)
    kv_pages = np.zeros((4, pad // PS), np.int32)
    rows = np.zeros((4, 1), np.int32)
    true_lens = np.ones(4, np.int32)
    for j, p in enumerate(prompts):
        toks[j, :len(p)] = p
        kv_pages[j] = 1 + j * (pad // PS) + np.arange(pad // PS)
        rows[j], true_lens[j] = 1 + j, len(p)
    first, kp, vp, states, convs = programs.ssm_moe_prefill_batch(
        params, None, jnp.zeros(4, jnp.int32), jnp.asarray(toks),
        (jnp.asarray(kv_pages), jnp.asarray(rows)), *cache,
        jnp.asarray(true_lens), jnp.zeros(4), jax.random.PRNGKey(0), CFG)
    for j, p in enumerate(prompts):
        want = R.forward(5, CFG, p, q_block=32, state_at=(len(p),),
                         logits_from=len(p) - 1)
        assert rel(states[:, 1 + j], want["state"][:, 0]) < 1e-5, len(p)
        got = convs[:, 1 + j].reshape(N_M, 3, -1)
        assert float(jnp.abs(got - want["conv"][:, 0]).max()) < 1e-5, len(p)
        if len(p) < 3:
            assert not np.asarray(got[:, :3 - len(p)]).any()
        assert int(first[j]) == int(jnp.argmax(want["logits"][0]))
    # rows nobody drew keep what was planted; the junk row took the dummies'
    untouched = [r for r in range(1, 6) if r > len(lens)]
    assert np.all(np.asarray(states[:, untouched]) == 7.0)


def test_a_dead_slot_and_the_junk_row_never_reach_a_live_slot():
    """Decode with one live slot of three: the dead slots' steps go to the
    junk row (planted non-zero, as every other row), which no live slot
    owns — its conv row stays bit for bit, and so does its state, which
    takes ``dt`` 0 — the live slot's row is the reference's, and rows
    nobody holds keep what was planted."""
    eng = _engine()
    kp, vp, states, convs = eng.cache
    eng.cache = (kp, vp, states + 3.0, convs + 3.0)
    prompt = np.random.default_rng(2).integers(3, CFG.vocab_size, 12).tolist()
    row = eng.free[1][0]
    prompts, outs = _serve_one(eng, prompt, 13)
    assert float(_logit_gaps(5, CFG, prompts, outs).max()) == 0.0
    want = R.forward(5, CFG, prompt + outs[0][:-1], q_block=32, state_at=(24,))
    assert rel(eng.cache[2][:, row], want["state"][:, 0]) < 1e-5
    others = [r for r in range(1, 4) if r != row]
    assert np.all(np.asarray(eng.cache[2][:, others]) == 3.0)
    assert np.all(np.asarray(eng.cache[2][:, 0]) == 3.0)       # dt 0: unchanged
    assert np.all(np.asarray(eng.cache[3][:, others]) == 3.0)
    assert np.all(np.asarray(eng.cache[3][:, 0]) == 3.0)       # junk: unowned


def test_a_slot_reused_after_a_release_starts_from_a_zero_state():
    """Two rows for five requests: every row is drawn again after a release
    with its last holder's state in it, and every request is the
    reference's."""
    eng = _engine(n_pages={"kv": 41, "state": 3}, max_batch=2)
    cases = [(9, 6), (17, 5), (3, 9), (24, 4), (11, 5)]
    prompts, outs = _serve(eng, cases)
    assert float(_logit_gaps(5, CFG, prompts, outs).max()) == 0.0
    drawn = metrics.stage_totals()["rt_llm_pages_drawn_total"]["state"]["sum"]
    assert drawn >= 5 and len(eng.free[1]) == 2


def test_long_and_short_requests_in_one_queue_admit_in_order():
    """Too few pages for all at once: the head of the queue waits for its
    pages, the ones behind it wait for it, every request finishes with the
    reference's tokens and every page and row comes back."""
    eng = _engine(n_pages={"kv": 13, "state": 4})
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
    assert (len(eng.free[0]), len(eng.free[1])) == (12, 3)


def test_admission_waits_for_a_state_row_as_for_a_page():
    """One state row for three requests: they run one after the other."""
    eng = _engine(n_pages={"kv": 41, "state": 2})
    prompts, outs = _serve(eng, [(9, 5), (12, 4), (5, 6)])
    assert float(_logit_gaps(5, CFG, prompts, outs).max()) == 0.0
    assert len(eng.free[1]) == 1


def test_the_wave_limit_is_the_familys_and_splits_a_group():
    eng = _engine(max_batch=8, n_pages={"kv": 200, "state": 9})
    assert eng.programs.prefill_wave_limit == (8, 16384)
    assert [len(w) for w in eng._split_wave(2048, [0] * 8)] == [8]
    assert [len(w) for w in eng._split_wave(4096, [0] * 7)] == [4, 3]
    assert [len(w) for w in eng._split_wave(512, [0] * 8)] == [8]


# ---------------------------------------------------------------- the controls
@pytest.mark.parametrize("variant", [
    {"state": "bfloat16"}, {"gate": "after"}, {"skip": False},
    {"rope": True}, {"act": "relu"}, {"act": "swiglu"}])
def test_a_reference_with_other_mathematics_fails_the_comparison(served, variant):
    """The controls: the state kept in bf16 between positions, the gate
    applied after the grouped norm, no ``D . x``, a rotation in the
    attention block, experts of relu without the square, SwiGLU experts —
    each is a forward pass whose cache rows are not the program's."""
    prompts, outs = served
    seq = prompts[1] + outs[1][:-1]
    want = R.forward(5, CFG, seq, q_block=32, state_at=(len(seq),))
    low = R.forward(5, CFG, seq, q_block=32, state_at=(len(seq),),
                    variant=variant)
    assert max(rel(low[n], want[n]) for n in ("state", "k", "v")) > 2e-3
    if "state" not in variant:  # by a wide margin, and the tokens say so too
        assert float(_logit_gaps(5, CFG, prompts, outs, variant=variant).max()) > 0.05


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
    assert rel(low["k"], want["k"]) < 1e-6   # the true positions' rows are kept


def test_bf16_programs_stay_within_a_stated_tolerance():
    """The same comparison in the type the cell serves. Near-tied expert
    choices flip between bf16 and float32 (three expert blocks, 4 of 16), and
    a flipped position carries another expert's output: tokens are held to a
    fraction of a logit spread, a position's logits to 5 % at the median
    and 20 % over all, and block 0's state (before any routing; float32 in
    the pool whatever the model's type) to 1 %."""
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    eng = _engine(cfg=cfg)
    assert eng.cache[2].dtype == jnp.float32 and eng.cache[3].dtype == jnp.bfloat16
    row = eng.free[1][0]
    prompts, outs = _serve(eng, [(40, 9)])
    gaps = _logit_gaps(5, cfg, prompts, outs)
    assert float(np.percentile(gaps, 50)) == 0.0 and float(gaps.max()) < 0.5
    seq = prompts[0] + outs[0][:-1]
    low = R.forward(5, cfg, seq, q_block=32, state_at=(len(seq),))
    want = ssm_moe_forward(W.make_params(W.seed_key(5), cfg),
                           jnp.asarray([seq]), cfg)
    got = want[0].astype(jnp.float32)
    by_position = (jnp.linalg.norm(got - low["logits"], axis=-1)
                   / jnp.linalg.norm(low["logits"], axis=-1))
    assert float(jnp.median(by_position)) < 0.05
    assert rel(got, low["logits"]) < 0.2
    assert rel(eng.cache[2][0, row], low["state"][0, 0]) < 0.01


@pytest.mark.parametrize("seed", SEEDS)
def test_forward_logits_match_the_plain_reference(seed):
    params = W.make_params(W.seed_key(seed), CFG)
    tokens = np.random.default_rng(seed % 1000).integers(3, CFG.vocab_size, 70)
    want = R.forward(seed, CFG, tokens, q_block=32)
    got = ssm_moe_forward(params, jnp.asarray(tokens)[None], CFG)[0]
    assert rel(got, want["logits"]) < 1e-5
    assert want["chosen"].shape == (3, 70, CFG.n_experts_per_tok)


# ------------------------------------------------------------------ the experts
@pytest.mark.parametrize("holders", [8, 2])
def test_holders_parts_add_up_to_the_uncut_block(holders):
    """The chip's share of a deployment (model-configs guide, section 4): the
    16 experts of a block divided over ``holders``; each routes over all of
    them and computes its own experts' part, and every holder computes the
    shared expert alike — counted ONCE, the parts are the uncut reference's
    block output."""
    whole = dataclasses.replace(CFG, experts_held=None)
    key = W.layer_key(W.seed_key(5), 1)
    full = W.layer_from_seed(W.seed_key(5), whole, 1)["moe"]
    h = jax.random.normal(jax.random.PRNGKey(1), (37, CFG.d_model))
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), full)
    want, _ = R.moe(f32, h, whole, "float32")
    shared_only = want - R.moe(f32, h, whole, "float32", shared=False)[0]
    per = CFG.n_experts // holders
    total, loads = shared_only, []
    for r in range(holders):
        cfg = dataclasses.replace(CFG, experts_held=(r * per, (r + 1) * per))
        mine = {"w_up": W.expert_stack(key, cfg, 1),
                "w_down": W.expert_stack(key, cfg, 2)}
        assert jnp.array_equal(mine["w_up"],
                               full["experts"]["w_up"][r * per:(r + 1) * per])
        idx, w = sigmoid_topk_route(
            h, full["router"]["kernel"], full["router"]["bias"],
            cfg.n_experts_per_tok, cfg.routed_scaling_factor)
        part, load = routed_experts(h, idx, w, mine, cfg.held)
        # the reference's own share of the same holder, shared expert apart
        ref_part, _ = R.moe({**f32, "experts": jax.tree.map(
            lambda a: a.astype(jnp.float32), mine)}, h, whole, "float32",
            held=cfg.held, shared=False)
        assert rel(part, ref_part) < 1e-5
        total = total + part
        loads.append(load)
    assert rel(total, want) < 1e-5
    assert int(jnp.concatenate(loads).sum()) == h.shape[0] * CFG.n_experts_per_tok


@pytest.mark.parametrize("rows", [5, 200, 400])  # a decode step's few, a prefill's many
def test_ungated_experts_through_routed_experts_are_a_dense_loop(rows):
    """``W_down . relu(W_up . h)^2`` for each chosen held expert, weighted:
    every held expert on every token for a step's few rows, two
    ``ragged_dot`` calls over the sorted rows past ``_DENSE_ROWS``; rows that
    are not ``valid`` add nothing in either form, and ``expert_passes``
    counts what ran."""
    n, D, F, k = 6, 32, 48, 3
    ks = jax.random.split(jax.random.PRNGKey(rows), 5)
    h = jax.random.normal(ks[0], (rows, D))
    experts = {"w_up": jax.random.normal(ks[1], (n, D, F)) / D ** 0.5,
               "w_down": jax.random.normal(ks[2], (n, F, D)) / F ** 0.5}
    idx = jnp.argsort(jax.random.uniform(ks[3], (rows, 8)), axis=-1)[:, :k]
    w = jax.random.uniform(ks[4], (rows, k))
    valid = jax.random.uniform(ks[4], (rows,)) > 0.3
    got, load = routed_experts(h, idx, w, experts, (1, 7), valid)
    want = jnp.zeros_like(h)
    for e in range(1, 7):
        y = jnp.square(jax.nn.relu(h @ experts["w_up"][e - 1])) @ experts["w_down"][e - 1]
        want = want + y * jnp.where(idx == e, w, 0.0).sum(-1)[:, None]
    assert rel(got, jnp.where(valid[:, None], want, 0.0)) < 1e-5
    assert not np.asarray(got[~np.asarray(valid)]).any()
    assert int(load.sum()) == int((((idx >= 1) & (idx < 7)) & valid[:, None]).sum())
    dense = rows * k <= moe._DENSE_ROWS
    assert moe._applies_every_expert(rows * k, gated=False) == dense
    assert not moe._applies_every_expert(rows * k, gated=True)
    assert int(expert_passes(load, rows * k, gated=False)) == (
        6 if dense else int((load > 0).sum()))


# ---------------------------------------------------------------- the counters
def _grown(before, after, name, tag=""):
    return (after[name].get(tag, {"sum": 0})["sum"]
            - before.get(name, {}).get(tag, {"sum": 0})["sum"])


def test_the_stats_column_and_read_counters_against_a_hand_count():
    """A request of 20 + 13 tokens: 12 decode steps (blocks 8 + 4) at
    lengths 21..32. ``ssm_updates``: one live slot x 3 Mamba-2 blocks a
    step. The K/V read counters count the attention block's positions only:
    the state kind holds none, so it adds nothing and dilutes nothing."""
    eng = _engine()
    assert eng.programs.stats == programs.MOE_STATS + (
        "ssm_updates", "walk_blocks", "walk_run_blocks")
    before = metrics.stage_totals()
    _serve(eng, [(20, 13)])
    after = metrics.stage_totals()

    def grown(name, tag=""):
        return _grown(before, after, name, tag)

    steps = 12
    assert grown("rt_llm_ssm_state_updates_total") == steps * N_M
    assert grown("rt_llm_moe_expert_slots_total") == steps * 3 * 8
    # a step's few rows: every held expert is applied once a block a step
    assert grown("rt_llm_moe_expert_passes_total") == steps * 3 * 8
    assert grown("rt_llm_moe_experts_touched_total") <= steps * 3 * 8
    assert grown("rt_llm_decode_kv_tokens_live_total") == sum(range(21, 33))
    assert grown("rt_llm_decode_kv_tokens_live_total", "kv") == sum(range(21, 33))
    assert grown("rt_llm_decode_kv_tokens_live_total", "state") == 0
    assert grown("rt_llm_decode_kv_tokens_read_total", "state") == 0
    assert grown("rt_llm_decode_kv_tokens_read_total") == steps * eng.B * eng.MAXP * PS
    assert grown("rt_llm_pages_drawn_total", "state") == 1
    assert grown("rt_llm_pages_drawn_total", "kv") == 5          # ceil(33 / 8)
    assert eng._last_kv["kv_live"] == sum(range(29, 33)) / 4
    assert eng._last_stats["ssm_updates"] == N_M
    assert {"moe_passes", "ssm_updates"} <= set(eng._last_stats)
    # the gathered form walks nothing
    assert grown("rt_llm_walk_blocks_total") == 0
    assert grown("rt_llm_walk_run_blocks_total") == 0


def test_the_walks_copies_are_counted_where_the_kernel_runs(monkeypatch):
    """The chip's branch without a chip: the decode kernels interpreted
    under the engine at blocks of 4 pages and sub-runs of 2, two programs of
    4 steps. The first slot's lengths 14..21 cross a page and a sub-run (16 |
    17) inside the first program, the second's 22..29 a page and a sub-run
    (24 | 25) between the two. The table's runs are found once a program and
    the free list hands both slots their pages in runs, so every sub-run
    whose pages all hold tokens is inside ONE copy: pages 2 2 2 3 3 3 3 3 + 3
    3 3 4 4 4 4 4 a step, sub-runs 1 1 1 2 2 2 2 2 + 2 2 2 2 2 2 2 2, whole
    1 1 1 1 1 1 1 1 + 1 1 1 2 2 2 2 2 — in the one attention block."""
    from ray_tpu.ops import paged_attention

    cases = [(13, 9), (21, 9)]
    _, want = _serve(_engine(block_buckets=(4,)), cases)
    monkeypatch.setattr(programs, "_reads_in_place", lambda: True)
    monkeypatch.setattr(paged_attention, "_BLOCK_BYTES",
                        4 * PS * 2 * CFG.n_kv_heads * 128 * 4)
    monkeypatch.setattr(paged_attention, "_RUN_PAGES", 2)
    programs.ssm_moe_decode_multi.clear_cache()
    try:
        eng = _engine(block_buckets=(4,))
        before = metrics.stage_totals()
        _, got = _serve(eng, cases)
        after = metrics.stage_totals()
    finally:
        programs.ssm_moe_decode_multi.clear_cache()
    assert got == want
    assert _grown(before, after, "rt_llm_walk_blocks_total") == 13 + 16
    assert _grown(before, after, "rt_llm_walk_run_blocks_total") == 8 + 13


def test_both_programs_name_the_new_parts():
    """``conv`` and ``ssm`` are parts of the vocabulary, and both programs'
    lowered text carries them on their operations (what PR 35's part table
    joins a trace to)."""
    from ray_tpu.utils import tracing

    assert {"conv", "ssm"} <= set(tracing.PARTS)
    eng = _engine()
    B = eng.B
    i32 = jnp.zeros(B, jnp.int32)
    text = programs.ssm_moe_decode_multi.lower(
        eng.params, None, i32, i32, i32,
        tuple(jnp.asarray(t) for t in eng.tables), *eng.cache,
        jnp.ones(B, bool), jnp.zeros(B), jax.random.PRNGKey(0), cfg=CFG,
        n_steps=2).as_text(debug_info=True)
    for part in ("conv", "ssm", "project", "attn_out", "experts", "router"):
        assert f"/{part}/" in text or f"{part}/" in text, part


# ---------------------------------------------------------------- the kernels
def test_paged_decode_attention_at_two_kv_heads_and_sixteen_a_group():
    """The cell's attention shape — 2 KV heads, 16 query heads each, pages
    of 16, no ring — in the interpreter against a dense softmax: slots
    inside a page, at its edge, over several pages, and an inactive one."""
    KV, G, hd, ps, entries = 2, 16, 128, 16, 6
    H = KV * G
    rng = np.random.default_rng(0)
    lengths = np.array([5, 16, 17, 0, 47, 96], np.int32)
    B = len(lengths)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    kpool = jax.random.normal(ks[1], (2, 40, ps, KV, hd), jnp.float32)
    vpool = jax.random.normal(ks[2], (2, 40, ps, KV, hd), jnp.float32)
    tables = rng.permutation(np.arange(1, 37)).reshape(B, entries).astype(np.int32)
    got = np.asarray(paged_decode_attention(
        q, kpool, vpool, 1, jnp.asarray(tables), jnp.asarray(lengths),
        interpret=True))
    for b in range(B):
        n = int(lengths[b])
        if not n:
            assert not got[b].any()
            continue
        pos = np.arange(n)
        k = np.asarray(kpool)[1, tables[b, pos // ps], pos % ps]
        v = np.asarray(vpool)[1, tables[b, pos // ps], pos % ps]
        for h in range(H):
            s = k[:, h // G] @ np.asarray(q)[b, h] / np.sqrt(hd)
            p = np.exp(s - s.max())
            assert np.abs(got[b, h] - (p / p.sum()) @ v[:, h // G]).max() < 2e-5


def _pool_inputs(L, R, H, P, N, G, owned, seed=0, dtype=jnp.float32):
    """A state pool and one step's inputs laid out by row, as ``_mamba_step``
    hands them over: rows outside ``owned`` carry ``dt`` 0 and zeros."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    keep = np.zeros(R, bool)
    keep[list(owned)] = True

    def by_row(a):
        return jnp.where(keep.reshape((R,) + (1,) * (a.ndim - 1)), a, 0)

    return (jax.random.normal(ks[0], (L, R, H, P, N), jnp.float32),
            by_row(jax.random.normal(ks[1], (R, H, P), dtype)),
            by_row(jax.random.uniform(ks[2], (R, H), jnp.float32, 0.001, 0.1)),
            -jax.random.uniform(ks[3], (H,), jnp.float32, 1.0, 16.0),
            by_row(jax.random.normal(ks[4], (R, G, N), dtype)),
            by_row(jax.random.normal(ks[5], (R, G, N), dtype)),
            jax.random.normal(ks[6], (H,), jnp.float32))


@pytest.mark.parametrize("owned", [range(5), (1, 3), ()],
                         ids=["all", "some", "none"])
@pytest.mark.parametrize("H,P,N,G,dtype", [
    (16, 8, 16, 2, jnp.float32),      # the tiny configuration's
    (8, 8, 128, 2, jnp.bfloat16),     # a state as wide as the lanes
    (4, 16, 128, 4, jnp.bfloat16),    # a head a group
    (6, 32, 128, 2, jnp.bfloat16),    # four heads fill the lanes, 6 % 4 != 0
], ids=["tiny", "lanes", "head-a-group", "heads-not-a-tile"])
def test_the_pool_step_kernel_is_the_plain_step(H, P, N, G, dtype, owned):
    """``ssm_pool_step`` in the interpreter against ``ssm.ssm_step`` on one
    block of a pool of three: every row live, some rows unowned (``dt`` 0),
    all of them unowned."""
    pool, *step = _pool_inputs(3, 5, H, P, N, G, owned, dtype=dtype)
    want_S, want_y = ssm.ssm_step(pool[1], *step)
    got, y = ssm_pool_step(pool, 1, *step, interpret=True)
    assert y.shape == want_y.shape and y.dtype == jnp.float32
    assert float(jnp.abs(got[1] - want_S).max()) <= 1e-6 * float(
        jnp.abs(want_S).max())
    assert float(jnp.abs(y - want_y).max()) <= 1e-6 * max(
        float(jnp.abs(want_y).max()), 1.0)
    assert jnp.array_equal(got[0], pool[0]) and jnp.array_equal(got[2], pool[2])


def test_the_pool_step_kernel_leaves_unowned_rows_and_other_blocks_bit_for_bit():
    """A row whose ``dt`` is 0 — the junk row, a row no live slot owns —
    comes back as it entered (``array_equal``), whatever block is stepped,
    the block's index traced or not; the pool's other blocks are not
    touched; an unowned row's ``y`` is zero."""
    owned = np.array([2, 4])
    unowned = np.array([0, 1, 3, 5])
    pool, *step = _pool_inputs(4, 6, 8, 8, 128, 2, owned, seed=1)
    traced = jax.jit(lambda p, j: ssm_pool_step(p, j, *step, interpret=True))
    for j, call in ((0, traced), (3, traced), (3, lambda p, j: ssm_pool_step(
            p, j, *step, interpret=True))):
        got, y = (np.asarray(a) for a in call(pool, j))
        assert np.array_equal(got[j, unowned], pool[j, unowned])
        assert not y[unowned].any()
        others = np.array([b for b in range(4) if b != j])
        assert np.array_equal(got[others], pool[others])
        assert not np.array_equal(got[j, 2], pool[j, 2])
        assert rel(got[j, owned], ssm.ssm_step(pool[j], *step)[0][owned]) < 1e-6


def test_a_decode_step_shifts_live_conv_rows_by_one_input_and_leaves_the_rest():
    """One decode step on planted pools of five rows, slots 0 and 2 live
    (rows 2 and 3), slot 1 dead with its old row 1 still in its table: in
    every Mamba-2 block the junk row, the dead slot's old row and a row
    nobody drew stay bit for bit, and a live slot's row drops its oldest
    input and keeps the other two where they were, bit for bit. In the first
    block, whose input is the embedding, the input taken and the state are
    ``ops/ssm.py``'s one-step forms on that slot ALONE."""
    eng = _engine(n_pages={"kv": 41, "state": 5})
    C, K = CFG.conv_width, CFG.conv_kernel
    rng = np.random.default_rng(6)
    kp, vp, states, convs = eng.cache
    start = (kp, vp, jnp.asarray(rng.normal(size=states.shape), states.dtype),
             jnp.asarray(rng.normal(size=convs.shape), convs.dtype))
    tables = (jnp.asarray(rng.permutation(np.arange(1, 37)).reshape(3, 12),
                          jnp.int32), jnp.asarray([[2], [1], [3]], jnp.int32))
    tok = jnp.asarray([7, 9, 11], jnp.int32)
    _, _, _, _, _, got_S, got = programs.ssm_moe_decode_multi(
        eng.params, None, jnp.zeros(3, jnp.int32), tok,
        jnp.asarray([5, 8, 17], jnp.int32), tables,
        *(jnp.copy(a) for a in start), jnp.asarray([True, False, True]),
        jnp.zeros(3), jax.random.PRNGKey(0), cfg=CFG, n_steps=1)
    old = np.asarray(start[3])
    got = np.asarray(got)
    assert got.shape == (N_M, 5, (K - 1) * C)
    for unowned in (0, 1, 4):                 # junk, a dead slot's, nobody's
        assert np.array_equal(got[:, unowned], old[:, unowned]), unowned
        assert np.array_equal(got_S[:, unowned], start[2][:, unowned])
    layer = eng.params["layers_0"]
    assert CFG.pattern[0] == MAMBA
    for slot, row in ((0, 2), (2, 3)):
        assert np.array_equal(got[:, row, :-C], old[:, row, C:])   # every block
        assert not np.array_equal(got[:, row, -C:], old[:, row, -C:])
        x = eng.params["tok"]["embedding"][tok[slot]][None, None]
        _, u, dt = mamba_in(layer, x, CFG)
        window = jnp.concatenate(
            [start[3][0, row].reshape(1, K - 1, C), u], axis=1)
        assert rel(got[0, row], window[:, 1:].reshape(-1)) < 1e-6
        xbc = ssm.conv_step(window, layer["conv"]["kernel"],
                            layer["conv"]["bias"])
        xs, Bm, Cm = split_conv(xbc, CFG)
        want_S, _ = ssm.ssm_step(
            start[2][0, row][None], xs, mamba_dt(layer, dt[:, 0]),
            mamba_decay(layer), Bm, Cm, layer["D"])
        assert rel(got_S[0, row], want_S[0]) < 1e-6


def test_decode_through_the_pool_step_kernel_is_the_plain_decode(monkeypatch):
    """Two blocks of four steps with the in-place forms interpreted (the
    ``_reads_in_place`` switch patched) against the plain forms: all three
    slots live, then one of them dead from the second block on — its steps
    go to the junk row. The tokens, the states and what the live slots hold
    of the other pools agree; the junk row's state stays bit for bit."""
    eng = _engine()
    B = eng.B
    rng = np.random.default_rng(4)
    kp, vp, states, convs = eng.cache
    start = (kp, vp, jnp.asarray(rng.normal(size=states.shape), states.dtype),
             jnp.asarray(rng.normal(size=convs.shape), convs.dtype))
    tables = (jnp.asarray(rng.permutation(np.arange(1, 37)).reshape(B, 12),
                          jnp.int32),
              jnp.asarray([[2], [1], [3]], jnp.int32))

    def two_blocks():
        programs.ssm_moe_decode_multi.clear_cache()
        tok, pos = jnp.asarray([7, 9, 11], jnp.int32), jnp.asarray(
            [5, 8, 17], jnp.int32)
        cache, rows = tuple(jnp.copy(a) for a in start), []
        for active in ([True, True, True], [True, False, True]):
            out, tok, pos, *cache = programs.ssm_moe_decode_multi(
                eng.params, None, jnp.zeros(B, jnp.int32), tok, pos, tables,
                *cache, jnp.asarray(active), jnp.zeros(B),
                jax.random.PRNGKey(0), cfg=CFG, n_steps=4)
            rows.append(np.asarray(out))
        programs.ssm_moe_decode_multi.clear_cache()
        return np.concatenate(rows), cache

    want_rows, want = two_blocks()
    monkeypatch.setattr(programs, "_reads_in_place", lambda: True)
    calls = []
    monkeypatch.setattr(programs, "ssm_pool_step", lambda *a: (
        calls.append(a[1]), ssm_pool_step(*a))[1])
    got_rows, got = two_blocks()
    assert calls == list(range(N_M))                 # once a block a trace
    # tokens and stats; the walk's copies alone are the kernel's to count
    assert np.array_equal(got_rows[:, :-2], want_rows[:, :-2])
    assert got_rows[:, -2].all() and not want_rows[:, -2:].any()
    assert got_rows[:, :B].any() and not got_rows[4:, 1].any()
    live = np.asarray(tables[0])[[0, 2]].ravel()     # a dead slot's K/V page,
    for a, b in zip(got[:2], want[:2]):              # as the junk conv row,
        assert rel(a[:, live], b[:, live]) < 1e-5    # holds what nobody reads
    assert rel(got[2], want[2]) < 1e-5 and rel(got[3][:, 1:], want[3][:, 1:]) < 1e-5
    for a in (got[2], want[2]):                      # the junk row's state
        assert jnp.array_equal(a[:, 0], start[2][:, 0])
    # the dead slot's row moved in the first block and not in the second
    assert not jnp.array_equal(got[2][:, 1], start[2][:, 1])
    assert rel(got[2][:, 1], want[2][:, 1]) < 1e-6


# ---------------------------------------------------------------- refusals
@pytest.mark.parametrize("feature,make", [
    ("kv_dtype='int8'", lambda: _engine(kv_dtype="int8")),
    ("lora_adapters", lambda: _engine(lora_adapters={"a": {}})),
    ("spec_enable", lambda: _engine(spec_enable=True)),
    ("export_pages", lambda: _engine().export_pages(1)),
    ("submit_prefilled", lambda: _engine().submit_prefilled([1], None, None, 3)),
    ("a K or V pool", lambda: _engine().kpool),
])
def test_what_takes_a_prefix_of_pages_for_a_prefix_is_refused_by_name(feature, make):
    with pytest.raises(UnsupportedByModel, match=feature.split("(")[0]) as e:
        make()
    assert "ssm_moe" in str(e.value)
    assert "a prefix of its pages is a prefix of the sequence" in " ".join(
        str(e.value).split())

"""The MLA + sparse-expert family (``models/mla_moe.py``, ``parallel/moe.py``,
``llm/mla_moe.py``) against the benchmark's plain float32 reference
(``benchmarks/reference/mla_moe.py``), at a tiny size that keeps every width
ratio of the published shape: rope part smaller than the nope part,
v_head_dim != qk_head_dim, shared width = 2 x expert width, first layer
dense. CPU, float32, seeded weights."""
import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import weights_mla_moe as W
from benchmarks.reference import mla_moe as R
from ray_tpu.llm.engine import (ContinuousBatchingEngine, UnsupportedByModel,
                                serving_programs)
from ray_tpu.models.mla_moe import (MlaMoeConfig, mla_attend_absorbed,
                                    mla_attend_expanded, mla_moe_forward,
                                    mla_moe_init, mla_project)
from ray_tpu.ops.basic import rms_norm, rope_freqs, swiglu
from ray_tpu.parallel.moe import moe_layer, routed_experts, sigmoid_topk_route

CFG = MlaMoeConfig.tiny()
SEEDS = [3, 2**31 + 7, 99]


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def test_tiny_keeps_the_published_ratios():
    full = MlaMoeConfig()
    assert CFG.qk_rope_head_dim < CFG.qk_nope_head_dim
    assert CFG.v_head_dim != CFG.qk_head_dim
    assert CFG.first_dense_layers == full.first_dense_layers == 1
    assert CFG.n_shared_experts == full.n_shared_experts == 2
    assert full.latent_width == 576 and full.qk_head_dim == 192
    assert [CFG.is_moe_layer(i) for i in range(3)] == [False, True, True]


@pytest.mark.parametrize("seed", SEEDS)
def test_forward_logits_match_the_plain_reference(seed):
    params = W.make_params(W.seed_key(seed), CFG, 2)
    tokens = jax.random.randint(jax.random.PRNGKey(seed % 1000), (2, 40), 3,
                                CFG.vocab_size)
    want = R.forward(seed, CFG, tokens, zero_col=2)
    assert rel(mla_moe_forward(params, tokens, CFG), want["logits"]) < 1e-5
    assert float(jnp.abs(want["logits"][..., 2]).max()) == 0.0  # eos column


def test_own_init_has_the_layout_the_programs_take():
    params = mla_moe_init(jax.random.PRNGKey(0), CFG)
    seeded = W.make_params(W.seed_key(0), CFG)
    assert (jax.tree.map(lambda x: (x.shape, x.dtype), params)
            == jax.tree.map(lambda x: (x.shape, x.dtype), seeded))
    assert "moe" not in params["layers_0"] and "moe" in params["layers_1"]
    logits = mla_moe_forward(params, jnp.ones((1, 8), jnp.int32), CFG)
    assert logits.shape == (1, 8, CFG.vocab_size)


def test_absorbed_and_expanded_attention_agree_on_one_cache():
    layer = W.layer_from_seed(W.seed_key(7), CFG, 1)
    B, T = 2, 24
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, CFG.d_model))
    cos, sin = rope_freqs(CFG.qk_rope_head_dim, CFG.max_seq_len, CFG.rope_theta)
    positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    h = rms_norm(x, layer["attn_norm"]["scale"])
    q, latent = mla_project(layer, h, cos, sin, positions, CFG)
    idx = jnp.arange(T)
    mask = jnp.broadcast_to(idx[None, :, None] >= idx[None, None, :], (B, T, T))
    a = mla_attend_expanded(layer, q, latent, mask, CFG)
    b = mla_attend_absorbed(layer, q, latent, mask, CFG)
    assert a.shape == (B, T, CFG.n_heads * CFG.v_head_dim)
    assert rel(b, a) < 1e-5
    # one query over the whole cache: the decode shape
    assert rel(mla_attend_absorbed(layer, q[:, -1:], latent, mask[:, -1:], CFG),
               a[:, -1:]) < 1e-5


# ---------------------------------------------------------------- the router
def _router_case(case: str):
    """(h, router) for one routing situation."""
    T, D, E = 12, CFG.d_model, CFG.n_experts
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(11), 3)
    h = jax.random.normal(k1, (T, D))
    kernel = jax.random.normal(k2, (D, E)) * 0.2
    bias = 0.1 * jax.random.normal(k3, (E,))
    if case == "large_bias":
        # b alone decides: experts 9, 10, 11 whatever their scores
        bias = bias.at[jnp.array([9, 10, 11])].set(5.0)
    elif case == "all_to_the_same":
        h = jnp.broadcast_to(h[:1], (T, D))
    elif case == "exact_ties":
        kernel = jnp.zeros((D, E))     # every score 0.5
        bias = jnp.zeros((E,)).at[jnp.array([4, 6])].set(0.25)
    elif case == "switch_top1":
        pass  # k = 1 below: the old Switch router's choice, no capacity
    return h, {"kernel": kernel, "bias": bias}


@pytest.mark.parametrize("case", ["random", "large_bias", "all_to_the_same",
                                  "exact_ties", "switch_top1"])
def test_router_matches_the_reference(case):
    h, router = _router_case(case)
    cfg = (dataclasses.replace(CFG, n_experts_per_tok=1)
           if case == "switch_top1" else CFG)
    k = cfg.n_experts_per_tok
    idx, w = sigmoid_topk_route(h, router["kernel"], router["bias"], k,
                                cfg.routed_scaling_factor)
    chosen, combine = R.route(h, router, cfg, "float32")
    assert (np.sort(np.asarray(idx), -1) == np.sort(np.asarray(chosen), -1)).all()
    dense = jnp.zeros_like(combine).at[jnp.arange(h.shape[0])[:, None], idx].set(w)
    assert rel(dense, combine) < 1e-6
    # the weights of a token sum to the scaling factor; the bias never weighs
    np.testing.assert_allclose(np.asarray(w.sum(-1)), cfg.routed_scaling_factor,
                               rtol=1e-5)
    if case == "large_bias":
        assert (np.sort(np.asarray(idx), -1) == [9, 10, 11]).all()
        s = jax.nn.sigmoid(h @ router["kernel"])
        got = jnp.take_along_axis(s, idx, -1)
        assert rel(w, got / got.sum(-1, keepdims=True)
                   * cfg.routed_scaling_factor) < 1e-6
    if case == "exact_ties":  # the two biased, then the lowest index
        assert (np.asarray(idx) == [4, 6, 0]).all()


@pytest.mark.parametrize("case", ["random", "all_to_the_same", "exact_ties"])
def test_expert_layer_matches_the_reference_and_drops_nothing(case):
    h, router = _router_case(case)
    moe = W.layer_from_seed(W.seed_key(5), CFG, 1)["moe"]
    moe = {**moe, "router": router}
    y, load = moe_layer(h, moe, k=CFG.n_experts_per_tok,
                        scale=CFG.routed_scaling_factor, held=CFG.held)
    want, _ = R.moe(jax.tree.map(lambda a: a.astype(jnp.float32), moe), h, CFG,
                    "float32", expert_block=4)
    assert rel(y, want) < 1e-5
    # every assignment reached an expert, whatever the imbalance
    assert int(load.sum()) == h.shape[0] * CFG.n_experts_per_tok
    if case == "all_to_the_same":
        assert sorted(np.asarray(load)[np.asarray(load) > 0]) == [h.shape[0]] * 3


def test_dead_rows_are_routed_nowhere():
    h, router = _router_case("random")
    moe = {**W.layer_from_seed(W.seed_key(5), CFG, 1)["moe"], "router": router}
    valid = jnp.arange(h.shape[0]) % 2 == 0
    idx, w = sigmoid_topk_route(h, router["kernel"], router["bias"],
                                CFG.n_experts_per_tok, 1.0)
    y, load = routed_experts(h, idx, w, moe["experts"], CFG.held, valid)
    full, _ = routed_experts(h, idx, w, moe["experts"], CFG.held)
    assert int(load.sum()) == int(valid.sum()) * CFG.n_experts_per_tok
    assert float(jnp.abs(y[~valid]).max()) == 0.0
    assert rel(y[valid], full[valid]) < 1e-6


@pytest.mark.parametrize("holders", [8, 2])
def test_the_holders_parts_add_up_to_the_uncut_layer(holders):
    """The chip's share of a deployment (model-configs guide, section 4): the
    experts of a layer divided over ``holders``; each routes over all of
    them and computes its own experts' part; the parts, with the shared
    experts counted once, are the uncut reference's layer output."""
    h, router = _router_case("random")
    full = W.layer_from_seed(W.seed_key(5), CFG, 1)["moe"]
    full = {**full, "router": router}
    want, _ = R.moe(jax.tree.map(lambda a: a.astype(jnp.float32), full), h, CFG,
                    "float32", expert_block=4)
    per = CFG.n_experts // holders
    total, loads = 0.0, []
    for r in range(holders):
        cfg = dataclasses.replace(CFG, experts_held=(r * per, (r + 1) * per))
        mine = W.layer_from_seed(W.seed_key(5), cfg, 1)["moe"]
        assert mine["experts"]["w_gate"].shape[0] == per
        idx, w = sigmoid_topk_route(h, router["kernel"], router["bias"],
                                    cfg.n_experts_per_tok,
                                    cfg.routed_scaling_factor)
        part, load = routed_experts(h, idx, w, mine["experts"], cfg.held)
        total = total + part
        loads.append(load)
        # the reference given the same share agrees part by part
        ref_part, _ = R.moe(
            jax.tree.map(lambda a: a.astype(jnp.float32),
                         {**mine, "router": router}),
            h, cfg, "float32", expert_block=per, held=cfg.held, shared=False)
        assert float(jnp.abs(part - ref_part).max()) < 1e-5
    sh = full["shared"]
    shared_once = swiglu(h, sh["w_gate"]["kernel"], sh["w_up"]["kernel"],
                         sh["w_down"]["kernel"])
    assert rel(total + shared_once, want) < 1e-5
    assert int(jnp.concatenate(loads).sum()) == h.shape[0] * CFG.n_experts_per_tok


# ------------------------------------------------------ the paged latent pool
def _prefill_then_decode(seed, prompts, n_new):
    """Two requests of different lengths in one prefill wave, then decode
    steps through the page table, straight on the programs."""
    P = serving_programs(CFG)
    params = W.make_params(W.seed_key(seed), CFG, 2)
    PS, B = 8, 4
    (pool,) = P.make_cache(CFG, PS, 40, None)
    assert pool.shape == (CFG.n_layers, 40, PS, CFG.latent_width)
    pad = -(-max(map(len, prompts)) // PS) * PS
    maxp = 128 // PS
    tables = np.zeros((B, maxp), np.int32)
    toks = np.zeros((2, pad), np.int32)
    nxt = 1
    for j, p in enumerate(prompts):
        n = -(-(len(p) + n_new) // PS)
        tables[j, :n] = np.arange(nxt, nxt + n)
        nxt += n
        toks[j, :len(p)] = p
    lens = np.array([len(p) for p in prompts], np.int32)
    key = jax.random.PRNGKey(0)
    first, pool = P.prefill_batch(
        params, None, jnp.zeros(2, jnp.int32), jnp.asarray(toks),
        jnp.asarray(tables[:2, :pad // PS]), pool, jnp.asarray(lens),
        jnp.zeros(2, jnp.float32), key, CFG)
    tok = np.zeros(B, np.int32)
    tok[:2] = np.asarray(first)
    seq = np.zeros(B, np.int32)
    seq[:2] = lens
    active = np.array([True, True, False, False])
    rows, _, _, pool = P.decode_multi(
        params, None, jnp.zeros(B, jnp.int32), jnp.asarray(tok),
        jnp.asarray(seq), jnp.asarray(tables), pool, jnp.asarray(active),
        jnp.zeros(B, jnp.float32), key, CFG, n_new - 1)
    rows = np.asarray(rows)
    out = [[int(first[j]), *rows[:, j].tolist()] for j in range(2)]
    return out, rows[:, B:], pool, tables


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_prefill_then_decode_through_the_pool_matches_the_full_forward(seed):
    rng = np.random.default_rng(seed % 1000)
    prompts = [rng.integers(3, CFG.vocab_size, n).tolist() for n in (21, 37)]
    n_new = 6
    out, stats, pool, tables = _prefill_then_decode(seed, prompts, n_new)
    for j, (p, o) in enumerate(zip(prompts, out)):
        seq = jnp.asarray([p + o[:-1]], jnp.int32)
        want = R.forward(seed, CFG, seq, zero_col=2)
        # every emitted token is the reference's best logit at its position
        assert o == [int(t) for t in jnp.argmax(want["logits"][0, len(p) - 1:], -1)]
        # the last layer's cache rows, prompt and decoded positions alike
        n_rows = len(p) + n_new - 1
        got = pool[CFG.n_layers - 1][jnp.asarray(tables[j])].reshape(
            -1, CFG.latent_width)[:n_rows]
        r = CFG.kv_lora_rank
        for part, ref in ((got[:, :r], want["rows"][-1, 0, :, :r]), (got[:, r:], want["rows"][-1, 0, :, r:])):
            assert rel(part[:len(p)], ref[:len(p)]) < 1e-5     # prefill
            assert rel(part[len(p):], ref[len(p):]) < 1e-5     # decode
    # a step's sums over the 2 expert layers: 2 live slots x 3 experts each
    assert (stats[:, 0] == 2 * CFG.n_moe_layers * CFG.n_experts_per_tok).all()
    assert (stats[:, 3] == CFG.n_moe_layers * CFG.n_experts).all()
    assert ((stats[:, 1] >= CFG.n_moe_layers * CFG.n_experts_per_tok)
            & (stats[:, 1] <= stats[:, 0])).all()
    assert ((stats[:, 2] >= CFG.n_moe_layers) & (stats[:, 2] <= 2 * CFG.n_moe_layers)).all()


# ------------------------------------------------------------- the engine
def _engine(**kw):
    params = W.make_params(W.seed_key(5), CFG, 2)
    kw = {"max_batch": 4, "page_size": 8, "n_pages": 64, "max_seq_len": 128,
          "eos_id": 2, **kw}
    return ContinuousBatchingEngine(params, CFG, **kw)


@pytest.mark.parametrize("eos_id", [2, None])  # the reactive and planned loops
def test_engine_generate_is_the_references_greedy_tokens(eos_id):
    from ray_tpu.utils import metrics

    async def run():
        eng = _engine(eos_id=eos_id)
        await eng.start()
        rng = np.random.default_rng(0)
        prompts = [rng.integers(3, CFG.vocab_size, n).tolist() for n in (21, 37, 9)]
        outs = await asyncio.gather(*(eng.generate(p, max_tokens=m)
                                      for p, m in zip(prompts, (12, 9, 20))))
        await eng.stop()
        return eng, prompts, outs

    before = metrics.stage_totals()
    eng, prompts, outs = asyncio.run(run())
    for p, o in zip(prompts, outs):
        seq = jnp.asarray([p + o[:-1]], jnp.int32)
        want = R.forward(5, CFG, seq, zero_col=2)
        assert o == [int(t) for t in jnp.argmax(want["logits"][0, len(p) - 1:], -1)]
    assert len(eng.free_pages) == 63 and len(eng.cache) == 1
    after = metrics.stage_totals()

    def grown(name):
        return (after[name][""]["sum"]
                - before.get(name, {}).get("", {"sum": 0})["sum"])

    assert grown("rt_llm_moe_assignments_total") > 0
    assert 0 < grown("rt_llm_moe_experts_touched_total") <= grown(
        "rt_llm_moe_assignments_total")
    assert grown("rt_llm_moe_expert_slots_total") % (
        CFG.n_moe_layers * CFG.n_experts) == 0
    assert set(eng._last_stats) == set(serving_programs(CFG).stats)


def _kv_counters():
    from ray_tpu.utils import metrics

    totals = metrics.stage_totals()
    return tuple(
        totals[f"rt_llm_decode_kv_tokens_{n}_total"].get("", {}).get("sum", 0)
        for n in ("live", "read"))


def test_engine_read_counters_say_which_decode_path_ran(monkeypatch):
    """The family's ``decode_in_place`` is what the engine's read counters
    go by: where the window is gathered (this backend) a step fetches slots
    x table x page positions; where the latent pool is attended in place
    (``_reads_in_place`` answered for the test; the kernel interpreted) it
    fetches the whole pages the live lengths span — and the greedy tokens
    are the same. The Llama family's twin is ``tests/test_llm.py::
    test_engine_decode_in_place_matches_gathered``."""
    from ray_tpu.llm import mla_moe as programs

    async def lone():
        # the planned loop dispatches exactly the request's 8 decode steps:
        # two blocks of 4 from lengths 5 and 9, attending 6..13 positions
        eng = _engine(eos_id=None, block_buckets=(4,))
        await eng.start()
        before = _kv_counters()
        out = await eng.generate([5, 6, 7, 8, 9], max_tokens=9)
        grown = tuple(a - b for a, b in zip(_kv_counters(), before))
        await eng.stop()
        return eng, out, grown

    eng, gathered, grown = asyncio.run(lone())
    assert not eng._kv_in_place and len(gathered) == 9
    # 4 slots x 16 pages x 8 tokens a step, whatever is live
    assert grown == (sum(range(6, 14)), 8 * 4 * 16 * 8)
    assert eng._last_kv == {"kv_live": sum(range(10, 14)) / 4,
                            "kv_read": 4 * 16 * 8}

    monkeypatch.setattr(programs, "_reads_in_place", lambda: True)
    programs.mla_moe_decode_multi.clear_cache()  # traced with the other answer
    try:
        eng, in_place, grown = asyncio.run(lone())
    finally:
        programs.mla_moe_decode_multi.clear_cache()
    assert eng._kv_in_place and in_place == gathered
    # whole pages of 8: one for 6..8 positions, two for 9..13
    assert grown == (sum(range(6, 14)), 3 * 8 + 5 * 16)
    assert eng._last_kv == {"kv_live": sum(range(10, 14)) / 4, "kv_read": 16}


@pytest.mark.parametrize("feature,make", [
    ("kv_dtype='int8'", lambda: _engine(kv_dtype="int8")),
    ("lora_adapters", lambda: _engine(lora_adapters={"a": {}})),
    ("spec_enable", lambda: _engine(spec_enable=True)),
    ("export_pages", lambda: _engine().export_pages(1)),
    ("submit_prefilled", lambda: _engine().submit_prefilled([1], None, None, 3)),
    ("a K or V pool", lambda: _engine().kpool),
    ("static-batch generate", lambda: __import__(
        "ray_tpu.llm.generation", fromlist=["generate"]).generate(
            None, CFG, [[1, 2]])),
    ("disaggregated serving", lambda: __import__(
        "ray_tpu.llm.disagg.pools", fromlist=["PrefillWorker"]).PrefillWorker(CFG)),
    ("disaggregated serving", lambda: __import__(
        "ray_tpu.llm.disagg.pools", fromlist=["DecodeWorker"]).DecodeWorker(CFG)),
])
def test_what_assumes_k_and_v_pools_is_refused_by_name(feature, make):
    with pytest.raises(UnsupportedByModel, match=feature.split("(")[0]) as e:
        make()
    assert "mla_moe" in str(e.value) or "MlaMoeConfig" in str(e.value)


def test_llama_engine_keeps_its_two_pools():
    from ray_tpu.models.llama import LlamaConfig, llama_init

    cfg = LlamaConfig.tiny()
    eng = ContinuousBatchingEngine(llama_init(jax.random.PRNGKey(0), cfg), cfg,
                                   n_pages=16)
    assert eng.programs.family == "llama" and not eng.programs.stats
    assert eng.kpool is eng.cache[0] and eng.vpool is eng.cache[1]
    assert eng.kpool.shape == (cfg.n_layers, 16, 16, cfg.n_kv_heads, cfg.head_dim)
    with pytest.raises(TypeError, match="no serving programs"):
        serving_programs(object())

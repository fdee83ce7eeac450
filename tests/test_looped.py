"""The looped family served: prefill then decode through the planes of every
pass (``llm/looped.py``) against the benchmark's plain float32 reference at
every prompt length that matters, the pools' rows pass by pass, one to four
passes, the exit rule, and bf16. The tiny size, the engine and the
comparison: ``tests/_looped_common.py``; the planes' probes and admission
with too few pages, the kernels interpreted, and the controls have files of
their own beside this one."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _looped_common import (CASES, CFG, L, PS, SEED, U, R, W, _engine,
                            _logit_gaps, _serve, _serve_one, _slot_rows, rel)
from ray_tpu.models.looped import (LoopedConfig, looped_exit,
                                   looped_exit_start, looped_forward,
                                   looped_init)
from ray_tpu.ops.attention import masked_attention
from ray_tpu.ops.basic import rms_norm, rope, rope_freqs
from ray_tpu.utils import metrics


def test_tiny_keeps_the_published_shape():
    full = LoopedConfig()
    assert (full.n_layers, full.n_passes, full.planes) == (48, 4, 192)
    assert (full.n_heads, full.n_kv_heads, full.head_dim) == (16, 16, 128)
    assert (full.d_model, full.d_ff, full.vocab_size) == (2048, 5632, 49152)
    assert (full.rope_theta, full.rms_norm_eps, full.exit_threshold) == (
        1e6, 1e-6, 1.0)
    # 1.5 MiB of cache a position
    assert full.planes * 2 * full.n_kv_heads * full.head_dim * 2 == 1572864
    assert (CFG.n_heads, CFG.n_kv_heads, CFG.planes) == (4, 4, 12)
    assert CFG.d_ff / CFG.d_model == full.d_ff / full.d_model == 2.75
    params = looped_init(jax.random.PRNGKey(0), CFG)
    seeded = W.make_params(W.seed_key(0), CFG)
    assert (jax.tree.map(lambda x: (x.shape, x.dtype), params)
            == jax.tree.map(lambda x: (x.shape, x.dtype), seeded))
    assert sorted(params["layers_0"]) == [
        "norm1", "norm2", "norm3", "norm4", "w_down", "w_gate_up", "wo", "wqkv"]
    n = sum(x.size for x in jax.tree.leaves(jax.eval_shape(
        lambda: looped_init(jax.random.PRNGKey(0), full))))
    assert 2.66e9 < n < 2.68e9   # the published 2.6 B


@pytest.fixture(scope="module")
def served():
    eng = _engine()
    prompts, outs = _serve(eng, CASES)
    assert len(eng.free[0]) == 48   # every page back
    return prompts, outs


@pytest.mark.parametrize("case", range(len(CASES)),
                         ids=[f"{n}+{m}" for n, m in CASES])
def test_prefill_then_decode_through_the_planes_is_the_reference(served, case):
    prompts, outs = served
    assert len(outs[case]) == CASES[case][1]
    assert float(_logit_gaps(CFG, prompts[case], outs[case]).max()) == 0.0


def test_the_reactive_loop_serves_the_references_tokens():
    cases = [CASES[-1]]
    prompts, outs = _serve(_engine(eos_id=300), cases)
    assert float(_logit_gaps(CFG, prompts[0], outs[0]).max()) == 0.0


@pytest.fixture(scope="module")
def left_in_the_pools():
    """A request across three pages' edges: (what it left in every plane,
    the reference's forward over the same tokens)."""
    eng = _engine()
    prompt = np.random.default_rng(1).integers(3, CFG.vocab_size, 21).tolist()
    drawn = list(eng.free[0][:eng._pages_of(21 + 20)[0]])
    assert len(drawn) == 6
    out = _serve_one(eng, prompt, 20)
    n_rows = 21 + 20 - 1
    assert [c.shape for c in eng.cache] == [(12, 49, PS, 4, 16)] * 2
    return _slot_rows(eng, drawn, n_rows), R.forward(SEED, CFG, prompt + out[:-1])


@pytest.mark.parametrize("u", range(U))
def test_the_pools_hold_every_pass_in_planes_of_its_own(left_in_the_pools, u):
    """Pass ``u`` of layer ``l`` lies in plane ``u L + l``, prompt rows and
    decoded rows alike, and is no other pass's rows."""
    got, want = left_in_the_pools
    for l in range(L):
        for n in "kv":
            assert rel(got[n][u * L + l], want[n][u * L + l]) < 1e-5
            if u:  # the passes differ: a plane is not the one below it
                assert rel(got[n][u * L + l], want[n][(u - 1) * L + l]) > 0.05


@pytest.mark.parametrize("n_passes", [1, 2, 3, 4])
def test_any_number_of_passes_is_the_reference(n_passes):
    cfg = dataclasses.replace(CFG, n_passes=n_passes)
    eng = _engine(cfg=cfg)
    assert eng.cache[0].shape[0] == n_passes * L
    prompts, outs = _serve(eng, [(11, 10)])
    assert float(_logit_gaps(cfg, prompts[0], outs[0]).max()) == 0.0
    logits, depth = looped_forward(eng.params, jnp.asarray([prompts[0]]), cfg)
    want = R.forward(SEED, cfg, prompts[0], planes=())
    assert rel(logits[0], want["logits"]) < 1e-5
    assert (np.asarray(depth[0]) == n_passes).all()


def test_one_pass_is_a_plain_sandwich_norm_transformer():
    """``n_passes`` 1, the gate aside: embed, then a layer after the other —
    N1, attention, N2, residual, N3, SwiGLU, N4, residual —, the final norm,
    the head: written out here from the tree."""
    cfg = dataclasses.replace(CFG, n_passes=1)
    params = W.make_params(W.seed_key(SEED), cfg)
    tokens = jnp.asarray([np.random.default_rng(2).integers(3, 256, 19)])
    cos, sin = rope_freqs(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    T, H, hd = tokens.shape[1], cfg.n_heads, cfg.head_dim
    causal = (jnp.arange(T)[:, None] >= jnp.arange(T)[None, :])[None]
    x = params["tok"]["embedding"][tokens]
    for i in range(cfg.n_layers):
        w = params[f"layers_{i}"]
        a = rms_norm(x, w["norm1"]["scale"], cfg.rms_norm_eps)
        q, k, v = jnp.split((a @ w["wqkv"]["kernel"]).reshape(1, T, 3 * H, hd),
                            3, axis=2)
        o = masked_attention(rope(q, cos, sin), rope(k, cos, sin), v, causal)
        x = x + rms_norm(o @ w["wo"]["kernel"], w["norm2"]["scale"],
                         cfg.rms_norm_eps)
        m = rms_norm(x, w["norm3"]["scale"], cfg.rms_norm_eps)
        g, up = jnp.split(m @ w["w_gate_up"]["kernel"], 2, axis=-1)
        x = x + rms_norm((jax.nn.silu(g) * up) @ w["w_down"]["kernel"],
                         w["norm4"]["scale"], cfg.rms_norm_eps)
    want = rms_norm(x, params["norm"]["scale"], cfg.rms_norm_eps
                    ) @ params["head"]["kernel"]
    got, depth = looped_forward(params, tokens, cfg)
    assert rel(got, want) < 1e-5 and (np.asarray(depth) == 1).all()


def _stat(name):
    return metrics.stage_totals()[f"rt_llm_looped_{name}_total"].get(
        "", {}).get("sum", 0)


@pytest.mark.parametrize("threshold", [0.3, 0.5, 0.9, 1.0])
def test_the_exit_rule_picks_the_references_pass_token_by_token(threshold):
    """The served tokens are the argmax of the reference's logits at ITS
    chosen pass's state, and the exit-depth counter grew by the sum of the
    passes the reference chose for the decoded positions."""
    cfg = dataclasses.replace(CFG, exit_threshold=threshold)
    eng = _engine(cfg=cfg, block_buckets=(4,))
    before = _stat("exit_depth"), _stat("live_slots")
    prompts, outs = _serve(eng, [(13, 13)])
    prompt, out = prompts[0], outs[0]
    want = R.forward(SEED, cfg, prompt + out[:-1], logits_from=len(prompt) - 1,
                     planes=())
    assert [int(t) for t in np.argmax(np.asarray(want["logits"]), -1)] == out
    depth = want["depth"]
    if threshold == 1.0:
        assert (depth == U).all()
    elif threshold < 0.9:
        assert len(set(depth.tolist())) > 1   # the rule picks by token
    # 12 decode steps emitted tokens 2 .. 13 at positions 13 .. 24: three
    # blocks of 4, one live slot of four
    grew = [_stat(n) - b for n, b in zip(("exit_depth", "live_slots"), before)]
    steps = eng.steps
    assert grew[1] == steps >= 12
    if steps == 12:  # no run-on block: the decoded positions alone
        assert grew[0] == int(depth[1:].sum())
    else:            # a run-on block's steps choose passes too
        assert grew[0] >= int(depth[1:].sum())


def test_a_saturated_gate_exits_at_its_pass():
    """``looped_exit`` over given gates against the reference's rule, a gate
    of exactly 1.0 at pass 2 among them: everything is left there."""
    lams = np.array([[0.3, 1.0, 0.5, 0.2], [0.2, 0.3, 0.4, 0.5],
                     [0.6, 0.1, 0.9, 0.9], [0.05, 0.1, 0.2, 0.9]], np.float32).T
    for threshold in (0.3, 0.5, 0.9, 1.0):
        cfg = dataclasses.replace(CFG, exit_threshold=threshold)
        g = jnp.arange(4 * 4, dtype=jnp.float32).reshape(4, 4)
        state = looped_exit_start(g)
        for u in range(U):
            state = looped_exit(state, g + 100 * u, jnp.asarray(lams[u]), u, cfg)
        pdf, depth = R.exit_rule(lams, threshold)
        assert np.asarray(state[3]).tolist() == depth.tolist()
        assert np.allclose(pdf.sum(0), 1.0, atol=1e-6)
        # the chosen state is the chosen pass's
        assert np.asarray(state[2][:, 0]).tolist() == [
            float(4 * i + 100 * (d - 1)) for i, d in enumerate(depth)]
    assert R.exit_rule(lams, 1.0)[1].tolist() == [2, 4, 4, 4]
    assert R.exit_rule(lams, 0.5)[1].tolist() == [2, 3, 1, 4]


def test_bf16_programs_stay_within_a_stated_tolerance():
    """The same comparison in the type the cell serves: most tokens the
    reference's own and the rest within 0.3 of a logit spread of its best,
    every plane's rows within 3 % of the reference's."""
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    eng = _engine(cfg=cfg)
    prompt = np.random.default_rng(1).integers(3, CFG.vocab_size, 21).tolist()
    drawn = list(eng.free[0][:6])
    out = _serve_one(eng, prompt, 20)
    gaps = _logit_gaps(cfg, prompt, out)
    assert float(np.percentile(gaps, 60)) == 0.0 and float(gaps.max()) < 0.3
    want = R.forward(SEED, cfg, prompt + out[:-1])
    got = _slot_rows(eng, drawn, 40)
    for p in range(cfg.planes):
        assert rel(got["k"][p].astype(np.float32), want["k"][p]) < 0.03

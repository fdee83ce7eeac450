"""The parts of the window-with-a-sink family's layer and of its cache: the
no-cache forward against the reference, the rotation of a third of a head at the kind's own base, layer 0's dense half,
the sixteen holders' parts against the uncut layer, the allocator and the
counters by kind of page, and what the engine refuses by name."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _sink_moe_common import (CFG, PS, RING, SEED, WINDOW, R, W, _engine,
                              _serve, rel)
from ray_tpu.llm import sink_moe as programs
from ray_tpu.llm.engine import UnsupportedByModel, serving_programs
from ray_tpu.models.sink_moe import (SinkMoeConfig, sink_moe_forward,
                                     sink_project, sink_rope_freqs)
from ray_tpu.parallel.moe import routed_experts, sigmoid_topk_route
from ray_tpu.utils import metrics


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_forward_logits_match_the_plain_reference(seed):
    params = W.make_params(W.seed_key(seed), CFG)
    tokens = np.random.default_rng(seed % 1000).integers(3, CFG.vocab_size, 70)
    want = R.forward(seed, CFG, tokens, q_block=32, probe=(1, 5))
    got = sink_moe_forward(params, jnp.asarray(tokens)[None], CFG)[0]
    assert rel(got, want["logits"]) < 1e-5
    assert sorted(want["chosen"]) == [1, 2, 3, 4, 5, 6]     # layer 0 is dense
    assert want["chosen"][1].shape == (70, CFG.n_experts_per_tok)
    # the sink takes a real share of a window query's mass, a full layer none
    share = np.asarray(want["sink_share"][1])
    assert 0.1 < float(np.median(share)) < 0.6
    assert not np.asarray(want["sink_share"][5]).any()
    assert want["att"][1].shape == (70, CFG.n_heads * CFG.v_head_dim)


# ---------------------------------------------------------------- the layer
def test_a_third_of_a_head_rotates_at_the_kinds_own_base():
    """Lanes past ``rotary_lanes`` pass as the projection left them, position
    0 rotates nothing, and the two kinds' bases give different rows."""
    cfg = SinkMoeConfig.tiny(max_seq_len=64)
    layer = W.layer_from_seed(W.seed_key(1), cfg, 1)
    h = jax.random.normal(jax.random.PRNGKey(0), (2, 9, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(9) * 5, (2, 9))
    ropes = sink_rope_freqs(cfg)
    q, k, v = sink_project(layer, h, ropes, pos, cfg, True)
    plain_q = (h @ layer["wq"]["kernel"]).reshape(2, 9, 8, 24)
    plain_k = (h @ layer["wk"]["kernel"]).reshape(2, 9, 4, 24)
    assert jnp.array_equal(q[..., 8:], plain_q[..., 8:])
    assert jnp.array_equal(k[..., 8:], plain_k[..., 8:])
    assert jnp.allclose(q[:, 0], plain_q[:, 0]) and not jnp.allclose(
        q[:, 1:, :, :8], plain_q[:, 1:, :, :8], atol=1e-3)
    assert rel(v, 0.707 * (h @ layer["wv"]["kernel"]).reshape(2, 9, 4, 16)) < 1e-6
    # the reference's rotation, lane for lane
    want = R.rotate_lanes(plain_k[0], 8, cfg.swa_rope_theta)
    assert rel(k[0, 1], want[5]) > 0.01   # row 1 sits at position 5, not 1
    same = sink_project(layer, h, ropes, jnp.broadcast_to(jnp.arange(9), (2, 9)),
                        cfg, True)[1]
    assert rel(same[0], want) < 1e-5
    # a full layer's table turns more slowly
    assert not jnp.allclose(ropes[True][1][7], ropes[False][1][7], atol=1e-3)


def test_layer_0_is_dense_and_its_load_is_none():
    from ray_tpu.models.sink_moe import sink_ffn
    from ray_tpu.ops.basic import swiglu

    layer = W.layer_from_seed(W.seed_key(2), CFG, 0)
    g = jax.random.normal(jax.random.PRNGKey(3), (1, 11, CFG.d_model))
    y, load = sink_ffn(layer, g, CFG)
    f = layer["ffn"]
    assert load is None and f["w_gate"].shape == (CFG.d_model, CFG.d_ff)
    assert rel(y, swiglu(g, f["w_gate"], f["w_up"], f["w_down"])) < 1e-6
    y, load = sink_ffn(W.layer_from_seed(W.seed_key(2), CFG, 1), g, CFG)
    assert load.shape == (8,) and y.shape == g.shape


# ---------------------------------------------------------------- the share
@pytest.mark.parametrize("holders", [16, 2])
def test_holders_parts_add_up_to_the_uncut_layer(holders):
    """The chip's share of a deployment (model-configs guide, section 4): the
    16 experts of a layer divided over ``holders``; each routes over all of
    them (the bias choosing, the score weighing) and computes its own
    experts' part; the parts are the uncut reference's expert half — the
    layer has no shared expert, and what every holder computes alike
    (attention, layer 0's dense half) is counted once, outside this sum."""
    whole = dataclasses.replace(CFG, experts_held=None)
    key = W.layer_key(W.seed_key(SEED), 1)
    full = W.layer_from_seed(W.seed_key(SEED), whole, 1)["moe"]
    h = jax.random.normal(jax.random.PRNGKey(1), (37, CFG.d_model))
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), full)
    want, _, chosen = R.moe(f32, h, whole, "float32")
    per = CFG.n_experts // holders
    total, loads = 0.0, []
    for r in range(holders):
        cfg = dataclasses.replace(CFG, experts_held=(r * per, (r + 1) * per))
        mine = {n: W.expert_stack(key, cfg, i)
                for i, n in enumerate(("w_gate", "w_up", "w_down"))}
        assert mine["w_gate"].shape[0] == per
        assert jnp.array_equal(mine["w_up"],
                               full["experts"]["w_up"][r * per:(r + 1) * per])
        idx, w = sigmoid_topk_route(h, full["router"]["kernel"],
                                    full["router"]["bias"],
                                    cfg.n_experts_per_tok, 1.0)
        assert jnp.array_equal(jnp.sort(idx, -1), jnp.sort(chosen, -1))
        part, load = routed_experts(h, idx, w, mine, cfg.held)
        total = total + part
        loads.append(load)
        # the holder's part alone is the reference's held share, by its mask
        if r == 1:
            assert rel(part, R.moe({**f32, "experts": mine}, h, whole, "float32",
                                   held=cfg.held)[0]) < 1e-5
    assert rel(total, want) < 1e-5
    assert int(jnp.concatenate(loads).sum()) == h.shape[0] * CFG.n_experts_per_tok
    # the bias chooses: without it other experts are chosen for some tokens
    plain, _ = sigmoid_topk_route(h, full["router"]["kernel"], None,
                                  CFG.n_experts_per_tok, 1.0)
    assert not jnp.array_equal(jnp.sort(plain, -1), jnp.sort(chosen, -1))


# ------------------------------------------------------------- the allocator
def test_a_slot_never_holds_more_window_pages_than_the_ring():
    eng = _engine()
    full, window = eng.kinds
    assert (full.name, full.layers, full.table, full.reach) == ("full", 2, 20, None)
    assert (window.name, window.layers, window.table, window.reach) == (
        "window", 5, RING, WINDOW)
    for n in (1, 8, 9, 23, 24, 25, 100, 160):
        assert eng._pages_of(n) == [-(-n // PS), min(-(-n // PS), RING)]
    before = metrics.stage_totals()
    _serve(eng, [(70, 13)])   # 12 decode steps: blocks 8 + 4
    after = metrics.stage_totals()

    def grown(name, tag=""):
        return (after[name][tag]["sum"]
                - before.get(name, {}).get(tag, {"sum": 0})["sum"])

    assert grown("rt_llm_pages_drawn_total", "full") == 11       # ceil(83 / 8)
    assert grown("rt_llm_pages_drawn_total", "window") == RING
    # reads: a full layer's reach is the length, a window layer's 16 at most
    live_w = grown("rt_llm_decode_kv_tokens_live_total", "window")
    live_f = grown("rt_llm_decode_kv_tokens_live_total", "full")
    assert live_f == sum(range(71, 83)) and live_w == 12 * WINDOW
    # off the TPU a step gathers every slot's whole table: the ring's rows
    read_w = grown("rt_llm_decode_kv_tokens_read_total", "window")
    assert read_w == 12 * eng.B * RING * PS
    assert grown("rt_llm_decode_kv_tokens_live_total") == pytest.approx(
        (5 * live_w + 2 * live_f) / 7)
    assert serving_programs(CFG).prefill_wave_limit == (8, 16384)
    assert serving_programs(CFG) is programs.PROGRAMS



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
    assert "sink_moe" in str(e.value)

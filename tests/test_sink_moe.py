"""The window-with-a-sink + full attention sparse-expert family served:
prefill then decode through both kinds of pages (``llm/sink_moe.py``) against
the benchmark's plain float32 reference at every prompt length that matters,
the reactive loop, the pools' rows in both geometries, and bf16. The tiny
size, the engine and the comparison: ``tests/_sink_moe_common.py``; the
controls, the kernels interpreted and the layer's parts have files of their
own beside this one."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _sink_moe_common import (CASES, CFG, FEW, PS, RING, SEED, R, W, _engine,
                              _logit_gaps, _serve, _serve_one, rel)
from ray_tpu.models.sink_moe import (SinkMoeConfig, sink_moe_forward,
                                     sink_moe_init)


def test_tiny_keeps_the_published_shape():
    full = SinkMoeConfig()
    assert CFG.layer_window == full.layer_window[:7]
    assert CFG.layer_moe == full.layer_moe[:7]
    assert CFG.layers_of(False) == (0, 5) and CFG.layers_of(True) == (1, 2, 3, 4, 6)
    assert [i for i in range(48) if not full.is_window(i)] == [
        0, 5, 11, 17, 23, 29, 35, 41, 47]
    assert (full.rotary_lanes, CFG.rotary_lanes) == (64, 8)
    assert (full.kv_heads(False), full.kv_heads(True)) == (4, 8)
    assert (CFG.kv_heads(False), CFG.kv_heads(True)) == (2, 4)
    assert CFG.held == (4, 12) and CFG.vocab_size == 256
    with pytest.raises(ValueError, match="layer_window"):
        SinkMoeConfig.tiny(n_layers=3)
    params = sink_moe_init(jax.random.PRNGKey(0), CFG)
    seeded = W.make_params(W.seed_key(0), CFG)
    assert (jax.tree.map(lambda x: (x.shape, x.dtype), params)
            == jax.tree.map(lambda x: (x.shape, x.dtype), seeded))
    # a sink a window layer, none on a full one; layer 0 dense, the rest routed
    assert [("sink" in params[f"layers_{i}"]) for i in range(7)] == list(
        CFG.layer_window)
    assert [("ffn" in params[f"layers_{i}"]) for i in range(7)] == [True] + [False] * 6



@pytest.fixture(scope="module")
def served():
    eng = _engine()
    prompts, outs = _serve(eng, CASES)
    assert [len(f) for f in eng.free] == [80, 15]   # every page of both kinds back
    return prompts, outs


@pytest.mark.parametrize("case", range(len(CASES)),
                         ids=[f"{n}+{m}" for n, m in CASES])
def test_prefill_then_decode_through_both_kinds_of_pages_is_the_reference(
        served, case):
    prompts, outs = served
    assert len(outs[case]) == CASES[case][1]
    assert float(_logit_gaps(CFG, prompts[case], outs[case]).max()) == 0.0


def test_the_reactive_loop_serves_the_references_tokens():
    cases = [CASES[i] for i in FEW]
    prompts, outs = _serve(_engine(eos_id=300), cases)
    assert [len(o) for o in outs] == [m for _, m in cases]
    for p, o in zip(prompts, outs):
        assert float(_logit_gaps(CFG, p, o).max()) == 0.0


def test_the_pools_hold_the_references_rows_in_both_geometries():
    """Cache rows: a request past three rings leaves the full layers' rows
    whole at 2 KV heads, and of the window layers' at 4 KV heads the ring's
    last pages, each at entry ``page % 3`` of the slot's table (the oldest
    is left out: the last fused block decodes past the last token, into the
    page that lies over it). A key lies in the first 24 of its row's lanes,
    the rest zeros; a value is its 16 as it is, scaled."""
    eng = _engine()
    prompt = np.random.default_rng(1).integers(3, CFG.vocab_size, 50).tolist()
    drawn = [list(f[:n]) for f, n in zip(eng.free, eng._pages_of(80))]
    assert [len(d) for d in drawn] == [10, RING]
    out = _serve_one(eng, prompt, 30)
    n_rows = 50 + 30 - 1
    want = R.forward(SEED, CFG, prompt + out[:-1], q_block=32)
    kf, vf, kw, vw = eng.cache
    assert kf.shape[1:] == (81, PS, 2, 128) and vf.shape[1:] == (81, PS, 2, 16)
    assert kw.shape[1:] == (16, PS, 4, 128) and vw.shape[1:] == (16, PS, 4, 16)
    assert (kf.shape[0], kw.shape[0]) == (2, 5)
    for at, layer in enumerate(CFG.layers_of(False)):
        rows = kf[at][jnp.asarray(drawn[0])].reshape(-1, 2, 128)[:n_rows]
        assert not rows[..., 24:].any()
        assert rel(rows[..., :24].reshape(n_rows, -1), want["k"][layer]) < 1e-5
        got = vf[at][jnp.asarray(drawn[0])].reshape(-1, 2 * 16)[:n_rows]
        assert rel(got, want["v"][layer]) < 1e-5
    last = (n_rows - 1) // PS
    for page in range(last - RING + 2, last + 1):
        rows = slice(page * PS, min((page + 1) * PS, n_rows))
        for at, layer in enumerate(CFG.layers_of(True)):
            got = vw[at][drawn[1][page % RING]].reshape(PS, 4 * 16)
            assert rel(got[:rows.stop - rows.start], want["v"][layer][rows]) < 1e-5
            got = kw[at][drawn[1][page % RING]][..., :24].reshape(PS, 4 * 24)
            assert rel(got[:rows.stop - rows.start], want["k"][layer][rows]) < 1e-5



def test_bf16_programs_stay_within_a_stated_tolerance():
    """The same comparison in the type the cell serves: three tokens in four
    the reference's own and the rest within 0.3 of a logit spread of its
    best (one near-tie in twelve flips, at 0.24), the no-cache forward
    within 5 % of the reference rounded alike."""
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    prompts, outs = _serve(_engine(cfg=cfg), [CASES[9]])
    gaps = _logit_gaps(cfg, prompts[0], outs[0])
    assert float(np.percentile(gaps, 75)) == 0.0 and float(gaps.max()) < 0.3
    seq = prompts[0] + outs[0][:-1]
    low = R.forward(SEED, cfg, seq, q_block=32)
    want = sink_moe_forward(W.make_params(W.seed_key(SEED), cfg),
                            jnp.asarray([seq]), cfg)
    assert rel(want[0].astype(jnp.float32), low["logits"]) < 0.05

"""The controls of the window-with-a-sink family: a reference with other
mathematics is not what the served program computes, judged on ONE served
request (three rings long). ``tests/_sink_moe_common.py`` has the tiny size
and the comparison."""
import jax
import pytest

from _sink_moe_common import (CASES, CFG, FEW, SEED, WINDOW, R, _engine,
                              _logit_gaps, _serve, rel)


@pytest.fixture(scope="module")
def served():
    """The one request the controls are judged on, its prompt the one the
    served-lengths file gives that case."""
    prompts, outs = _serve(_engine(), CASES, only=FEW)
    return dict(zip(FEW, prompts)), dict(zip(FEW, outs))


CONTROLS = {
    "no_sink": {"sink_window": False},
    "sink_on_full_layers_too": {"sink_full": True},
    "the_sinks_value_counted": {"sink_value": True},
    "window_15": {"sliding_window": WINDOW - 1},
    "window_17": {"sliding_window": WINDOW + 1},
    "no_window": {"sliding_window": 10**9},
    "values_unscaled": {"value_scale": 1.0},
    "whole_head_rotated": {"partial_rotary_factor": 1.0},
    "half_the_head_rotated": {"partial_rotary_factor": 0.5},
    "full_layers_base_in_window_layers": {"swa_rope_theta": 5000000.0},
    "full_layers_grouping_in_window_layers": {"window_group": 4},
    "weights_not_renormalised": {"norm_topk_prob": False},
    "bias_in_the_weight": {"bias_in_weight": True},
    "layer_0_routed": {"layer_moe": (True,) * 7},
}


@pytest.mark.parametrize("name", list(CONTROLS) + ["fp8"])
def test_a_reference_with_other_mathematics_fails_the_comparison(served, name):
    """The controls: each is a forward pass the program's tokens are not the
    greedy tokens of, by a wide margin (the sound reference: by none)."""
    prompts, outs = served
    kw = {"mode": "fp8"} if name == "fp8" else {"variant": CONTROLS[name]}
    worst = max(float(_logit_gaps(CFG, prompts[i], outs[i], **kw).max())
                for i in FEW)
    if name == "bias_in_the_weight":
        # a bias of 0.05 beside scores near 1 moves a weight by a few per
        # cent and hardly a greedy token: read in the logits themselves
        seq = prompts[FEW[-1]] + outs[FEW[-1]][:-1]
        sound = R.forward(SEED, CFG, seq, q_block=32)["logits"]
        worst = rel(R.forward(SEED, CFG, seq, q_block=32, **kw)["logits"], sound)
    jax.clear_caches()
    assert worst > 0.05

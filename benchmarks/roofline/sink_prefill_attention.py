"""What the blocked prefill kernels of ``sink_moe_prefill_batch`` must do for
prompts of given *true* lengths: a score (2 operations a lane of the 192-lane
key) and a weighted value (2 a lane of the 128-lane value) for every (query,
key) pair a layer attends, for every query head — half the square on a full
layer, at most ``sliding_window`` keys a query on a window layer (the sink is
a scalar a head: no pair). ``kinds`` picks the layers counted: the
window-with-sink kernel's alone for its own roofline share. Bound by
operations."""
from __future__ import annotations

from benchmarks.roofline.gqa_prefill_attention import pairs


def flops(cfg, true_lens: list[float], kinds=(False, True)) -> float:
    per_pair = 2 * cfg.n_heads * (cfg.head_dim + cfg.v_head_dim)
    total = 0.0
    for t in true_lens:
        for window in kinds:
            total += per_pair * len(cfg.layers_of(window)) * pairs(
                t, cfg.sliding_window if window else None)
    return total


def least_seconds(cfg, peaks: dict, true_lens: list[float]) -> float:
    """The window layers' kernel alone (``gqa_sink_prefill_attention``)."""
    return flops(cfg, true_lens, kinds=(True,)) / peaks["bf16_flops_per_s"]

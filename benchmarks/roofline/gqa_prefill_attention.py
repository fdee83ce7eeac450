"""What the ``gqa_prefill_attention`` kernel calls of a prefill must do for
prompts of given *true* lengths: two matmuls of 2 operations over every
(query, key) pair a layer attends — half the square on a full layer, the
window's band on a window layer — for every query head. Bound by
operations."""
from __future__ import annotations


def pairs(t: int, window: int | None) -> float:
    if window is None or window >= t:
        return t * (t + 1) / 2
    return window * (window + 1) / 2 + (t - window) * window


def flops(cfg, true_lens: list[int]) -> float:
    total = 0.0
    for t in true_lens:
        for i in range(cfg.n_layers):
            total += 4 * cfg.n_heads * cfg.head_dim * pairs(
                t, cfg.sliding_window if cfg.is_window(i) else None)
    return total


def least_seconds(cfg, peaks: dict, true_lens: list[int]) -> float:
    return flops(cfg, true_lens) / peaks["bf16_flops_per_s"]

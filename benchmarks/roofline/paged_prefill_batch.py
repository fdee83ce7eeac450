"""What ``paged_prefill_batch`` must do for prompts of given *true* lengths
(padding is the program's waste, not work): 2 operations per weight per
token, causal attention at half the square, and the head at each prompt's
last position only. Bound by operations at these lengths."""
from __future__ import annotations

from benchmarks.roofline.common import head_params, layer_matmul_params


def flops(cfg, true_lens: list[int]) -> float:
    total = 0.0
    for t in true_lens:
        matmul = 2 * t * cfg.n_layers * layer_matmul_params(cfg)
        # scores and weighted values: 2 matmuls x 2 operations x t*t/2 pairs
        attn = 2 * t * t * cfg.n_heads * cfg.head_dim * cfg.n_layers
        total += matmul + attn + 2 * head_params(cfg)
    return total


def least_seconds(cfg, peaks: dict, true_lens: list[int]) -> float:
    return flops(cfg, true_lens) / peaks["bf16_flops_per_s"]

"""What ``cca_moe_prefill_batch`` must do for prompts of given *true* lengths
(padding is the program's waste, not work): 2 operations a weight a token for
everything outside the routed experts (a layer's projections, its depthwise
taps, the grouped convolution's 2 taps x 10 heads x 128 x 128, its router),
2 a weight of ONE expert a token a layer (``held / n_experts`` of one where a
holder has a share), the causal pairs at 2 operations a lane of keys and
values for every query head, and the head at each prompt's last position
only. Bound by operations."""
from __future__ import annotations

from benchmarks.roofline.cca_moe_decode_multi import (
    expert_params, mixer_params, router_params)


def token_params(cfg) -> float:
    """Matmul weights a token meets over the whole depth."""
    lo, hi = cfg.held
    routed = (hi - lo) / cfg.n_experts
    return cfg.n_layers * (mixer_params(cfg) + router_params(cfg)
                           + routed * expert_params(cfg))


def attention_flops(cfg, true_lens: list[float]) -> float:
    """Two matmuls of 2 operations over every causal (query, key) pair — the
    scores over a head's lanes, the values over as many — for every query
    head of every layer: 2 x 8 x (128 + 128) a pair at the published
    widths."""
    return sum(2 * cfg.n_heads * 2 * cfg.head_dim * cfg.n_layers
               * t * (t + 1) / 2 for t in true_lens)


def flops(cfg, true_lens: list[float]) -> float:
    tokens = sum(true_lens)
    head = 2 * len(true_lens) * cfg.d_model * cfg.vocab_size
    return (2 * tokens * token_params(cfg) + attention_flops(cfg, true_lens)
            + head)


def least_seconds(cfg, peaks: dict, true_lens: list[float]) -> float:
    return flops(cfg, true_lens) / peaks["bf16_flops_per_s"]

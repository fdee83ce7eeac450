"""What ``kda_moe_prefill_batch`` must do for prompts of given *true* lengths
(padding is the program's waste, not work): 2 operations a weight a token for
everything outside the routed experts (a KDA layer's projections and taps, an
MLA layer's matrices, the dense layers, an expert layer's router and shared
expert), 2 a weight for each row routed to a HELD expert (the mean under an
even router: ``top_k . held / n_experts`` a token an expert layer, ONE in the
cell), the delta rule's own 7 operations a state element a token a KDA layer
— the one-step form's count, which is under any chunking's — the causal
pairs of the MLA layers at 2 operations a lane of the expanded keys and
values, and the head at each prompt's last position only. Bound by
operations."""
from __future__ import annotations

from benchmarks.roofline.kda_moe_decode_multi import (
    KDA, MLA, delta_flops, dense_params, expert_layer_fixed, expert_params,
    kda_params, layers, mla_params)


def token_params(cfg) -> float:
    """Matmul weights a token meets over the whole depth."""
    lo, hi = cfg.held
    routed = cfg.n_experts_per_tok * (hi - lo) / cfg.n_experts
    return (layers(cfg, KDA) * kda_params(cfg)
            + layers(cfg, MLA) * mla_params(cfg)
            + dense_params(cfg)
            + cfg.n_moe_layers * (expert_layer_fixed(cfg)
                                  + routed * expert_params(cfg)))


def scan_flops(cfg, tokens: float) -> float:
    """A token is an update of every KDA layer's state."""
    return delta_flops(cfg, tokens * layers(cfg, KDA))


def attention_flops(cfg, true_lens: list[float]) -> float:
    """Two matmuls of 2 operations over every causal (query, key) pair — the
    scores over nope + rope lanes, the values over v lanes — for every head
    of every MLA layer."""
    return sum(2 * cfg.n_heads * (cfg.qk_head_dim + cfg.v_head_dim)
               * layers(cfg, MLA) * t * (t + 1) / 2 for t in true_lens)


def flops(cfg, true_lens: list[float]) -> float:
    tokens = sum(true_lens)
    head = 2 * len(true_lens) * cfg.d_model * cfg.vocab_size
    return (2 * tokens * token_params(cfg) + scan_flops(cfg, tokens)
            + attention_flops(cfg, true_lens) + head)


def least_seconds(cfg, peaks: dict, true_lens: list[float]) -> float:
    return flops(cfg, true_lens) / peaks["bf16_flops_per_s"]


def scan_least_seconds(cfg, peaks: dict, true_lens: list[float]) -> float:
    """The delta rule's operations alone: what the parts ``conv`` + ``delta``
    of the prefill program are held against."""
    return scan_flops(cfg, sum(true_lens)) / peaks["bf16_flops_per_s"]

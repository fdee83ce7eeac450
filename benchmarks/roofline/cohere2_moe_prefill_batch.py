"""What ``cohere2_moe_prefill_batch`` must do for prompts of given *true*
lengths (padding is the program's waste, not work): 2 operations a weight a
token for everything outside the routed experts (four attention matrices,
the router, the shared experts), 2 a weight for each row routed to a HELD
expert, the attention kernel's pairs (``roofline/gqa_prefill_attention.py``)
and the head at each prompt's last position only. The prefill program counts
no routed rows, so they are the mean under an even router: ``top_k`` choices
a token of which ``held / n_experts`` land here (1.0 a token a layer in the
cell; the decode steps' counter read 0.96-1.01 there, my chip runs, PR 31) —
an eighth of the matmul operations. Bound by operations."""
from __future__ import annotations

from benchmarks.roofline import gqa_prefill_attention as attention
from benchmarks.roofline.cohere2_moe_decode_multi import (attn_params,
                                                          expert_params)


def token_params(cfg) -> float:
    """Matmul weights a token meets in one layer."""
    lo, hi = cfg.held
    routed = cfg.n_experts_per_tok * (hi - lo) / cfg.n_experts
    return (attn_params(cfg) + cfg.d_model * cfg.n_experts
            + (cfg.n_shared_experts + routed) * expert_params(cfg))


def flops(cfg, true_lens: list[float]) -> float:
    matmul = 2 * sum(true_lens) * cfg.n_layers * token_params(cfg)
    head = 2 * len(true_lens) * cfg.d_model * cfg.vocab_size
    return matmul + attention.flops(cfg, true_lens) + head


def least_seconds(cfg, peaks: dict, true_lens: list[float]) -> float:
    return flops(cfg, true_lens) / peaks["bf16_flops_per_s"]

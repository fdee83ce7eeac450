"""What ``sparse_moe_prefill_batch`` must do for prompts of given *true*
lengths (padding is the program's waste, not work): 2 operations a weight a
token for everything outside the routed experts (four attention matrices, the
indexer's three, the router), 2 a weight for each row routed to a HELD expert
(the mean under an even router: ``top_k . held / n_experts`` a token a layer,
1.0 in the cell), every causal indexer pair at 2 operations a head a lane
(the selection is exact, so every pair is scored), the attention over the
picked keys (``roofline/sparse_prefill_attention.py``) and the head at each
prompt's last position only. Bound by operations."""
from __future__ import annotations

from benchmarks.roofline import sparse_prefill_attention as attention
from benchmarks.roofline.sparse_moe_decode_multi import (expert_params,
                                                         layer_params)


def token_params(cfg) -> float:
    """Matmul weights a token meets in one layer."""
    lo, hi = cfg.held
    routed = cfg.n_experts_per_tok * (hi - lo) / cfg.n_experts
    return layer_params(cfg) + routed * expert_params(cfg)


def indexer_flops(cfg, true_lens: list[float]) -> float:
    return sum(2 * cfg.indexer_heads * cfg.indexer_head_dim * cfg.n_layers
               * attention.causal_pairs(t) for t in true_lens)


def flops(cfg, true_lens: list[float]) -> float:
    matmul = 2 * sum(true_lens) * cfg.n_layers * token_params(cfg)
    head = 2 * len(true_lens) * cfg.d_model * cfg.vocab_size
    return (matmul + indexer_flops(cfg, true_lens)
            + attention.flops(cfg, true_lens) + head)


def least_seconds(cfg, peaks: dict, true_lens: list[float]) -> float:
    return flops(cfg, true_lens) / peaks["bf16_flops_per_s"]

"""What the attention kernel calls of a prefill (``gqa_picked_attention``)
must do for prompts of given *true* lengths: two matmuls of 2 operations over
every (query, PICKED key) pair for every query head — query t attends
``min(t + 1, topk)`` keys, whatever blocks a walk visits to find them. The
indexer's pairs are scored outside this kernel and counted with the program
(``roofline/sparse_moe_prefill_batch.py``). Bound by operations."""
from __future__ import annotations


def picked_pairs(t: float, topk: int) -> float:
    """sum over queries 0..t-1 of min(query + 1, topk)."""
    if t <= topk:
        return t * (t + 1) / 2
    return topk * (topk + 1) / 2 + (t - topk) * topk


def causal_pairs(t: float) -> float:
    return t * (t + 1) / 2


def flops(cfg, true_lens: list[float]) -> float:
    return sum(4 * cfg.n_heads * cfg.head_dim * cfg.n_layers
               * picked_pairs(t, cfg.topk) for t in true_lens)


def least_seconds(cfg, peaks: dict, true_lens: list[float]) -> float:
    return flops(cfg, true_lens) / peaks["bf16_flops_per_s"]

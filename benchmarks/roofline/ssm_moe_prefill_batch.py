"""What ``ssm_moe_prefill_batch`` must do for prompts of given *true* lengths
(padding is the program's waste, not work): 2 operations a weight a token for
everything outside the routed experts (a Mamba-2 block's projections and
taps, an attention block's four matrices, an expert block's router and shared
expert), 2 a weight for each row routed to a HELD expert (the mean under an
even router: ``top_k . held / n_experts`` a token an expert block, 0.75 in
the cell), the recurrence's own 5 operations a state element a token a
Mamba-2 block — the one-step form's count, a lower bound for any chunking —
the causal attention pairs of the attention blocks and the head at each
prompt's last position only. Bound by operations."""
from __future__ import annotations

from benchmarks.roofline.ssm_moe_decode_multi import (
    ATTENTION, EXPERTS, MAMBA, attn_params, blocks, expert_block_fixed,
    expert_params, mamba_params)


def token_params(cfg) -> float:
    """Matmul weights a token meets over the whole depth."""
    lo, hi = cfg.held
    routed = cfg.n_experts_per_tok * (hi - lo) / cfg.n_experts
    return (blocks(cfg, MAMBA) * mamba_params(cfg)
            + blocks(cfg, ATTENTION) * attn_params(cfg)
            + blocks(cfg, EXPERTS) * (expert_block_fixed(cfg)
                                      + routed * expert_params(cfg)))


def scan_flops(cfg, tokens: float) -> float:
    return (5 * tokens * blocks(cfg, MAMBA) * cfg.mamba_heads
            * cfg.mamba_head_dim * cfg.ssm_state)


def attention_flops(cfg, true_lens: list[float]) -> float:
    """Two matmuls of 2 operations over every causal (query, key) pair, for
    every query head of every attention block."""
    return sum(4 * cfg.n_heads * cfg.head_dim * blocks(cfg, ATTENTION)
               * t * (t + 1) / 2 for t in true_lens)


def flops(cfg, true_lens: list[float]) -> float:
    tokens = sum(true_lens)
    head = 2 * len(true_lens) * cfg.d_model * cfg.vocab_size
    return (2 * tokens * token_params(cfg) + scan_flops(cfg, tokens)
            + attention_flops(cfg, true_lens) + head)


def least_seconds(cfg, peaks: dict, true_lens: list[float]) -> float:
    return flops(cfg, true_lens) / peaks["bf16_flops_per_s"]

"""What one decode step of ``cohere2_moe_decode_multi`` must do, at the
least: read every weight outside the routed experts once (the batch shares
them: four attention matrices, the router and the shared experts a layer),
the held routed experts that the step's tokens TOUCH (as the program counted
them), the keys and values WITHIN EACH LAYER'S REACH once (a window layer's
reach is its window, not the slot's length), and the held rows of the
embedding for the head. At 48 slots the step is bound by bytes."""
from __future__ import annotations

from benchmarks.roofline.common import dtype_bytes


def attn_params(cfg) -> int:
    d, hd = cfg.d_model, cfg.head_dim
    return 2 * d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd


def expert_params(cfg) -> int:
    return 3 * cfg.d_model * cfg.d_expert


def fixed_params(cfg) -> int:
    """Every matmul weight a step reads whatever the routing."""
    layer = (attn_params(cfg) + cfg.d_model * cfg.n_experts
             + cfg.n_shared_experts * expert_params(cfg))
    return cfg.n_layers * layer + cfg.d_model * cfg.vocab_size


def kv_row_bytes(cfg) -> int:
    """A position's keys and values in one layer."""
    return 2 * cfg.n_kv_heads * cfg.head_dim * dtype_bytes(cfg)


def bytes_per_step(cfg, reach_tokens: float, experts_touched: float) -> float:
    """``reach_tokens``: positions within reach, summed over slots, the mean
    over layers; ``experts_touched``: mean distinct held experts a layer."""
    routed = cfg.n_layers * experts_touched * expert_params(cfg)
    return ((fixed_params(cfg) + routed) * dtype_bytes(cfg)
            + reach_tokens * cfg.n_layers * kv_row_bytes(cfg))


def flops_per_step(cfg, slots: int, reach_tokens: float,
                   assignments: float) -> float:
    """``assignments``: rows routed to held experts, a step a layer."""
    attn = 4 * reach_tokens * cfg.n_heads * cfg.head_dim * cfg.n_layers
    return (2 * slots * fixed_params(cfg)
            + 2 * cfg.n_layers * assignments * expert_params(cfg) + attn)


def least_seconds(cfg, peaks: dict, slots: int, reach_tokens: float,
                  experts_touched: float, assignments: float) -> float:
    return max(bytes_per_step(cfg, reach_tokens, experts_touched)
               / peaks["hbm_bytes_per_s"],
               flops_per_step(cfg, slots, reach_tokens, assignments)
               / peaks["bf16_flops_per_s"])

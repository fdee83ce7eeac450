"""What one decode step of ``ssm_moe_decode_multi`` must do, at the least,
whichever way it reaches a slot's state: read every weight outside the routed
experts once (the batch shares them: a Mamba-2 block's two projections and
its taps, an attention block's four matrices, an expert block's router and
shared expert), the held routed experts that the step's tokens TOUCH — two
matrices each — as the program counted them, **each updated state row once
and write it once** (the recurrence's state in float32 and the convolution's
saved inputs; ``rt_llm_ssm_state_updates_total`` counts the rows), the keys
and values of the live positions of the attention blocks, and the held
columns of the head. Every term is a lower bound for any exact
implementation. At 128 slots the step is bound by bytes, half of them state
rows."""
from __future__ import annotations

from benchmarks.roofline.common import dtype_bytes

# the pattern's characters, as ``ray_tpu/models/ssm_moe.py`` has them
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def mamba_params(cfg) -> int:
    """In-projection, the depthwise taps, out-projection."""
    return (cfg.d_model * (cfg.d_inner + cfg.conv_width + cfg.mamba_heads)
            + cfg.conv_kernel * cfg.conv_width + cfg.d_inner * cfg.d_model)


def attn_params(cfg) -> int:
    d, hd = cfg.d_model, cfg.head_dim
    return 2 * d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd


def expert_params(cfg) -> int:
    """One routed expert: up and down, no gate."""
    return 2 * cfg.d_model * cfg.d_expert


def expert_block_fixed(cfg) -> int:
    """An expert block outside its routed experts: router, shared expert."""
    return (cfg.d_model * cfg.n_experts
            + 2 * cfg.d_model * cfg.n_shared_experts * cfg.d_shared)


def blocks(cfg, kind: str) -> int:
    return len(cfg.blocks_of(kind))


def fixed_params(cfg) -> int:
    """Every weight a step reads whatever the routing."""
    return (blocks(cfg, MAMBA) * mamba_params(cfg)
            + blocks(cfg, ATTENTION) * attn_params(cfg)
            + blocks(cfg, EXPERTS) * expert_block_fixed(cfg)
            + cfg.d_model * cfg.vocab_size)


def state_row_bytes(cfg) -> int:
    """What a Mamba-2 block keeps of one slot: the state in float32 and the
    convolution's last K - 1 inputs."""
    return (4 * cfg.mamba_heads * cfg.mamba_head_dim * cfg.ssm_state
            + (cfg.conv_kernel - 1) * cfg.conv_width * dtype_bytes(cfg))


def state_bytes(cfg, updates: float) -> float:
    """``updates`` rows (slots x Mamba-2 blocks), each read and written."""
    return 2 * updates * state_row_bytes(cfg)


def kv_row_bytes(cfg) -> int:
    """A position's keys and values in one attention block."""
    return 2 * cfg.n_kv_heads * cfg.head_dim * dtype_bytes(cfg)


def bytes_per_step(cfg, updates: float, reach_tokens: float,
                   experts_touched: float) -> float:
    """``updates``: state rows updated; ``reach_tokens``: live positions,
    summed over slots (one attention block's); ``experts_touched``: mean
    distinct held experts an expert block."""
    routed = blocks(cfg, EXPERTS) * experts_touched * expert_params(cfg)
    return ((fixed_params(cfg) + routed) * dtype_bytes(cfg)
            + state_bytes(cfg, updates)
            + reach_tokens * blocks(cfg, ATTENTION) * kv_row_bytes(cfg))


def flops_per_step(cfg, slots: int, updates: float, reach_tokens: float,
                   assignments: float) -> float:
    """``assignments``: rows routed to held experts, a step an expert block.
    The recurrence is 5 operations a state element an update: the decay's
    product, the outer product's and its sum, the read-out's and its sum."""
    scan = 5 * updates * cfg.mamba_heads * cfg.mamba_head_dim * cfg.ssm_state
    attn = (4 * reach_tokens * cfg.n_heads * cfg.head_dim
            * blocks(cfg, ATTENTION))
    return (2 * slots * fixed_params(cfg)
            + 2 * blocks(cfg, EXPERTS) * assignments * expert_params(cfg)
            + scan + attn)


def least_seconds(cfg, peaks: dict, slots: int, updates: float,
                  reach_tokens: float, experts_touched: float,
                  assignments: float) -> float:
    return max(bytes_per_step(cfg, updates, reach_tokens, experts_touched)
               / peaks["hbm_bytes_per_s"],
               flops_per_step(cfg, slots, updates, reach_tokens, assignments)
               / peaks["bf16_flops_per_s"])

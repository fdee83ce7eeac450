"""What one decode step of ``mla_moe_decode_multi`` must do, at the least:
read every weight outside the routed experts once (the batch shares them),
the routed experts that the step's tokens TOUCH (as the program counted
them — not all that it holds), each live request's latent rows once, and the
head. At 32 slots the step is bound by bytes. The absorbed attention reads a
slot's window twice (scores, then the sum of latents) and the page gather
copies it first; the least is once, so this count is a floor."""
from __future__ import annotations

from benchmarks.roofline.common import dtype_bytes


def attn_params(cfg) -> int:
    d, H = cfg.d_model, cfg.n_heads
    return (d * H * cfg.qk_head_dim + d * cfg.latent_width
            + cfg.kv_lora_rank * H * (cfg.qk_nope_head_dim + cfg.v_head_dim)
            + H * cfg.v_head_dim * d)


def expert_params(cfg) -> int:
    return 3 * cfg.d_model * cfg.d_expert


def shared_params(cfg) -> int:
    """Per expert layer, outside the routed experts: the router and the
    shared experts."""
    return (cfg.d_model * cfg.n_experts
            + 3 * cfg.d_model * cfg.n_shared_experts * cfg.d_expert)


def fixed_params(cfg) -> int:
    """Every matmul weight a step reads whatever the routing."""
    dense = cfg.n_layers - cfg.n_moe_layers
    return (cfg.n_layers * attn_params(cfg) + dense * 3 * cfg.d_model * cfg.d_ff
            + cfg.n_moe_layers * shared_params(cfg)
            + cfg.d_model * cfg.vocab_size)


def bytes_per_step(cfg, live_kv_tokens: float, experts_touched: float) -> float:
    """``experts_touched``: mean distinct routed experts a step a layer."""
    b = dtype_bytes(cfg)
    routed = cfg.n_moe_layers * experts_touched * expert_params(cfg)
    latents = live_kv_tokens * cfg.n_layers * cfg.latent_width
    return (fixed_params(cfg) + routed + latents) * b


def flops_per_step(cfg, slots: int, live_kv_tokens: float) -> float:
    per_token = (fixed_params(cfg)
                 + cfg.n_moe_layers * cfg.n_experts_per_tok * expert_params(cfg))
    # absorbed attention: scores over r + rope, the sum of latents over r
    attn = 2 * live_kv_tokens * cfg.n_heads * (
        cfg.latent_width + cfg.kv_lora_rank) * cfg.n_layers
    return 2 * slots * per_token + attn


def least_seconds(cfg, peaks: dict, slots: int, live_kv_tokens: float,
                  experts_touched: float) -> float:
    return max(bytes_per_step(cfg, live_kv_tokens, experts_touched)
               / peaks["hbm_bytes_per_s"],
               flops_per_step(cfg, slots, live_kv_tokens)
               / peaks["bf16_flops_per_s"])

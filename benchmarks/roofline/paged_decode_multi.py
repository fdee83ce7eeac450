"""What one decode step of ``paged_decode_multi`` must do: read every weight
once (the batch shares them) and the live keys and values of every running
request; 2 operations per weight per slot. At 16 slots the step is bound by
bytes, so its roofline is bytes over the chip's memory bandwidth."""
from __future__ import annotations

from benchmarks.roofline.common import dtype_bytes, head_params, layer_matmul_params


def bytes_per_step(cfg, live_kv_tokens: float) -> float:
    b = dtype_bytes(cfg)
    weights = (cfg.n_layers * layer_matmul_params(cfg) + head_params(cfg)) * b
    kv = live_kv_tokens * cfg.n_layers * 2 * cfg.n_kv_heads * cfg.head_dim * b
    return weights + kv


def flops_per_step(cfg, slots: int, live_kv_tokens: float) -> float:
    matmul = 2 * slots * (cfg.n_layers * layer_matmul_params(cfg) + head_params(cfg))
    attn = 4 * live_kv_tokens * cfg.n_heads * cfg.head_dim * cfg.n_layers
    return matmul + attn


def least_seconds(cfg, peaks: dict, slots: int, live_kv_tokens: float) -> float:
    return max(bytes_per_step(cfg, live_kv_tokens) / peaks["hbm_bytes_per_s"],
               flops_per_step(cfg, slots, live_kv_tokens) / peaks["bf16_flops_per_s"])

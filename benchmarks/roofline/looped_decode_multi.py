"""What one decode step of ``looped_decode_multi`` must do: read every
layer's weights ONCE A PASS — no chip holds a stack of 4.93 GB between two
passes, and a pass serves at most the slots there are, so ``n_passes`` reads
a step are a true lower bound for any exact implementation —, the norms, the
gate and the head once, the slots' rows of the embedding, and the live keys
and values of every plane (a pass a layer: ``n_passes . n_layers`` planes);
2 operations a weight a slot a pass. At 24 slots the step is bound by bytes,
so its roofline is bytes over the chip's memory bandwidth."""
from __future__ import annotations

from benchmarks.roofline.common import dtype_bytes, head_params, layer_matmul_params


def kv_position_bytes(cfg) -> int:
    """Keys and values of one position in ONE plane."""
    return 2 * cfg.n_kv_heads * cfg.head_dim * dtype_bytes(cfg)


def layer_bytes(cfg) -> int:
    """One layer's matrices and its four norms."""
    return (layer_matmul_params(cfg) + 4 * cfg.d_model) * dtype_bytes(cfg)


def bytes_per_step(cfg, live_kv_tokens: float) -> float:
    b = dtype_bytes(cfg)
    weights = cfg.n_passes * cfg.n_layers * layer_bytes(cfg)
    once = (head_params(cfg) + cfg.d_model) * b + (
        cfg.d_model + 1) * 4   # the head and the final norm; the gate
    kv = live_kv_tokens * cfg.planes * kv_position_bytes(cfg)
    return weights + once + kv


def flops_per_step(cfg, slots: int, live_kv_tokens: float) -> float:
    matmul = 2 * slots * (cfg.planes * layer_matmul_params(cfg)
                          + head_params(cfg))
    attn = 4 * live_kv_tokens * cfg.n_heads * cfg.head_dim * cfg.planes
    return matmul + attn


def least_seconds(cfg, peaks: dict, slots: int, live_kv_tokens: float) -> float:
    return max(bytes_per_step(cfg, live_kv_tokens) / peaks["hbm_bytes_per_s"],
               flops_per_step(cfg, slots, live_kv_tokens) / peaks["bf16_flops_per_s"])

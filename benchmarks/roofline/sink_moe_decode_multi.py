"""What one decode step of ``sink_moe_decode_multi`` must do, at the least:
read every weight outside the routed experts once (the batch shares them: a
layer's four attention matrices at its kind's KV heads, the router of an
expert layer, layer 0's dense SwiGLU, the head), the held routed experts that
the step's tokens TOUCH (as the program counted them) at three matrices each,
the keys and values WITHIN EACH LAYER'S REACH once at the widths the model
has — 192 + 128 numbers a KV head a position, 4 heads in a full layer and 8
in a window layer, NOT the 256 lanes a cached key lies in (an exact program
could pack them) — and the two page tables once. At 64 slots the step is
bound by bytes."""
from __future__ import annotations

from benchmarks.roofline.common import dtype_bytes


def attn_params(cfg, window: bool) -> int:
    d, H, KV = cfg.d_model, cfg.n_heads, cfg.kv_heads(window)
    return (d * H * cfg.head_dim + d * KV * (cfg.head_dim + cfg.v_head_dim)
            + H * cfg.v_head_dim * d)


def expert_params(cfg) -> int:
    return 3 * cfg.d_model * cfg.d_expert


def layer_fixed_params(cfg, i: int) -> int:
    """Layer ``i``'s matmul weights outside its routed experts."""
    ffn = (cfg.d_model * cfg.n_experts if cfg.is_moe(i)
           else 3 * cfg.d_model * cfg.d_ff)
    return attn_params(cfg, cfg.is_window(i)) + ffn


def fixed_params(cfg) -> int:
    """Every matmul weight a step reads whatever the routing."""
    return (sum(layer_fixed_params(cfg, i) for i in range(cfg.n_layers))
            + cfg.d_model * cfg.vocab_size)


def kv_row_bytes(cfg, window: bool) -> int:
    """A position's keys and values in one layer of a kind, as wide as the
    model has them."""
    return (cfg.kv_heads(window) * (cfg.head_dim + cfg.v_head_dim)
            * dtype_bytes(cfg))


def kv_bytes(cfg, reach_full: float, reach_window: float) -> float:
    """``reach_*``: positions within one layer's reach, summed over slots."""
    return (reach_full * len(cfg.layers_of(False)) * kv_row_bytes(cfg, False)
            + reach_window * len(cfg.layers_of(True)) * kv_row_bytes(cfg, True))


def bytes_per_step(cfg, reach_full: float, reach_window: float,
                   experts_touched: float, table_entries: int = 0) -> float:
    """``experts_touched``: distinct held experts that got a row, summed over
    the expert layers; ``table_entries``: int32 entries of both tables."""
    return ((fixed_params(cfg) + experts_touched * expert_params(cfg))
            * dtype_bytes(cfg) + kv_bytes(cfg, reach_full, reach_window)
            + 4 * table_entries)


def flops_per_step(cfg, slots: int, reach_full: float, reach_window: float,
                   assignments: float) -> float:
    """``assignments``: rows routed to held experts a step, summed over the
    expert layers."""
    pair = 2 * cfg.n_heads * (cfg.head_dim + cfg.v_head_dim)
    attn = pair * (reach_full * len(cfg.layers_of(False))
                   + reach_window * len(cfg.layers_of(True)))
    return (2 * slots * fixed_params(cfg)
            + 2 * assignments * expert_params(cfg) + attn)


def least_seconds(cfg, peaks: dict, slots: int, reach_full: float,
                  reach_window: float, experts_touched: float,
                  assignments: float, table_entries: int = 0) -> float:
    return max(bytes_per_step(cfg, reach_full, reach_window, experts_touched,
                              table_entries) / peaks["hbm_bytes_per_s"],
               flops_per_step(cfg, slots, reach_full, reach_window,
                              assignments) / peaks["bf16_flops_per_s"])

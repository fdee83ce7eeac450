"""What ``eva_decode_multi`` must move for ONE decode step, at the least, for
any exact implementation: every layer's matmul weights and head 0's columns
once (the other heads are no part of the served path), each attended row of
either kind — an exact row of the slot's own window or a pooled pair — once a
layer, K and V (``row_bytes``: 32 heads x 128 x 2 pools x 2 B = 16,384 B at
published widths), and each written row and pair once. The rows are the
program's own counts (``rt_llm_decode_kv_tokens_live_total{kind}``, one
layer's worth; ``rt_llm_eva_pairs_written_total``, summed over layers: a live
slot fills a chunk every ``chunk_size`` steps, so ``pairs x chunk_size`` rows
were written beside them). Bound by bytes: a step's matmuls are 24 rows."""
from __future__ import annotations

from benchmarks.roofline.common import dtype_bytes


def layer_params(cfg) -> int:
    """wq, wk, wv, wo and the three SwiGLU matrices."""
    width = cfg.n_heads * cfg.head_dim
    return 4 * cfg.d_model * width + 3 * cfg.d_model * cfg.d_ff


def row_bytes(cfg) -> int:
    """One row of one layer, K and V (or K^ and V^)."""
    return 2 * cfg.n_heads * cfg.head_dim * dtype_bytes(cfg)


def attended_bytes(cfg, rows_window: float, rows_summary: float) -> float:
    return cfg.n_layers * (rows_window + rows_summary) * row_bytes(cfg)


def bytes_per_step(cfg, rows_window: float, rows_summary: float,
                   pairs: float) -> float:
    """``rows_*``: rows one layer attends, summed over slots; ``pairs``:
    pairs written, summed over slots and layers."""
    weights = (cfg.n_layers * layer_params(cfg)
               + cfg.d_model * cfg.vocab_size) * dtype_bytes(cfg)
    written = pairs * (1 + cfg.chunk_size) * row_bytes(cfg)
    return weights + attended_bytes(cfg, rows_window, rows_summary) + written


def least_seconds(cfg, peaks: dict, rows_window: float, rows_summary: float,
                  pairs: float) -> float:
    return bytes_per_step(cfg, rows_window, rows_summary, pairs
                          ) / peaks["hbm_bytes_per_s"]


def attention_bytes(cfg, rows_window: float, rows_summary: float,
                    pairs: float) -> float:
    """The two walks' own: the attended rows, and q in and o out for every
    live slot (``pairs x chunk_size / n_layers`` of them a step)."""
    live = pairs * cfg.chunk_size / cfg.n_layers
    q_and_o = 2 * live * cfg.n_layers * cfg.n_heads * cfg.head_dim * dtype_bytes(cfg)
    return attended_bytes(cfg, rows_window, rows_summary) + q_and_o

"""What the scoring and attention kernels of ONE decode step must do over all
layers (``paged_index_scores`` and ``paged_decode_attention``'s selected
form): read the indexer's key of every position scored, the keys and values
of the rows attended — the picked rows alone, which is what a perfect gather
would read — the query rows of both, and write the output rows; 2 operations
a (indexer head, lane, position), two matmuls of 2 over every (query head,
attended row) pair. Bound by bytes at these lengths."""
from __future__ import annotations

from benchmarks.roofline.common import dtype_bytes
from benchmarks.roofline.sparse_moe_decode_multi import (index_row_bytes,
                                                         kv_row_bytes)


def bytes_per_step(cfg, slots: int, scored: float, attended: float) -> float:
    """``scored``, ``attended``: summed over slots and layers."""
    rows = cfg.n_layers * slots * dtype_bytes(cfg) * (
        2 * cfg.n_heads * cfg.head_dim
        + cfg.indexer_heads * cfg.indexer_head_dim)
    return (scored * index_row_bytes(cfg) + attended * kv_row_bytes(cfg)
            + rows)


def flops_per_step(cfg, scored: float, attended: float) -> float:
    return (2 * scored * cfg.indexer_heads * cfg.indexer_head_dim
            + 4 * attended * cfg.n_heads * cfg.head_dim)


def least_seconds(cfg, peaks: dict, slots: int, scored: float,
                  attended: float) -> float:
    return max(bytes_per_step(cfg, slots, scored, attended)
               / peaks["hbm_bytes_per_s"],
               flops_per_step(cfg, scored, attended) / peaks["bf16_flops_per_s"])

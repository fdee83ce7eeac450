"""What the paged decode kernel's calls of ONE decode step must do for the
layers of one kind of page (``window``: ``_paged_window_attention``, from the
first page within reach; ``full``: ``_paged_decode_attention``): read the
keys and values within reach once, the query rows, and write the output
rows; two matmuls of 2 operations over every (query head, position) pair. At
these lengths it is bound by bytes."""
from __future__ import annotations

from benchmarks.roofline.cohere2_moe_decode_multi import kv_row_bytes
from benchmarks.roofline.common import dtype_bytes


def layers(cfg, kind: str) -> int:
    return len(cfg.layers_of(kind == "window"))


def bytes_per_call(cfg, slots: int, reach_tokens: float) -> float:
    """``reach_tokens``: positions within one layer's reach, summed over
    slots; q in and o out are ``slots`` rows of every query head."""
    rows = 2 * slots * cfg.n_heads * cfg.head_dim * dtype_bytes(cfg)
    return reach_tokens * kv_row_bytes(cfg) + rows


def flops_per_call(cfg, reach_tokens: float) -> float:
    return 4 * reach_tokens * cfg.n_heads * cfg.head_dim


def least_seconds(cfg, peaks: dict, slots: int, kind: str,
                  reach_tokens: float) -> float:
    return layers(cfg, kind) * max(
        bytes_per_call(cfg, slots, reach_tokens) / peaks["hbm_bytes_per_s"],
        flops_per_call(cfg, reach_tokens) / peaks["bf16_flops_per_s"])

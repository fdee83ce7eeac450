"""What the paged decode kernel's calls of ONE decode step must do for this
family's K/V kind (``_paged_decode_attention``, every layer): read the keys
and values within reach once — 2 key heads of 128 lanes, 1,024 B a position
a layer: the compressed cache —, the query rows, and write the output rows;
two matmuls of 2 operations over every (query head, position) pair. In
``roofline/paged_kind_attention.py``'s manner, with this family's own layer
count (every layer holds the kind). At these lengths it is bound by bytes."""
from __future__ import annotations

from benchmarks.roofline.cca_moe_decode_multi import kv_row_bytes
from benchmarks.roofline.common import dtype_bytes


def bytes_per_call(cfg, slots: int, reach_tokens: float) -> float:
    """``reach_tokens``: positions within one layer's reach, summed over
    slots; q in and o out are ``slots`` rows of every query head."""
    rows = 2 * slots * cfg.n_heads * cfg.head_dim * dtype_bytes(cfg)
    return reach_tokens * kv_row_bytes(cfg) + rows


def flops_per_call(cfg, reach_tokens: float) -> float:
    return 4 * reach_tokens * cfg.n_heads * cfg.head_dim


def least_seconds(cfg, peaks: dict, slots: int, kind: str,
                  reach_tokens: float) -> float:
    if kind != "kv":
        raise ValueError(f"the attended kind is 'kv', not {kind!r}")
    return cfg.n_layers * max(
        bytes_per_call(cfg, slots, reach_tokens) / peaks["hbm_bytes_per_s"],
        flops_per_call(cfg, reach_tokens) / peaks["bf16_flops_per_s"])

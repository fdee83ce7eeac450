"""What the paged decode kernel's calls of ONE decode step must do for the
looped family (``_paged_decode_attention``, a call a pass a layer): read the
keys and values within reach once in each of the ``n_passes . n_layers``
planes — 16 KV heads of 128 lanes, 8,192 B a position a plane —, the query
rows, and write the output rows; two matmuls of 2 operations over every
(query head, position) pair. In ``roofline/cca_decode_attention.py``'s manner
with a plane where that has a layer. At these lengths it is bound by bytes."""
from __future__ import annotations

from benchmarks.roofline.common import dtype_bytes
from benchmarks.roofline.looped_decode_multi import kv_position_bytes


def bytes_per_call(cfg, slots: int, reach_tokens: float) -> float:
    """``reach_tokens``: positions within one plane's reach, summed over
    slots; q in and o out are ``slots`` rows of every query head."""
    rows = 2 * slots * cfg.n_heads * cfg.head_dim * dtype_bytes(cfg)
    return reach_tokens * kv_position_bytes(cfg) + rows


def flops_per_call(cfg, reach_tokens: float) -> float:
    return 4 * reach_tokens * cfg.n_heads * cfg.head_dim


def least_seconds(cfg, peaks: dict, slots: int, kind: str,
                  reach_tokens: float) -> float:
    if kind:  # one kind of page: the engine's untagged count is its own
        raise ValueError(f"the family has one kind of page, not {kind!r}")
    return cfg.planes * max(
        bytes_per_call(cfg, slots, reach_tokens) / peaks["hbm_bytes_per_s"],
        flops_per_call(cfg, reach_tokens) / peaks["bf16_flops_per_s"])

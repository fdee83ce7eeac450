"""``roofline/paged_kind_attention.py`` for a cache whose kinds differ in
their KV heads and whose keys are wider than its values: what the paged
decode kernel's calls of ONE decode step must do for the layers of one kind
of page (``window``: the ring's walk given out as a part,
``paged_window_part``; ``full``: ``_paged_decode_attention``): read the keys
and values within reach once at the widths the MODEL has (192 + 128 numbers
a KV head, 640 B: not the 256 lanes a cached key lies in), the query rows,
and write the output rows; a score and a weighted value of 2 operations a
lane over every (query head, position) pair. Bound by bytes."""
from __future__ import annotations

from benchmarks.roofline.common import dtype_bytes
from benchmarks.roofline.sink_moe_decode_multi import kv_row_bytes


def layers(cfg, kind: str) -> int:
    return len(cfg.layers_of(kind == "window"))


def bytes_per_call(cfg, slots: int, kind: str, reach_tokens: float) -> float:
    """``reach_tokens``: positions within one layer's reach, summed over
    slots; q in and o out are ``slots`` rows of every query head."""
    rows = (slots * cfg.n_heads * (cfg.head_dim + cfg.v_head_dim)
            * dtype_bytes(cfg))
    return reach_tokens * kv_row_bytes(cfg, kind == "window") + rows


def flops_per_call(cfg, reach_tokens: float) -> float:
    return 2 * reach_tokens * cfg.n_heads * (cfg.head_dim + cfg.v_head_dim)


def least_seconds(cfg, peaks: dict, slots: int, kind: str,
                  reach_tokens: float) -> float:
    return layers(cfg, kind) * max(
        bytes_per_call(cfg, slots, kind, reach_tokens)
        / peaks["hbm_bytes_per_s"],
        flops_per_call(cfg, reach_tokens) / peaks["bf16_flops_per_s"])

"""What one decode step of ``sparse_moe_decode_multi`` must do, at the least,
whichever of a walk and a gather it is: read every weight outside the routed
experts once (the batch shares them: four attention matrices, the indexer's
three and the router a layer), the held routed experts that the step's tokens
TOUCH (as the program counted them), the indexer's key of every position it
SCORES (all of a slot's, in every layer: the selection is exact), the keys
and values of the rows it ATTENDS only (min(length, topk) a slot a layer: a
perfect gather reads no other), and the held columns of the head. Every term
is a lower bound for any exact implementation. At 32 slots of 9k positions
the step is bound by bytes."""
from __future__ import annotations

from benchmarks.roofline.common import dtype_bytes


def attn_params(cfg) -> int:
    d, hd = cfg.d_model, cfg.head_dim
    return 2 * d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd


def indexer_params(cfg) -> int:
    return cfg.d_model * (cfg.indexer_heads * cfg.indexer_head_dim
                          + cfg.indexer_head_dim + cfg.indexer_heads)


def expert_params(cfg) -> int:
    return 3 * cfg.d_model * cfg.d_expert


def layer_params(cfg) -> int:
    """A layer's matmul weights outside its routed experts."""
    return attn_params(cfg) + indexer_params(cfg) + cfg.d_model * cfg.n_experts


def fixed_params(cfg) -> int:
    """Every matmul weight a step reads whatever the routing."""
    return cfg.n_layers * layer_params(cfg) + cfg.d_model * cfg.vocab_size


def kv_row_bytes(cfg) -> int:
    """A position's keys and values in one layer."""
    return 2 * cfg.n_kv_heads * cfg.head_dim * dtype_bytes(cfg)


def index_row_bytes(cfg) -> int:
    """A position's indexer key in one layer."""
    return cfg.indexer_head_dim * dtype_bytes(cfg)


def bytes_per_step(cfg, scored: float, attended: float,
                   experts_touched: float) -> float:
    """``scored``, ``attended``: positions scored and rows attended, summed
    over slots AND layers; ``experts_touched``: mean distinct held experts a
    layer."""
    routed = cfg.n_layers * experts_touched * expert_params(cfg)
    return ((fixed_params(cfg) + routed) * dtype_bytes(cfg)
            + scored * index_row_bytes(cfg) + attended * kv_row_bytes(cfg))


def flops_per_step(cfg, slots: int, scored: float, attended: float,
                   assignments: float) -> float:
    """``assignments``: rows routed to held experts, a step a layer."""
    pairs = (2 * scored * cfg.indexer_heads * cfg.indexer_head_dim
             + 4 * attended * cfg.n_heads * cfg.head_dim)
    return (2 * slots * fixed_params(cfg)
            + 2 * cfg.n_layers * assignments * expert_params(cfg) + pairs)


def least_seconds(cfg, peaks: dict, slots: int, scored: float,
                  attended: float, experts_touched: float,
                  assignments: float) -> float:
    return max(bytes_per_step(cfg, scored, attended, experts_touched)
               / peaks["hbm_bytes_per_s"],
               flops_per_step(cfg, slots, scored, attended, assignments)
               / peaks["bf16_flops_per_s"])

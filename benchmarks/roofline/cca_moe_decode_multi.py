"""What one decode step of ``cca_moe_decode_multi`` must do, at the least,
whichever way it reaches a slot's cache: read every weight outside the routed
experts once (the batch shares them: a layer's one input projection, its two
convolutions' taps, its output projection and its router), the held routed
experts that the step's tokens TOUCH — three matrices each — as the program
counted them, the keys and values of the live positions of every layer
(1,024 B a position a layer at the published widths), **each updated row
once and write it once** (5,376 B: ``rt_llm_cca_row_updates_total`` counts
the rows), and the tied table once, for the head (the step's embedding rows
are of the same array and are not counted again). Every term is a lower
bound for any exact implementation, so the share cannot pass 100 % whichever
form runs. At 80 slots the step is bound by bytes, seven tenths of them the
touched experts'."""
from __future__ import annotations

from benchmarks.roofline.common import dtype_bytes


def mixer_params(cfg) -> int:
    """The input projection [q~ | k~ | v1 | v2], the depthwise taps, the
    grouped taps, the output projection."""
    heads, hd = cfg.n_heads + cfg.n_kv_heads, cfg.head_dim
    return (cfg.d_model * cfg.in_width + 2 * cfg.conv_width
            + 2 * heads * hd * hd + cfg.n_heads * hd * cfg.d_model)


def router_params(cfg) -> int:
    R = cfg.router_hidden
    return cfg.d_model * R + 2 * R * R + R * cfg.n_experts


def expert_params(cfg) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg.d_model * cfg.d_expert


def fixed_bytes(cfg) -> float:
    """Every weight a step reads whatever the routing: the mixers and the
    table in the model's type, the routers in float32 but for ``W_down``."""
    R = cfg.router_hidden
    router = (cfg.d_model * R * dtype_bytes(cfg)
              + 4 * (2 * R * R + R * cfg.n_experts))
    return (cfg.n_layers * (mixer_params(cfg) * dtype_bytes(cfg) + router)
            + cfg.d_model * cfg.vocab_size * dtype_bytes(cfg))


def kv_row_bytes(cfg) -> int:
    """A position's key and value in one layer."""
    return 2 * cfg.n_kv_heads * cfg.head_dim * dtype_bytes(cfg)


def row_bytes(cfg) -> int:
    """What a layer keeps of one slot beside its pages: u, c0 and the
    value's late half of the position before."""
    return (2 * cfg.conv_width + cfg.v_half) * dtype_bytes(cfg)


def bytes_per_step(cfg, updates: float, reach_tokens: float,
                   experts_touched: float) -> float:
    """``updates``: rows updated (live slots x layers); ``reach_tokens``:
    live positions, summed over slots (one layer's); ``experts_touched``:
    mean distinct held experts a layer."""
    routed = cfg.n_layers * experts_touched * expert_params(cfg)
    return (fixed_bytes(cfg) + routed * dtype_bytes(cfg)
            + 2 * updates * row_bytes(cfg)
            + reach_tokens * cfg.n_layers * kv_row_bytes(cfg))


def flops_per_step(cfg, slots: int, reach_tokens: float,
                   assignments: float) -> float:
    """``assignments``: rows routed to held experts, a step a layer. The
    attention scores a position over a head's lanes and sums its value's,
    for every query head."""
    attn = 4 * reach_tokens * cfg.n_heads * cfg.head_dim * cfg.n_layers
    fixed = cfg.n_layers * (mixer_params(cfg) + router_params(cfg)) + (
        cfg.d_model * cfg.vocab_size)
    return (2 * slots * fixed
            + 2 * cfg.n_layers * assignments * expert_params(cfg) + attn)


def least_seconds(cfg, peaks: dict, slots: int, updates: float,
                  reach_tokens: float, experts_touched: float,
                  assignments: float) -> float:
    return max(bytes_per_step(cfg, updates, reach_tokens, experts_touched)
               / peaks["hbm_bytes_per_s"],
               flops_per_step(cfg, slots, reach_tokens, assignments)
               / peaks["bf16_flops_per_s"])

"""What one decode step of ``kda_moe_decode_multi`` must do, at the least,
whichever way it reaches a slot's state: read every weight outside the routed
experts once (the batch shares them: a KDA layer's one input projection, its
taps and its output projection, an MLA layer's five matrices, the dense
layers' SwiGLU, an expert layer's router and shared expert), the held routed
experts that the step's tokens TOUCH — three matrices each — as the program
counted them, **each updated state row once and write it once** (the delta
rule's state in float32, 2,097,152 B at the published widths, and the
convolution's saved inputs, 73,728 B; ``rt_llm_delta_state_updates_total``
counts the rows), the latent rows of the live positions of the MLA layers
(1,152 B a position a layer), and the held columns of the head. Every term is
a lower bound for any exact implementation, so the share cannot pass 100 %
whichever form runs. At 96 slots the step is bound by bytes, a third of them
state rows."""
from __future__ import annotations

from benchmarks.roofline.common import dtype_bytes

KDA, MLA = "kda", "mla"  # as ``ray_tpu/models/kda_moe.py`` has them


def kda_params(cfg) -> int:
    """The input projection [q | k | v | a | beta | gate], the depthwise
    taps, the output projection."""
    return (cfg.d_model * (cfg.conv_width + cfg.d_inner + 2 * cfg.n_heads)
            + cfg.conv_kernel * cfg.conv_width + cfg.d_inner * cfg.d_model)


def mla_params(cfg) -> int:
    H, D = cfg.n_heads, cfg.d_model
    return (D * H * cfg.qk_head_dim + D * cfg.latent_width
            + cfg.kv_lora_rank * H * (cfg.qk_nope_head_dim + cfg.v_head_dim)
            + D * H + H * cfg.v_head_dim * D)


def expert_params(cfg) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg.d_model * cfg.d_expert


def expert_layer_fixed(cfg) -> int:
    """An expert layer outside its routed experts: router, shared expert."""
    return cfg.d_model * cfg.n_experts + 3 * cfg.d_model * cfg.d_shared


def dense_params(cfg) -> int:
    """The leading dense layers' SwiGLUs."""
    return min(cfg.first_dense_layers, cfg.n_layers) * 3 * cfg.d_model * cfg.d_ff


def layers(cfg, kind: str) -> int:
    return len(cfg.layers_of(kind))


def fixed_params(cfg) -> int:
    """Every weight a step reads whatever the routing."""
    return (layers(cfg, KDA) * kda_params(cfg)
            + layers(cfg, MLA) * mla_params(cfg)
            + dense_params(cfg)
            + cfg.n_moe_layers * expert_layer_fixed(cfg)
            + cfg.d_model * cfg.vocab_size)


def state_row_bytes(cfg) -> int:
    """What a KDA layer keeps of one slot: the state in float32 and the
    convolution's last K - 1 inputs."""
    return (4 * cfg.n_heads * cfg.head_dim * cfg.head_dim
            + (cfg.conv_kernel - 1) * cfg.conv_width * dtype_bytes(cfg))


def state_bytes(cfg, updates: float) -> float:
    """``updates`` rows (slots x KDA layers), each read and written."""
    return 2 * updates * state_row_bytes(cfg)


def latent_row_bytes(cfg) -> int:
    """A position's [c, k_rope] in one MLA layer."""
    return cfg.latent_width * dtype_bytes(cfg)


def bytes_per_step(cfg, updates: float, reach_tokens: float,
                   experts_touched: float) -> float:
    """``updates``: state rows updated; ``reach_tokens``: live positions,
    summed over slots (one MLA layer's); ``experts_touched``: mean distinct
    held experts an expert layer."""
    routed = cfg.n_moe_layers * experts_touched * expert_params(cfg)
    return ((fixed_params(cfg) + routed) * dtype_bytes(cfg)
            + state_bytes(cfg, updates)
            + reach_tokens * layers(cfg, MLA) * latent_row_bytes(cfg))


def delta_flops(cfg, updates: float) -> float:
    """The delta rule's one-step count: 7 operations a state element an
    update — the decay's product, ``S'^T k`` (product and sum), the rank-1
    update (product and sum) and the read-out (product and sum)."""
    return 7 * updates * cfg.n_heads * cfg.head_dim * cfg.head_dim


def flops_per_step(cfg, slots: int, updates: float, reach_tokens: float,
                   assignments: float) -> float:
    """``assignments``: rows routed to held experts, a step an expert layer.
    Absorbed attention scores a position over the latent's whole width and
    sums its ``kv_lora_rank`` lanes, for every head."""
    attn = (2 * reach_tokens * cfg.n_heads
            * (cfg.latent_width + cfg.kv_lora_rank) * layers(cfg, MLA))
    return (2 * slots * fixed_params(cfg)
            + 2 * cfg.n_moe_layers * assignments * expert_params(cfg)
            + delta_flops(cfg, updates) + attn)


def least_seconds(cfg, peaks: dict, slots: int, updates: float,
                  reach_tokens: float, experts_touched: float,
                  assignments: float) -> float:
    return max(bytes_per_step(cfg, updates, reach_tokens, experts_touched)
               / peaks["hbm_bytes_per_s"],
               flops_per_step(cfg, slots, updates, reach_tokens, assignments)
               / peaks["bf16_flops_per_s"])

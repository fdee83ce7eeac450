"""What ``sink_moe_prefill_batch`` must do for prompts of given *true*
lengths (padding is the program's waste, not work): 2 operations a weight a
token for everything outside the routed experts (a layer's four attention
matrices at its kind's KV heads, an expert layer's router, layer 0's dense
SwiGLU), 2 a weight for each row routed to a HELD expert, the attention's
pairs (``roofline/sink_prefill_attention.py``) and the head at each prompt's
last position only. The prefill program counts no routed rows, so they are
the mean under an even router: ``top_k`` choices a token of which ``held /
n_experts`` land here (8 x 16 / 256 = half an expert a token a layer in the
cell). Bound by operations."""
from __future__ import annotations

from benchmarks.roofline import sink_prefill_attention as attention
from benchmarks.roofline.sink_moe_decode_multi import (expert_params,
                                                       layer_fixed_params)


def token_params(cfg) -> float:
    """Matmul weights a token meets over all layers."""
    lo, hi = cfg.held
    routed = cfg.n_experts_per_tok * (hi - lo) / cfg.n_experts
    moe_layers = sum(cfg.is_moe(i) for i in range(cfg.n_layers))
    return (sum(layer_fixed_params(cfg, i) for i in range(cfg.n_layers))
            + moe_layers * routed * expert_params(cfg))


def flops(cfg, true_lens: list[float]) -> float:
    matmul = 2 * sum(true_lens) * token_params(cfg)
    head = 2 * len(true_lens) * cfg.d_model * cfg.vocab_size
    return matmul + attention.flops(cfg, true_lens) + head


def least_seconds(cfg, peaks: dict, true_lens: list[float]) -> float:
    return flops(cfg, true_lens) / peaks["bf16_flops_per_s"]

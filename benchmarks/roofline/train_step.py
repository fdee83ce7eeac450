"""Operations one training token requires, forward and backward, with no
recomputation counted: 6 per matmul weight (2 forward, 4 backward), the head
included and the embedding look-up not; causal attention 2*T*d forward and
twice that backward, per layer."""
from __future__ import annotations

from benchmarks.roofline.common import head_params, layer_matmul_params


def flops_per_token(cfg, seq_len: int) -> float:
    matmul = 6 * (cfg.n_layers * layer_matmul_params(cfg) + head_params(cfg))
    attn = 6 * seq_len * cfg.n_heads * cfg.head_dim * cfg.n_layers
    return matmul + attn

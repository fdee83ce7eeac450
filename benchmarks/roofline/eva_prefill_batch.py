"""What ``eva_prefill_batch`` must do for prompts of given *true* lengths
(padding is the program's waste, not work): 2 operations a weight a token for
the seven matrices of every layer, the attention's pairs
(``roofline/eva_prefill_attention.py``), the pooling's 4 operations a lane a
position a layer (the logit and the two weighted sums) and head 0 at each
prompt's last position only. Bound by operations."""
from __future__ import annotations

from benchmarks.roofline import eva_prefill_attention as attention
from benchmarks.roofline.eva_decode_multi import layer_params


def flops(cfg, true_lens: list[float]) -> float:
    tokens = sum(true_lens)
    matmul = 2 * tokens * cfg.n_layers * layer_params(cfg)
    pooling = 4 * tokens * cfg.n_layers * cfg.n_heads * cfg.head_dim
    head = 2 * len(true_lens) * cfg.d_model * cfg.vocab_size
    return matmul + attention.flops(cfg, true_lens) + pooling + head


def least_seconds(cfg, peaks: dict, true_lens: list[float]) -> float:
    return flops(cfg, true_lens) / peaks["bf16_flops_per_s"]

"""What ``looped_prefill_batch`` must do for prompts of given *true* lengths
(padding is the program's waste, not work): 2 operations a weight a token A
PASS, the causal pairs at 2 operations a lane of keys and of values for every
query head in each of the ``n_passes . n_layers`` planes, and the head at each
prompt's last position only. Bound by operations."""
from __future__ import annotations

from benchmarks.roofline.common import head_params, layer_matmul_params


def attention_flops(cfg, true_lens: list[float]) -> float:
    """Two matmuls of 2 operations over every causal (query, key) pair, the
    query's own included: 4 x 16 x 128 a pair a plane at the published
    widths."""
    return sum(4 * cfg.n_heads * cfg.head_dim * cfg.planes * t * (t + 1) / 2
               for t in true_lens)


def flops(cfg, true_lens: list[float]) -> float:
    matmul = 2 * sum(true_lens) * cfg.planes * layer_matmul_params(cfg)
    return (matmul + attention_flops(cfg, true_lens)
            + 2 * len(true_lens) * head_params(cfg))


def least_seconds(cfg, peaks: dict, true_lens: list[float]) -> float:
    return flops(cfg, true_lens) / peaks["bf16_flops_per_s"]

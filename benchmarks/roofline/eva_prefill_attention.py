"""What the ``eva_prefill_attention`` kernel calls of a prefill must do for
prompts of given *true* lengths: two matmuls of 2 operations over every
(query, row) pair a layer attends, for every head — position ``t`` attends
the ``t mod window + 1`` exact rows of its own window and one pooled pair for
each chunk of the windows before it, ``(window / chunk) . (t div window)``.
Bound by operations."""
from __future__ import annotations


def rows_attended(cfg, n: int) -> int:
    """Rows of either kind that the positions of a prompt of ``n`` attend,
    summed: by whole windows and the open one."""
    W, P = cfg.window_size, cfg.window_size // cfg.chunk_size
    whole, rest = divmod(int(n), W)
    exact = whole * W * (W + 1) // 2 + rest * (rest + 1) // 2
    pooled = P * (W * whole * (whole - 1) // 2 + rest * whole)
    return exact + pooled


def flops(cfg, true_lens: list[float]) -> float:
    return sum(4 * cfg.n_heads * cfg.head_dim * cfg.n_layers
               * rows_attended(cfg, t) for t in true_lens)


def least_seconds(cfg, peaks: dict, true_lens: list[float]) -> float:
    return flops(cfg, true_lens) / peaks["bf16_flops_per_s"]

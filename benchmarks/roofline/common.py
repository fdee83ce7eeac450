"""Counts shared by the operation and byte functions: the matmul parameters
of one decoder layer and of the head, from the configuration's sizes."""
from __future__ import annotations


def layer_matmul_params(cfg) -> int:
    d, hd = cfg.d_model, cfg.head_dim
    attn = d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd + cfg.n_heads * hd * d
    return attn + 3 * d * cfg.d_ff


def head_params(cfg) -> int:
    return cfg.d_model * cfg.vocab_size


def dtype_bytes(cfg) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[cfg.dtype]

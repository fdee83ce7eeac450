"""KV-cache autoregressive generation for the Llama family.

TPU-native counterpart of the reference's vLLM engine role (ref:
python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_engine.py) —
not a port of vLLM: a jit-compiled prefill + lax.scan decode loop with a
static-shape KV cache, so XLA compiles ONE program per (batch, prompt_len,
max_new) bucket and the MXU sees batched matmuls at every step. Left
padding + per-sequence offsets let ragged prompts share a batch.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.llm.llama import _gqa_attn
from ray_tpu.llm.programs import UnsupportedByModel, serving_programs
from ray_tpu.models.llama import (
    LlamaConfig, llama_attn_out, llama_ffn, llama_project)
from ray_tpu.ops.basic import rms_norm, rope_freqs


def init_cache(cfg: LlamaConfig, batch: int, max_len: int):
    """[n_layers, B, max_len, n_kv_heads, head_dim] k/v arrays."""
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    dtype = jnp.dtype(cfg.dtype)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def prefill(params, tokens, pad_lens, cfg: LlamaConfig, cache):
    """Process the (left-padded) prompt in one batched pass, filling the
    cache; returns last-position logits + cache.

    tokens: [B, Tp] int32, left-padded; pad_lens: [B] pad counts."""
    Tp = tokens.shape[1]
    cos, sin = rope_freqs(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    positions = jnp.maximum(jnp.arange(Tp)[None, :] - pad_lens[:, None], 0)
    # causal AND not-a-pad-key
    idx = jnp.arange(Tp)
    causal = idx[None, :, None] >= idx[None, None, :]
    valid_key = idx[None, None, :] >= pad_lens[:, None, None]
    mask = jnp.logical_and(causal, valid_key)

    x = params["tok"]["embedding"][tokens]
    for i in range(cfg.n_layers):
        layer = params[f"layers_{i}"]
        q, k, v = llama_project(layer, x, cos, sin, positions, cfg)
        cache["k"] = cache["k"].at[i, :, :Tp].set(k)
        cache["v"] = cache["v"].at[i, :, :Tp].set(v)
        att = _gqa_attn(q, k, v, mask)
        x = llama_ffn(layer, llama_attn_out(layer, x, att))
    x = rms_norm(x, params["norm"]["scale"])
    logits = x[:, -1] @ params["lm_head"]["kernel"]
    return logits, cache


def decode_step(params, token, pos, pad_lens, cfg: LlamaConfig, cache):
    """One incremental step: token [B] at absolute cache position pos
    (scalar); attends the whole cache through a validity mask (static
    shapes — XLA compiles exactly one step program)."""
    max_len = cache["k"].shape[2]
    cos, sin = rope_freqs(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    positions = jnp.maximum(pos - pad_lens, 0)[:, None]  # [B, 1]
    key_idx = jnp.arange(max_len)
    mask = jnp.logical_and(
        key_idx[None, None, :] <= pos,
        key_idx[None, None, :] >= pad_lens[:, None, None],
    )

    x = params["tok"]["embedding"][token][:, None, :]  # [B, 1, D]
    for i in range(cfg.n_layers):
        layer = params[f"layers_{i}"]
        q, k, v = llama_project(layer, x, cos, sin, positions, cfg)
        cache["k"] = cache["k"].at[i, :, pos].set(k[:, 0])
        cache["v"] = cache["v"].at[i, :, pos].set(v[:, 0])
        att = _gqa_attn(q, cache["k"][i], cache["v"][i], mask)
        x = llama_ffn(layer, llama_attn_out(layer, x, att))
    x = rms_norm(x, params["norm"]["scale"])
    logits = x[:, 0] @ params["lm_head"]["kernel"]
    return logits, cache


@partial(jax.jit, static_argnames=("cfg", "max_new_tokens"))
def generate_tokens(params, tokens, pad_lens, cfg: LlamaConfig,
                    max_new_tokens: int, temperature: float, key):
    """Batched generation: prefill + scan of decode steps.
    tokens: [B, Tp] left-padded prompts. Returns [B, max_new_tokens]."""
    B, Tp = tokens.shape
    cache = init_cache(cfg, B, Tp + max_new_tokens)
    logits, cache = prefill(params, tokens, pad_lens, cfg, cache)

    def pick(logits, k):
        greedy = jnp.argmax(logits, axis=-1)
        sampled = jax.random.categorical(k, logits / jnp.maximum(temperature, 1e-6))
        return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)

    def step(carry, i):
        cache, logits, key = carry
        key, sub = jax.random.split(key)
        tok = pick(logits, sub)
        logits, cache = decode_step(params, tok, Tp + i, pad_lens, cfg, cache)
        return (cache, logits, key), tok

    (cache, logits, key), out = jax.lax.scan(
        step, (cache, logits, key), jnp.arange(max_new_tokens)
    )
    return out.T  # [B, max_new_tokens]


def pad_prompts(prompts: list[list[int]], pad_id: int = 0):
    """Left-pad ragged prompts to one batch (numpy host-side)."""
    Tp = max(len(p) for p in prompts)
    B = len(prompts)
    tokens = np.full((B, Tp), pad_id, dtype=np.int32)
    pad_lens = np.zeros(B, dtype=np.int32)
    for i, p in enumerate(prompts):
        tokens[i, Tp - len(p):] = p
        pad_lens[i] = Tp - len(p)
    return jnp.asarray(tokens), jnp.asarray(pad_lens)


def generate(params, cfg: LlamaConfig, prompts: list[list[int]],
             max_new_tokens: int = 32, temperature: float = 0.0,
             seed: int = 0) -> list[list[int]]:
    """User-facing batched generate over ragged token prompts. The
    static-batch path is the Llama family's; other families are served by
    the continuous-batching engine (``llm/engine.py``) and refused here by
    name."""
    if not isinstance(cfg, LlamaConfig):
        raise UnsupportedByModel("the static-batch generate() path",
                                 serving_programs(cfg))
    tokens, pad_lens = pad_prompts(prompts)
    out = generate_tokens(
        params, tokens, pad_lens, cfg, max_new_tokens,
        jnp.float32(temperature), jax.random.PRNGKey(seed),
    )
    return [list(map(int, row)) for row in np.asarray(out)]

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

from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops.basic import rms_norm, rope, rope_freqs, swiglu


def _gqa_attn(q, k, v, mask):
    """Masked grouped-query attention. The H query heads are grouped over
    the KV key/value heads (query head h reads KV head h // G, G = H // KV,
    read from the shapes): a KV head's G query heads become G * Tq rows of
    ONE matmul against that head's keys, and of one against its values, as
    they lie — K and V are never written out to H heads. KV == H (G = 1) is
    plain multi-head attention through the same two contractions.

    The rows are merged before the contraction, not left to einsum as two
    free axes: with one free axis the TPU compiler fuses scale, mask and
    softmax into the contractions at prefill shapes as it did for the
    repeated form; with (g, q) free it writes the float32 scores out a
    second time (PERF.md section 6, PR 26).
    q: [B, Tq, H, d]; k/v: [B, Tk, KV, d]; mask: [B, Tq, Tk] (True=attend)."""
    B, Tq, H, d = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = (q.reshape(B, Tq, KV, G, d).transpose(0, 2, 3, 1, 4)
          .reshape(B, KV, G * Tq, d))
    scores = jnp.einsum("bkmd,bskd->bkms", qg, k) / jnp.sqrt(jnp.float32(d))
    scores = jnp.where(mask[:, None, None],
                       scores.reshape(B, KV, G, Tq, -1), jnp.float32(-1e30))
    w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bkms,bskd->bkmd", w.reshape(B, KV, G * Tq, -1), v)
    return (out.reshape(B, KV, G, Tq, d).transpose(0, 3, 1, 2, 4)
            .reshape(B, Tq, H, d))


def _layer_kv(layer, h, cfg):
    B, T, _ = h.shape
    hd = cfg.head_dim
    k = (h @ layer["wk"]["kernel"]).reshape(B, T, cfg.n_kv_heads, hd)
    v = (h @ layer["wv"]["kernel"]).reshape(B, T, cfg.n_kv_heads, hd)
    return k, v


def _ffn(layer, x):
    h = rms_norm(x, layer["ffn_norm"]["scale"])
    return x + swiglu(h, layer["w_gate"]["kernel"], layer["w_up"]["kernel"],
                      layer["w_down"]["kernel"])


def init_cache(cfg: LlamaConfig, batch: int, max_len: int):
    """[n_layers, B, max_len, n_kv_heads, head_dim] k/v arrays."""
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    dtype = jnp.dtype(cfg.dtype)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def prefill(params, tokens, pad_lens, cfg: LlamaConfig, cache):
    """Process the (left-padded) prompt in one batched pass, filling the
    cache; returns last-position logits + cache.

    tokens: [B, Tp] int32, left-padded; pad_lens: [B] pad counts."""
    B, Tp = tokens.shape
    max_len = cache["k"].shape[2]
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
        h = rms_norm(x, layer["attn_norm"]["scale"])
        q = (h @ layer["wq"]["kernel"]).reshape(B, Tp, cfg.n_heads, cfg.head_dim)
        k, v = _layer_kv(layer, h, cfg)
        q = rope(q, cos, sin, positions)
        k = rope(k, cos, sin, positions)
        cache["k"] = cache["k"].at[i, :, :Tp].set(k)
        cache["v"] = cache["v"].at[i, :, :Tp].set(v)
        att = _gqa_attn(q, k, v, mask)
        x = x + att.reshape(B, Tp, -1) @ layer["wo"]["kernel"]
        x = _ffn(layer, x)
    x = rms_norm(x, params["norm"]["scale"])
    logits = x[:, -1] @ params["lm_head"]["kernel"]
    return logits, cache


def decode_step(params, token, pos, pad_lens, cfg: LlamaConfig, cache):
    """One incremental step: token [B] at absolute cache position pos
    (scalar); attends the whole cache through a validity mask (static
    shapes — XLA compiles exactly one step program)."""
    B = token.shape[0]
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
        h = rms_norm(x, layer["attn_norm"]["scale"])
        q = (h @ layer["wq"]["kernel"]).reshape(B, 1, cfg.n_heads, cfg.head_dim)
        k, v = _layer_kv(layer, h, cfg)
        q = rope(q, cos, sin, positions)
        k = rope(k, cos, sin, positions)
        cache["k"] = cache["k"].at[i, :, pos].set(k[:, 0])
        cache["v"] = cache["v"].at[i, :, pos].set(v[:, 0])
        att = _gqa_attn(q, cache["k"][i], cache["v"][i], mask)
        x = x + att.reshape(B, 1, -1) @ layer["wo"]["kernel"]
        x = _ffn(layer, x)
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
        from ray_tpu.llm.engine import UnsupportedByModel

        raise UnsupportedByModel("the static-batch generate() path",
                                 type(cfg).__name__)
    tokens, pad_lens = pad_prompts(prompts)
    out = generate_tokens(
        params, tokens, pad_lens, cfg, max_new_tokens,
        jnp.float32(temperature), jax.random.PRNGKey(seed),
    )
    return [list(map(int, row)) for row in np.asarray(out)]

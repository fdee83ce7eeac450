"""The serving programs of ``models/mla_moe.py`` for the continuous-batching
engine: same slots, pages, tables and block pipeline as the Llama programs
of ``llm/llama.py``, another cache and another layer.

* **The cache is one latent pool** ``[L, P, PS, r + rope]``: a token leaves
  its normed latent ``c`` and its one rotary key ``k_rope`` per layer (576
  numbers at the published widths, against 2 x KV x hd).
* **Two attention paths over that one cache.** Prefill expands the fresh
  rows to per-head keys and values once for all the prompt's queries
  (``mla_attend_expanded``); decode absorbs ``wkv_b`` into the query and
  the output and attends the rows as they lie: in the pool, page by page
  (``paged_latent_attention``) or over the gathered window
  (``mla_attend_absorbed``), by the seam's rule, bound here as
  ``_reads_in_place``.
* **The expert layer** (``parallel/moe.py``) sees 32 tokens a decode step
  (bound by the bytes of the experts they touch: on a TPU one kernel streams
  them, ``ops/grouped_swiglu.py``) and a whole wave's prompt tokens in
  prefill (bound by the MXU: ``ragged_dot``); dead slots and prompt padding
  are routed nowhere.
* **What the experts did rides back with the tokens.** A decode step's row
  is ``[B tokens | MOE_STATS]``: routed assignments, distinct experts touched,
  the largest expert's load, the expert slots they are a share of and the
  grouped product's passes over an expert's matrices, each summed over the
  expert layers — read at the block's one sync, no second
  device->host read.

LoRA, int8 pools, speculative decoding, suffix prefill and page export
assume K and V pools; ``llm/engine.py`` refuses them for this family by name.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.llm.programs import (
    MOE_STATS, ServePrograms, _sample_tail, decode_frame, last_rows,
    moe_load_stats, reads_in_place)
from ray_tpu.models.mla_moe import (
    MlaMoeConfig, mla_absorb, mla_attend_absorbed, mla_attend_expanded,
    mla_expand, mla_moe_ffn, mla_moe_init, mla_project)
from ray_tpu.ops.basic import rms_norm, rope_freqs
from ray_tpu.ops.paged_attention import paged_latent_attention
from ray_tpu.utils import tracing

# The seam's platform rule under this module's own name, asked through this
# global by every program here and by ``PROGRAMS.decode_in_place``: ``tests/``
# ASSIGN an answer here to run the kernels interpreted.
_reads_in_place = reads_in_place


def make_latent_pool(cfg: MlaMoeConfig, page_size: int, n_pages: int,
                     kv_dtype: str | None):
    """The model's cache: a 1-tuple holding the latent pool."""
    if kv_dtype not in (None, "native", "bf16"):
        raise ValueError(f"unknown kv_dtype {kv_dtype!r}")
    dtype = jnp.bfloat16 if kv_dtype == "bf16" else jnp.dtype(cfg.dtype)
    return (jnp.zeros((cfg.n_layers, n_pages, page_size, cfg.latent_width),
                      dtype),)


def _decode_body(params, tokens, pos, page_tables, cache, active, temps, key,
                 cfg: MlaMoeConfig):
    """One decode step for every slot (masked where inactive), absorbed
    attention over each slot's ``pos + 1`` cached rows: in place through the
    page table (an inactive slot attends nothing) where ``_reads_in_place``
    holds, else over the whole gathered window with the positions past
    ``pos`` masked. Returns (next_tok [B], cache, stats)."""
    pool, = cache
    B = tokens.shape[0]
    L, P, PS, W = pool.shape
    MAXP = page_tables.shape[1]
    cos, sin = rope_freqs(cfg.qk_rope_head_dim, cfg.max_seq_len, cfg.rope_theta)
    positions = pos[:, None]
    row = jnp.take_along_axis(page_tables, (pos // PS)[:, None], axis=1)[:, 0]
    off = pos % PS
    in_place = _reads_in_place()
    if in_place:
        lengths = jnp.where(active, pos + 1, 0)
    else:
        mask = jnp.arange(MAXP * PS)[None, None, :] <= pos[:, None, None]
    loads = []
    with tracing.part("embed"):
        x = params["tok"]["embedding"][tokens][:, None, :]
    for i in range(cfg.n_layers):
        layer = params[f"layers_{i}"]
        with tracing.part("project"):
            h = rms_norm(x, layer["attn_norm"]["scale"])
            q, latent = mla_project(layer, h, cos, sin, positions, cfg)
        with tracing.part("kv_write"):
            pool = pool.at[i, row, off].set(latent[:, 0].astype(pool.dtype))
        with tracing.part("attention"):
            if in_place:
                o_lat = paged_latent_attention(
                    mla_absorb(layer, q, cfg)[:, 0].astype(pool.dtype), pool,
                    i, page_tables, lengths, v_width=cfg.kv_lora_rank,
                    sm_scale=cfg.qk_head_dim ** -0.5)
                att = mla_expand(layer, o_lat[:, None].astype(x.dtype), cfg)
            else:
                window = pool[i][page_tables].reshape(
                    B, MAXP * PS, W).astype(x.dtype)
                att = mla_attend_absorbed(layer, q, window, mask, cfg)
        with tracing.part("attn_out"):
            x = x + att @ layer["wo"]["kernel"]
        x, load = mla_moe_ffn(layer, x, cfg, valid=active[:, None])
        if load is not None:
            loads.append(load)
    with tracing.part("head"):
        x = rms_norm(x, params["norm"]["scale"])
        logits = x[:, 0] @ params["lm_head"]["kernel"]
    next_tok = _sample_tail(logits, temps, key)
    return (jnp.where(active, next_tok, 0), (pool,),
            moe_load_stats(loads, B * cfg.n_experts_per_tok))


@partial(jax.jit, static_argnames=("cfg", "n_steps"), donate_argnums=(6,))
def mla_moe_decode_multi(params, loras, aids, tokens, seq_lens, page_tables,
                         pool, active, temps, key, cfg: MlaMoeConfig,
                         n_steps: int):
    """``n_steps`` fused decode steps as one device program: the contract of
    ``llm/llama.py`` ``paged_decode_multi`` with one latent pool in place of the K
    and V pools, and rows of ``[B tokens | MOE_STATS]``. ``loras``/``aids`` are
    the engine's (None / zeros here: refused at construction)."""
    return decode_frame(_decode_body, params, tokens, seq_lens, page_tables,
                        (pool,), active, temps, key, cfg, n_steps)


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(5,))
def mla_moe_prefill_batch(params, loras, aids, tokens, pages, pool,
                          true_lens, temps, key, cfg: MlaMoeConfig):
    """Prefill a whole admission wave as one batched forward: the contract
    of ``llm/llama.py`` ``paged_prefill_batch``. Attention is over the wave's FRESH
    cache rows, expanded; what it writes to the pool is what decode reads
    back absorbed. Returns (first tokens [N], pool)."""
    N, Tp = tokens.shape
    L, P, PS, W = pool.shape
    cos, sin = rope_freqs(cfg.qk_rope_head_dim, cfg.max_seq_len, cfg.rope_theta)
    idx = jnp.arange(Tp)
    positions = jnp.broadcast_to(idx[None, :], (N, Tp))
    mask = jnp.broadcast_to(idx[None, :, None] >= idx[None, None, :],
                            (N, Tp, Tp))
    rows = pages[:, idx // PS]
    offs = jnp.broadcast_to(idx % PS, (N, Tp))
    valid = idx[None, :] < true_lens[:, None]  # padding is routed nowhere
    with tracing.part("embed"):
        x = params["tok"]["embedding"][tokens]
    for i in range(cfg.n_layers):
        layer = params[f"layers_{i}"]
        with tracing.part("project"):
            h = rms_norm(x, layer["attn_norm"]["scale"])
            q, latent = mla_project(layer, h, cos, sin, positions, cfg)
        with tracing.part("kv_write"):
            pool = pool.at[i, rows, offs].set(latent.astype(pool.dtype))
        att = mla_attend_expanded(layer, q, latent, mask, cfg)
        with tracing.part("attn_out"):
            x = x + att @ layer["wo"]["kernel"]
        x, _ = mla_moe_ffn(layer, x, cfg, valid=valid)
    with tracing.part("head"):
        x = rms_norm(x, params["norm"]["scale"])
        logits = last_rows(x, true_lens) @ params["lm_head"]["kernel"]
    return _sample_tail(logits, temps, key), pool


PROGRAMS = ServePrograms(
    family="mla_moe", make_cache=make_latent_pool,
    decode_multi=mla_moe_decode_multi, prefill_batch=mla_moe_prefill_batch,
    init=mla_moe_init, stats=MOE_STATS,
    decode_in_place=lambda cache: _reads_in_place(),
    caches="one latent row a position, keys and values in one pool")

"""Continuous-batching LLM engine with a paged KV cache and LoRA multiplex.

TPU-native counterpart of the reference's delegated vLLM engine (ref:
python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_engine.py:95 —
there Ray wires vLLM; here the engine is owned). Design maps the vLLM
ideas onto XLA's static-shape world:

* **Fixed decode slots.** One jitted decode step advances ALL ``max_batch``
  slots every iteration; inactive slots are masked. Admission = writing a
  new request's prompt KV into a free slot's pages *between* decode steps
  — a request never waits for the running batch to drain (continuous
  batching at decode-step granularity).
* **Paged KV.** One global pool ``[layers, n_pages, page_size, kv, hd]``;
  each slot owns a page table. Decode gathers the slot's pages for
  attention; prefill scatters prompt KV into freshly allocated pages.
  Shapes never depend on sequence length, so XLA compiles exactly one
  decode program (plus one prefill program per prompt-length bucket).
* **Streaming.** Every request gets an asyncio queue; tokens land there
  the step they are sampled.
* **LoRA multiplex** (ref: serve/multiplex.py): stacked low-rank adapters
  on the q/v projections, selected per slot — different requests in one
  decode batch can use different adapters (adapter 0 = base model).
* **The cache is the model's.** The engine owns slots, pages, tables,
  admission, blocks and the loop; what a page HOLDS is declared by the
  model family's programs (``ServePrograms``, chosen by the config's type):
  a K pool and a V pool for the Llama family, one latent pool for
  ``models/mla_moe.py``. The engine carries it as one tuple of pools
  (``self.cache``), hands it to every program and takes it back donated.
"""
from __future__ import annotations

import asyncio
import collections
import itertools
import time
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.devtools import chaos
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops.basic import rms_norm, rope, rope_freqs
from ray_tpu.ops.paged_attention import paged_decode_attention
from ray_tpu.utils import metrics, tracing


def _lora_delta(h, loras, name, aid):
    """Per-slot low-rank delta: h[B,T,D] x A[aid][D,r] x Bm[aid][r,O]."""
    if loras is None:
        return 0.0
    a = loras[name + "_a"][aid]  # [B, D, r]
    b = loras[name + "_b"][aid]  # [B, r, O]
    return jnp.einsum("btd,bdr->btr", h, a) @ b if a.ndim == 3 else (h @ a) @ b


# shared with the static-batch path — one implementation of the numerics
from ray_tpu.llm.generation import _ffn, _gqa_attn  # noqa: E402


def _kv_shape(pool):
    return (pool["q"] if isinstance(pool, dict) else pool).shape


def _kv_write(pool, i, row, off, val):
    """Store new K/V rows; int8 pools ({"q": int8, "s": f32 scales})
    quantize symmetrically per (token, kv-head) — one scale per hd
    vector, the granularity that keeps dequant a fused broadcast-mul.

    val: [..., KV, hd] float; row/off index [L, P, PS] positions."""
    if not isinstance(pool, dict):
        return pool.at[i, row, off].set(val)
    s = jnp.max(jnp.abs(val), axis=-1) / 127.0           # [..., KV]
    # clip BEFORE the int8 cast: low-precision (bf16) scale rounding can
    # put the max element's quotient at 128, and float->int overflow is
    # implementation-defined in XLA (saturates here, wraps elsewhere)
    q = jnp.clip(jnp.round(val / jnp.maximum(s, 1e-8)[..., None]),
                 -127, 127).astype(jnp.int8)
    return {"q": pool["q"].at[i, row, off].set(q),
            "s": pool["s"].at[i, row, off].set(s.astype(jnp.float32))}


def _kv_read(pool, i, page_tables, B, MAXP, PS, KV, hd, dtype):
    """Gather an attention window ``[B, MAXP * PS, KV, hd]``: every page of
    every slot's table, live or not — a slice of the layer's pool, the
    gather itself, then one read by each contraction of ``_gqa_attn``.
    What still reads the pool this way: several query rows a slot (suffix
    prefill, speculative decode and verify), int8 pools, and the decode
    step wherever ``_reads_in_place`` says no. On the chip the decode step
    of a plain pool does not (``ops/paged_attention.py``): there this
    window cost half the device's time for a tenth of it live (PERF.md
    section 6, PR 28), and this function with ``_gqa_attn`` is the plain
    reference that kernel is tested against. int8 pools move HALF the HBM
    bytes of bf16 through it; the scale gather is hd-times smaller —
    noise."""
    if not isinstance(pool, dict):
        return pool[i][page_tables].reshape(B, MAXP * PS, KV, hd)
    q = pool["q"][i][page_tables].reshape(B, MAXP * PS, KV, hd)
    s = pool["s"][i][page_tables].reshape(B, MAXP * PS, KV, 1)
    return q.astype(dtype) * s.astype(dtype)


@partial(jax.jit, donate_argnums=(0,))
def _scatter_pages_jit(pool, idx, stack):
    if isinstance(pool, dict):
        return {"q": pool["q"].at[:, idx].set(stack["q"]),
                "s": pool["s"].at[:, idx].set(stack["s"])}
    return pool.at[:, idx].set(stack.astype(pool.dtype))


def scatter_pages(pool, page_ids, stack):
    """Write an adopted page stack into pool rows ``page_ids`` (device
    op; the engine runs this at admission points, ordered like a prefill
    dispatch). ``stack`` is a bare ``[L, n, PS, KV, hd]`` array for plain
    pools or a ``{"q", "s"}`` dict for int8 pools — the shape
    ``disagg.adopt_pages`` returns. The pool is DONATED: an unjitted
    ``.at[].set`` copies the entire pool per adoption (tens of MB for a
    few adopted KB), which priced cache hits above the prefills they
    save; callers must rebind their pool to the return value."""
    idx = jnp.asarray(np.asarray(page_ids, np.int32))
    if isinstance(pool, dict):
        stack = {"q": jnp.asarray(stack["q"]), "s": jnp.asarray(stack["s"])}
    else:
        stack = jnp.asarray(stack)
    return _scatter_pages_jit(pool, idx, stack)


def _reads_in_place(pool) -> bool:
    """Whether the decode step's attention reads this pool where it lies
    (``paged_decode_attention``: only the pages that hold tokens) or
    through ``_kv_read``'s gathered window. Decided by what the code can
    see, no option: a plain pool on a TPU takes the kernel. An int8 pool
    keeps the window (the kernel does not dequantise); so does every other
    backend, where the kernel would be interpreted (seconds a call site to
    trace, and nothing to gain); and a single KV head under 32 bits, whose
    one-row page slice Mosaic refuses (tiling (2, 128))."""
    if isinstance(pool, dict) or jax.default_backend() != "tpu":
        return False
    return pool.shape[3] > 1 or pool.dtype.itemsize >= 4


def _sample_tail(logits, temps, key):
    """The sampling tail every serving program ends in: greedy where a row's
    temperature is 0, a categorical draw elsewhere. logits: [N, V]; temps:
    [N]. Returns [N] int32."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sampled():
        # Threefry bits for [N, V] gumbels are NOT free at decode batch
        # sizes — only pay when some row actually samples
        s = jax.random.categorical(
            key, logits / jnp.maximum(temps, 1e-6)[:, None]).astype(jnp.int32)
        return jnp.where(temps > 0, s, greedy)

    return jax.lax.cond(jnp.any(temps > 0), sampled, lambda: greedy)


def _decode_body(params, loras, aids, tokens, pos, page_tables,
                 kpool, vpool, active, temps, key, cfg: LlamaConfig):
    """One decode step for every slot (masked where inactive).

    tokens: [B] current input token; pos: [B] tokens already cached (the
    new token lands at that position); page_tables: [B, MAXP]; aids: [B]
    adapter ids; temps: [B]. Returns (next_tok [B], kpool, vpool).
    Pools are either plain [L, P, PS, KV, hd] arrays (cfg dtype) or int8
    quantized dicts (see _kv_write) — the engine's kv_dtype option.

    Every layer writes the new row into the pools, then attends the
    slot's ``pos + 1`` positions: in place, page by page through the table
    (``paged_decode_attention``; an inactive slot attends nothing) where
    ``_reads_in_place`` holds, else over ``_kv_read``'s whole window with
    the positions past ``pos`` masked."""
    B = tokens.shape[0]
    L, P, PS, KV, hd = _kv_shape(kpool)
    MAXP = page_tables.shape[1]
    cos, sin = rope_freqs(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    positions = pos[:, None]
    row = jnp.take_along_axis(page_tables, (pos // PS)[:, None], axis=1)[:, 0]
    off = pos % PS
    in_place = _reads_in_place(kpool)
    if in_place:
        lengths = jnp.where(active, pos + 1, 0)
    else:
        key_idx = jnp.arange(MAXP * PS)
        mask = key_idx[None, None, :] <= pos[:, None, None]

    Dq = cfg.n_heads * hd
    Dkv = KV * hd
    x = params["tok"]["embedding"][tokens][:, None, :]
    for i in range(cfg.n_layers):
        layer = params[f"layers_{i}"]
        h = rms_norm(x, layer["attn_norm"]["scale"])
        # fused qkv / gate-up matmuls: at decode batch sizes each step is
        # dominated by per-op dispatch, not FLOPs — the concatenated
        # weights are loop-invariant, so XLA hoists them out of the scan
        # and every layer runs 2 fat matmuls instead of 5 thin ones
        wqkv = jnp.concatenate(
            [layer["wq"]["kernel"], layer["wk"]["kernel"],
             layer["wv"]["kernel"]], axis=1)
        qkv = h @ wqkv
        q = (qkv[..., :Dq] + _lora_delta(h, loras, "wq", aids)
             ).reshape(B, 1, cfg.n_heads, hd)
        k = qkv[..., Dq:Dq + Dkv].reshape(B, 1, KV, hd)
        v = (qkv[..., Dq + Dkv:] + _lora_delta(h, loras, "wv", aids)
             ).reshape(B, 1, KV, hd)
        q = rope(q, cos, sin, positions)
        k = rope(k, cos, sin, positions)
        kpool = _kv_write(kpool, i, row, off, k[:, 0])
        vpool = _kv_write(vpool, i, row, off, v[:, 0])
        if in_place:
            att = paged_decode_attention(
                q[:, 0], kpool, vpool, i, page_tables, lengths)
        else:
            kb = _kv_read(kpool, i, page_tables, B, MAXP, PS, KV, hd, k.dtype)
            vb = _kv_read(vpool, i, page_tables, B, MAXP, PS, KV, hd, v.dtype)
            att = _gqa_attn(q, kb, vb, mask)
        x = x + att.reshape(B, 1, -1) @ layer["wo"]["kernel"]
        hf = rms_norm(x, layer["ffn_norm"]["scale"])
        w_gu = jnp.concatenate(
            [layer["w_gate"]["kernel"], layer["w_up"]["kernel"]], axis=1)
        gu = hf @ w_gu
        ff = gu.shape[-1] // 2
        x = x + (jax.nn.silu(gu[..., :ff]) * gu[..., ff:]
                 ) @ layer["w_down"]["kernel"]
    x = rms_norm(x, params["norm"]["scale"])
    logits = x[:, 0] @ params["lm_head"]["kernel"]

    next_tok = _sample_tail(logits, temps, key)
    return jnp.where(active, next_tok, 0), kpool, vpool


@partial(jax.jit, static_argnames=("cfg", "n_steps"), donate_argnums=(6, 7))
def paged_decode_multi(params, loras, aids, tokens, seq_lens, page_tables,
                       kpool, vpool, active, temps, key, cfg: LlamaConfig,
                       n_steps: int):
    """``n_steps`` fused decode steps as ONE device program (lax.scan).

    Decode is memory-bound; what killed throughput was the per-step host
    round trip (dispatch latency + arg upload + token download + asyncio),
    ~100x the step itself. Fusing K steps amortizes all of it K-fold; the
    host sees tokens in [K, B] blocks. The final (tokens, positions) carry
    is returned ON DEVICE so consecutive blocks chain without any host
    round trip — the engine pipelines the next block's dispatch before
    syncing this block's tokens. Slots that finish mid-block keep decoding
    junk — a position past the slot's allocated pages writes to and reads
    from whatever its table holds there (the junk page 0, or a page the
    table clips to; the in-place kernel walks ``ceil((pos + 1) / PS)``
    entries of the table, at most all of it, so it fetches those pages like
    any other), future-position writes are masked until legitimately
    overwritten, and the host discards the extra tokens, so over-decode is
    pure (bounded) waste, never corruption. The pools are updated in place
    through the scan: the kernel reads them as operands and returns only
    the attended rows (tests/test_chip_compile.py holds the compiled
    program to no copy of a pool)."""
    def step(carry, k):
        tok, pos, kpool, vpool = carry
        nxt, kpool, vpool = _decode_body(
            params, loras, aids, tok, pos, page_tables, kpool, vpool,
            active, temps, jax.random.fold_in(key, k), cfg)
        return (nxt, pos + 1, kpool, vpool), nxt

    (tok, pos, kpool, vpool), toks = jax.lax.scan(
        step, (tokens, seq_lens, kpool, vpool), jnp.arange(n_steps))
    return toks, tok, pos, kpool, vpool


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(5, 6))
def paged_prefill_batch(params, loras, aids, tokens, pages, kpool, vpool,
                        true_lens, temps, key, cfg: LlamaConfig):
    """Prefill a whole admission wave as ONE batched forward.

    tokens: [N, Tp_pad] right-padded prompts (same pad bucket); pages:
    [N, n_pages] pool pages per request (dummy rows use the junk page 0);
    true_lens/temps: [N]. Returns (first tokens [N], kpool, vpool).
    Batching the wave (instead of scanning rows at batch 1) matters
    because small-batch steps are per-op-overhead bound; one fat forward
    amortizes it across the whole wave."""
    N, Tp = tokens.shape
    L, P, PS, KV, hd = _kv_shape(kpool)
    cos, sin = rope_freqs(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    positions = jnp.arange(Tp)[None, :]
    idx = jnp.arange(Tp)
    mask = idx[None, :, None] >= idx[None, None, :]  # causal
    rows = pages[:, idx // PS]  # [N, Tp] pool row per prompt position
    offs = jnp.broadcast_to(idx % PS, (N, Tp))
    x = params["tok"]["embedding"][tokens]  # [N, Tp, D]
    for i in range(cfg.n_layers):
        layer = params[f"layers_{i}"]
        h = rms_norm(x, layer["attn_norm"]["scale"])
        q = (h @ layer["wq"]["kernel"] + _lora_delta(h, loras, "wq", aids)
             ).reshape(N, Tp, cfg.n_heads, hd)
        k = (h @ layer["wk"]["kernel"]).reshape(N, Tp, KV, hd)
        v = (h @ layer["wv"]["kernel"] + _lora_delta(h, loras, "wv", aids)
             ).reshape(N, Tp, KV, hd)
        q = rope(q, cos, sin, positions)
        k = rope(k, cos, sin, positions)
        kpool = _kv_write(kpool, i, rows, offs, k)
        vpool = _kv_write(vpool, i, rows, offs, v)
        att = _gqa_attn(q, k, v, mask)  # prefill attends the FRESH k/v:
        # quantization only affects what later decode steps read back
        x = x + att.reshape(N, Tp, -1) @ layer["wo"]["kernel"]
        x = _ffn(layer, x)
    x = rms_norm(x, params["norm"]["scale"])
    last = jnp.take_along_axis(
        x, (true_lens - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    logits = last @ params["lm_head"]["kernel"]  # [N, V]
    return _sample_tail(logits, temps, key), kpool, vpool


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(5, 6))
def paged_prefill_suffix(params, loras, aids, tokens, pages, kpool, vpool,
                         prefix_lens, true_lens, temps, key, cfg: LlamaConfig):
    """Prefill only a prompt's SUFFIX over already-resident prefix KV —
    the cross-request prefix-cache fast path (vLLM's PagedAttention
    sharing argument run cross-request: a cached prefix of k full pages
    is adopted into this pool verbatim and never recomputed).

    tokens: [N, Ts_pad] right-padded suffix tokens; pages: [N, W] page
    table covering prefix AND suffix positions in prompt order (junk
    page 0 beyond); prefix_lens: [N] PAGE-ALIGNED token counts already
    in the pool; true_lens: [N] real suffix lengths. Suffix position j
    sits at absolute position prefix_len + j, so its KV lands in the
    suffix pages and its attention window — gathered through the page
    table exactly like decode — covers the prefix for free. Returns
    (first tokens [N], kpool, vpool).

    int8 pools: the suffix queries read the prefix (and their own fresh
    K/V) back through dequantization, where full prefill attends the
    fresh float K/V directly — parity with the aggregated path is exact
    for float pools and within quantization noise for int8."""
    N, Ts = tokens.shape
    L, P, PS, KV, hd = _kv_shape(kpool)
    W = pages.shape[1]
    cos, sin = rope_freqs(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    positions = prefix_lens[:, None] + jnp.arange(Ts)[None, :]  # [N, Ts]
    rows = jnp.take_along_axis(pages, positions // PS, axis=1)
    offs = positions % PS
    key_idx = jnp.arange(W * PS)
    # window index == absolute position (the table is prompt-ordered),
    # so causal masking is one compare; tail junk-page keys sit past
    # every real position and mask out
    mask = key_idx[None, None, :] <= positions[:, :, None]  # [N, Ts, W*PS]
    x = params["tok"]["embedding"][tokens]
    for i in range(cfg.n_layers):
        layer = params[f"layers_{i}"]
        h = rms_norm(x, layer["attn_norm"]["scale"])
        q = (h @ layer["wq"]["kernel"] + _lora_delta(h, loras, "wq", aids)
             ).reshape(N, Ts, cfg.n_heads, hd)
        k = (h @ layer["wk"]["kernel"]).reshape(N, Ts, KV, hd)
        v = (h @ layer["wv"]["kernel"] + _lora_delta(h, loras, "wv", aids)
             ).reshape(N, Ts, KV, hd)
        q = rope(q, cos, sin, positions)
        k = rope(k, cos, sin, positions)
        kpool = _kv_write(kpool, i, rows, offs, k)
        vpool = _kv_write(vpool, i, rows, offs, v)
        kb = _kv_read(kpool, i, pages, N, W, PS, KV, hd, k.dtype)
        vb = _kv_read(vpool, i, pages, N, W, PS, KV, hd, v.dtype)
        att = _gqa_attn(q, kb, vb, mask)
        x = x + att.reshape(N, Ts, -1) @ layer["wo"]["kernel"]
        x = _ffn(layer, x)
    x = rms_norm(x, params["norm"]["scale"])
    last = jnp.take_along_axis(
        x, (true_lens - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    logits = last @ params["lm_head"]["kernel"]
    return _sample_tail(logits, temps, key), kpool, vpool


# --------------------------------------------------------------- speculative
def _ngram_propose(hist, pos, k: int, m: int):
    """Self-drafting prompt-lookup (Leviathan-style speculative decoding
    with the request's OWN history as the drafter): find the most recent
    earlier occurrence of the trailing ``m``-gram in ``hist`` and
    propose the ``k`` tokens that followed it. Pure device math — the
    drafter lives INSIDE the fused scan, so a spec block never pays a
    host round trip to draft.

    hist: [B, H] token history; positions ``0..pos`` are valid and
    ``hist[b, pos[b]]`` is the pending input token. Returns
    (drafts [B, k], draft_len [B]) with draft_len 0 where no match."""
    B, H = hist.shape
    n_win = H - m + 1
    gidx = pos[:, None] - (m - 1) + jnp.arange(m)[None, :]
    pattern = jnp.take_along_axis(hist, jnp.clip(gidx, 0, H - 1), axis=1)
    # all H-m+1 windows of width m as m shifted views: wins[b, i, t] =
    # hist[b, i + t] — one [B, n_win, m] compare finds every candidate
    wins = jnp.stack([hist[:, t:t + n_win] for t in range(m)], axis=-1)
    match = jnp.all(wins == pattern[:, None, :], axis=-1)     # [B, n_win]
    ends = jnp.arange(n_win) + (m - 1)                        # window end j
    valid = (ends[None, :] < pos[:, None]) & (pos[:, None] >= m)
    # a match at j proposes the pos-j tokens that FOLLOWED it, capped at
    # k — so prefer the most recent match with a full k followers (on
    # periodic text the nearest match sits at pos-1 and would draft just
    # ONE token), falling back to the nearest match otherwise
    hit = match & valid
    j_full = jnp.max(jnp.where(hit & (ends[None, :] <= pos[:, None] - k),
                               ends[None, :], -1), axis=1)
    j_any = jnp.max(jnp.where(hit, ends[None, :], -1), axis=1)
    j = jnp.where(j_full >= 0, j_full, j_any)
    found = j >= 0
    dl = jnp.where(found, jnp.minimum(k, pos - j), 0).astype(jnp.int32)
    didx = j[:, None] + 1 + jnp.arange(k)[None, :]
    drafts = jnp.take_along_axis(hist, jnp.clip(didx, 0, H - 1), axis=1)
    return drafts, dl


def _spec_verify_body(params, loras, aids, inputs, positions, page_tables,
                      kpool, vpool, temps, key, cfg: LlamaConfig):
    """One fused multi-position forward over ``T = k+1`` decode
    positions per slot — the ``paged_prefill_suffix`` shape run at the
    decode batch: token j of a slot sits at absolute position
    ``positions[b, j]``, its KV lands in the slot's pages through the
    page table, and its attention window (gathered exactly like decode)
    covers everything at or before it — including the sibling draft
    positions written THIS step, which is precisely the speculative
    verification semantics (draft j attends drafts 1..j-1).

    Returns (greedy [B, T] target tokens per position, next0 [B] the
    position-0 token with sampling applied for temps > 0 rows, kpool,
    vpool)."""
    B, T = inputs.shape
    L, P, PS, KV, hd = _kv_shape(kpool)
    MAXP = page_tables.shape[1]
    cos, sin = rope_freqs(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    rows = jnp.take_along_axis(page_tables, positions // PS, axis=1)
    offs = positions % PS
    key_idx = jnp.arange(MAXP * PS)
    mask = key_idx[None, None, :] <= positions[:, :, None]  # [B,T,MAXP*PS]
    Dq = cfg.n_heads * hd
    Dkv = KV * hd
    x = params["tok"]["embedding"][inputs]  # [B, T, D]
    for i in range(cfg.n_layers):
        layer = params[f"layers_{i}"]
        h = rms_norm(x, layer["attn_norm"]["scale"])
        wqkv = jnp.concatenate(
            [layer["wq"]["kernel"], layer["wk"]["kernel"],
             layer["wv"]["kernel"]], axis=1)
        qkv = h @ wqkv
        q = (qkv[..., :Dq] + _lora_delta(h, loras, "wq", aids)
             ).reshape(B, T, cfg.n_heads, hd)
        kk = qkv[..., Dq:Dq + Dkv].reshape(B, T, KV, hd)
        v = (qkv[..., Dq + Dkv:] + _lora_delta(h, loras, "wv", aids)
             ).reshape(B, T, KV, hd)
        q = rope(q, cos, sin, positions)
        kk = rope(kk, cos, sin, positions)
        kpool = _kv_write(kpool, i, rows, offs, kk)
        vpool = _kv_write(vpool, i, rows, offs, v)
        kb = _kv_read(kpool, i, page_tables, B, MAXP, PS, KV, hd, kk.dtype)
        vb = _kv_read(vpool, i, page_tables, B, MAXP, PS, KV, hd, v.dtype)
        att = _gqa_attn(q, kb, vb, mask)
        x = x + att.reshape(B, T, -1) @ layer["wo"]["kernel"]
        hf = rms_norm(x, layer["ffn_norm"]["scale"])
        w_gu = jnp.concatenate(
            [layer["w_gate"]["kernel"], layer["w_up"]["kernel"]], axis=1)
        gu = hf @ w_gu
        ff = gu.shape[-1] // 2
        x = x + (jax.nn.silu(gu[..., :ff]) * gu[..., ff:]
                 ) @ layer["w_down"]["kernel"]
    x = rms_norm(x, params["norm"]["scale"])
    logits = x @ params["lm_head"]["kernel"]  # [B, T, V]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sampled():
        s = jax.random.categorical(
            key, logits[:, 0] / jnp.maximum(temps, 1e-6)[:, None]
        ).astype(jnp.int32)
        return jnp.where(temps > 0, s, greedy[:, 0])

    next0 = jax.lax.cond(jnp.any(temps > 0), sampled, lambda: greedy[:, 0])
    return greedy, next0, kpool, vpool


def _spec_verify_accept(params, loras, aids, tok, pos, drafts, dl,
                        page_tables, kpool, vpool, active, temps, key,
                        cfg: LlamaConfig):
    """Verify ``drafts`` against the target in ONE fused forward and
    apply the greedy accept rule: accept the longest draft prefix the
    target agrees with, then take the target's own token at the first
    disagreement (or the bonus token after a full accept). Emission is
    token-identical to the non-speculative greedy engine by
    construction — every emitted token IS the target's argmax given the
    same prefix. Rejected tail positions hold junk KV that the next
    step's inputs legitimately overwrite (write-before-read per layer),
    so rollback is pure position arithmetic: no pool copy.

    Returns (out [B, k+1] emission candidates, n_emit [B], n_acc [B],
    new_tok [B], new_pos [B], kpool, vpool)."""
    B, k = drafts.shape
    inputs = jnp.concatenate([tok[:, None], drafts], axis=1)
    positions = pos[:, None] + jnp.arange(k + 1)[None, :]
    greedy, next0, kpool, vpool = _spec_verify_body(
        params, loras, aids, inputs, positions, page_tables, kpool, vpool,
        temps, key, cfg)
    okm = (drafts == greedy[:, :-1]) & (jnp.arange(k)[None, :] < dl[:, None])
    n_acc = jnp.sum(jnp.cumprod(okm.astype(jnp.int32), axis=1), axis=1)
    out = jnp.concatenate([next0[:, None], greedy[:, 1:]], axis=1)
    n_emit = jnp.where(active, n_acc + 1, 0).astype(jnp.int32)
    new_tok = jnp.where(
        active, jnp.take_along_axis(out, n_acc[:, None], axis=1)[:, 0], 0)
    return out, n_emit, n_acc, new_tok, pos + n_acc + 1, kpool, vpool


@partial(jax.jit, static_argnames=("cfg", "n_steps", "k", "ngram"),
         donate_argnums=(5, 7, 8))
def paged_decode_spec(params, loras, aids, tokens, seq_lens, hist,
                      page_tables, kpool, vpool, active, spec_ok, temps,
                      key, cfg: LlamaConfig, n_steps: int, k: int,
                      ngram: int):
    """``n_steps`` SPECULATIVE decode steps as one device program: each
    scan step drafts ``k`` tokens per slot with the on-device n-gram
    matcher, verifies all of them in one fused multi-position forward,
    and advances each slot by ``n_acc + 1`` positions — so one host
    round trip can emit up to ``n_steps * (k + 1)`` tokens instead of
    ``n_steps``. The (token, position, history) carry chains on device
    between blocks exactly like ``paged_decode_multi``'s; slots where
    ``spec_ok`` is False (sampled rows, per-request opt-out) run with
    draft_len 0, i.e. plain one-token decode — a mixed spec/plain wave
    is one program, one compiled bucket per (n_steps, k).

    Returns (toks [S, B, k+1], n_emit [S, B], n_prop [S, B], tok, pos,
    hist, kpool, vpool); the host emits the first ``n_emit[s, b]``
    tokens of each row and discards the rest (the rollback)."""
    def step(carry, s):
        tok, pos, hist, kpool, vpool = carry
        drafts, dl = _ngram_propose(hist, pos, k, ngram)
        dl = jnp.where(spec_ok, dl, 0)
        out, n_emit, n_acc, tok, pos, kpool, vpool = _spec_verify_accept(
            params, loras, aids, tok, pos, drafts, dl, page_tables,
            kpool, vpool, active, temps, jax.random.fold_in(key, s), cfg)
        # record the emitted tokens into the history so the NEXT step's
        # n-gram drafter sees them (indices past n_acc drop out-of-bounds)
        B, H = hist.shape
        widx = pos[:, None] - n_acc[:, None] + jnp.arange(k + 1)[None, :]
        widx = jnp.where(jnp.arange(k + 1)[None, :] <= n_acc[:, None],
                         widx, H)
        hist = hist.at[jnp.arange(B)[:, None], widx].set(out, mode="drop")
        return (tok, pos, hist, kpool, vpool), (out, n_emit, dl)

    (tok, pos, hist, kpool, vpool), (toks, n_emit, n_prop) = jax.lax.scan(
        step, (tokens, seq_lens, hist, kpool, vpool), jnp.arange(n_steps))
    return toks, n_emit, n_prop, tok, pos, hist, kpool, vpool


@partial(jax.jit, static_argnames=("cfg", "k"), donate_argnums=(7, 8))
def paged_decode_verify(params, loras, aids, tokens, seq_lens, drafts,
                        page_tables, kpool, vpool, draft_lens, active,
                        temps, key, cfg: LlamaConfig, k: int):
    """One speculative step with HOST-provided drafts — the drafter-hook
    path (``spec_drafter=``: a real small model, a custom matcher). Same
    verify/accept as the fused scan, but one step per dispatch since the
    host drafter needs the accepted tokens back before proposing the
    next window. Returns (toks [B, k+1], n_emit [B], n_prop [B], tok,
    pos, kpool, vpool)."""
    out, n_emit, n_acc, tok, pos, kpool, vpool = _spec_verify_accept(
        params, loras, aids, tokens, seq_lens, drafts, draft_lens,
        page_tables, kpool, vpool, active, temps, key, cfg)
    return out, n_emit, draft_lens, tok, pos, kpool, vpool


def make_lora_stack(cfg: LlamaConfig, adapters: dict[str, dict], rank: int):
    """Stack named adapters into gatherable arrays. Index 0 is the base
    model (zero delta). adapters: name -> {"wq_a": [D,r], "wq_b": [r,O],
    "wv_a": ..., "wv_b": ...}. Returns (stack dict, name->index map)."""
    D = cfg.d_model
    O_q = cfg.n_heads * cfg.head_dim
    O_v = cfg.n_kv_heads * cfg.head_dim
    names = ["__base__"] + sorted(adapters)
    idx = {n: i for i, n in enumerate(names)}
    stack = {
        "wq_a": np.zeros((len(names), D, rank), np.float32),
        "wq_b": np.zeros((len(names), rank, O_q), np.float32),
        "wv_a": np.zeros((len(names), D, rank), np.float32),
        "wv_b": np.zeros((len(names), rank, O_v), np.float32),
    }
    for name, ad in adapters.items():
        i = idx[name]
        for k in stack:
            if k in ad:
                stack[k][i] = np.asarray(ad[k], np.float32)
    return {k: jnp.asarray(v) for k, v in stack.items()}, idx


def make_kv_pools(cfg: LlamaConfig, page_size: int, n_pages: int,
                  kv_dtype: str | None):
    """One (kpool, vpool) pair for a paged cache: plain
    ``[L, P, PS, KV, hd]`` arrays for native/bf16, ``{"q", "s"}``
    quantized dicts for int8. Shared by the engine and the disagg
    prefill workers so the two pools are structurally identical and a
    page sliced from one scatters into the other."""
    dtype = jnp.dtype(cfg.dtype)
    pool_shape = (cfg.n_layers, n_pages, page_size, cfg.n_kv_heads,
                  cfg.head_dim)
    if kv_dtype == "int8":
        # quantized cache: half the HBM bytes through the decode
        # page-table gather (the bottleneck past ~64 slots) at the
        # cost of per-(token, kv-head) symmetric int8 rounding
        def make_pool():
            return {"q": jnp.zeros(pool_shape, jnp.int8),
                    "s": jnp.zeros(pool_shape[:-1], jnp.float32)}

        return make_pool(), make_pool()
    if kv_dtype in (None, "native"):
        kpool = jnp.zeros(pool_shape, dtype)
        return kpool, jnp.zeros_like(kpool)
    if kv_dtype == "bf16":
        # explicit half-precision cache, regardless of cfg.dtype
        kpool = jnp.zeros(pool_shape, jnp.bfloat16)
        return kpool, jnp.zeros_like(kpool)
    raise ValueError(f"unknown kv_dtype {kv_dtype!r}")


class UnsupportedByModel(NotImplementedError):
    """A feature of the engine that a model family's programs do not have,
    refused by name (never a silent read of a pool that is not there)."""

    def __init__(self, feature: str, family: str):
        super().__init__(
            f"{feature} is not supported for the {family!r} model family: "
            f"it assumes a K pool and a V pool of n_kv_heads x head_dim")
        self.feature, self.family = feature, family


@dataclass(frozen=True)
class ServePrograms:
    """What the engine needs of a model family. The cache is a TUPLE of
    pools ``make_cache`` builds; every program takes its members in place
    (after ``page_tables`` in decode, after ``pages`` in prefill) and
    returns them last, donated — so the engine threads ``*self.cache``
    through without knowing what a page holds.

    ``decode_multi(params, loras, aids, tokens, seq_lens, page_tables,
    *cache, active, temps, key, cfg, n_steps) -> (rows [K, B + len(stats)],
    tok, pos, *cache)``: a step's row holds the B tokens and then one int32
    per name in ``stats`` (the model's own per-step sums, read at the
    block's one sync). ``prefill_batch(params, loras, aids, tokens, pages,
    *cache, true_lens, temps, key, cfg) -> (first [N], *cache)``.
    ``decode_in_place(cache) -> bool``: whether ``decode_multi`` fetches
    only the pages of that cache that hold tokens; None where it gathers
    every slot's whole table a step (what the read counters then report).
    The rest are the Llama family's and None elsewhere: the engine refuses
    what needs them."""
    family: str
    make_cache: callable
    decode_multi: callable
    prefill_batch: callable
    stats: tuple = ()
    decode_in_place: callable = None
    prefill_suffix: callable = None
    decode_spec: callable = None
    decode_verify: callable = None
    lora: bool = False
    int8_cache: bool = False
    page_plane: bool = False   # export_pages / submit_prefilled (disagg)


def serving_programs(cfg) -> ServePrograms:
    """The programs that serve ``cfg``, by its type: no option chooses."""
    if isinstance(cfg, LlamaConfig):
        return LLAMA_PROGRAMS
    from ray_tpu.models.mla_moe import MlaMoeConfig

    if isinstance(cfg, MlaMoeConfig):
        from ray_tpu.llm.mla_moe import PROGRAMS

        return PROGRAMS
    raise TypeError(f"no serving programs for a {type(cfg).__name__}")


LLAMA_PROGRAMS = ServePrograms(
    family="llama", make_cache=make_kv_pools,
    decode_multi=paged_decode_multi, prefill_batch=paged_prefill_batch,
    decode_in_place=lambda cache: _reads_in_place(cache[0]),
    prefill_suffix=paged_prefill_suffix, decode_spec=paged_decode_spec,
    decode_verify=paged_decode_verify, lora=True, int8_cache=True,
    page_plane=True)


@dataclass
class _Request:
    req_id: int
    prompt: list[int]
    max_tokens: int
    temperature: float
    adapter: int
    out: asyncio.Queue = field(default_factory=asyncio.Queue)
    slot: int = -1
    emitted: int = 0
    planned: int = 0  # tokens scheduled on-device (planned mode)
    cancelled: bool = False
    finished: bool = False  # completed normally (max_tokens or eos)
    # disaggregated admission (llm/disagg): (k_stack, v_stack, first_tok)
    # adopted from a prefill worker's KVPageManifest — admission scatters
    # the stacks into this engine's pool instead of running a prefill
    prefilled: tuple | None = None
    # speculative decoding: this request's draft state rides here — the
    # opt-in flag (greedy-only; sampled rows always decode plain) plus
    # its slice of the engine's token-history mirror (the drafter's
    # context), which _reserve_slot/_emit_spec_block maintain
    spec: bool = False
    # perf_counter_ns stamps: submit, prefill dispatched (or pages
    # adopted), first token on the host. Observed once a request into
    # rt_llm_queue_wait / prefill_wait / decode_seconds
    t_submit: int = 0
    t_admit: int = 0
    t_first: int = 0
    # (trace_id, span_id) of the submitter's sampled span, captured once
    # in submit; None = no retro spans for this request
    trace: tuple | None = None


class EngineFull(Exception):
    """No free slot/pages and the waiting queue is at capacity."""


class ContinuousBatchingEngine:
    """Single-process engine; drive with ``await engine.start()`` then
    ``submit`` / ``stream`` from the same event loop."""

    def __init__(self, params, cfg: LlamaConfig, *, max_batch: int = 8,
                 page_size: int = 16, n_pages: int = 256,
                 max_seq_len: int = 512, eos_id: int | None = None,
                 lora_adapters: dict[str, dict] | None = None,
                 lora_rank: int = 8, max_waiting: int = 256,
                 block_buckets: tuple[int, ...] = (4, 8, 16, 32, 64),
                 kv_dtype: str | None = None, spec_enable: bool = False,
                 spec_k: int = 4, spec_ngram: int = 2,
                 spec_drafter=None):
        self.params = params
        self.cfg = cfg
        self.B = max_batch
        self.PS = page_size
        self.MAXP = -(-max_seq_len // page_size)
        self.eos_id = eos_id
        self.max_waiting = max_waiting
        # fused-decode block sizes (one compiled program per bucket); the
        # loop picks the smallest bucket covering the longest remaining
        # request, so short interactive requests stay low-latency while
        # long generations amortize dispatch 64x
        self.block_buckets = tuple(sorted(block_buckets))
        self.programs = P = serving_programs(cfg)
        for feature, asked, has in (
                ("kv_dtype='int8'", kv_dtype == "int8", P.int8_cache),
                ("lora_adapters", bool(lora_adapters), P.lora),
                ("spec_enable", bool(spec_enable), P.decode_spec is not None)):
            if asked and not has:
                raise UnsupportedByModel(feature, P.family)
        # the model's cache, one tuple of pools (see ServePrograms)
        self.cache = tuple(P.make_cache(cfg, page_size, n_pages, kv_dtype))
        self.kv_dtype = kv_dtype or "native"
        self._kv_in_place = bool(
            P.decode_in_place and P.decode_in_place(self.cache))
        self.n_pages = n_pages
        self.free_pages = list(range(1, n_pages))  # page 0 = junk page
        self.loras = None
        self.lora_index = {"__base__": 0}
        if lora_adapters:
            self.loras, self.lora_index = make_lora_stack(
                cfg, lora_adapters, lora_rank)
        # slot state (host side)
        self.slot_req: list[_Request | None] = [None] * self.B
        self.page_tables = np.zeros((self.B, self.MAXP), np.int32)
        self.seq_lens = np.zeros(self.B, np.int32)
        self.next_tok = np.zeros(self.B, np.int32)
        self.temps = np.zeros(self.B, np.float32)
        self.aids = np.zeros(self.B, np.int32)
        self.waiting: list[_Request] = []
        self._req_ids = itertools.count(1)
        self._reqs: dict[int, _Request] = {}
        # finished requests not yet drained by a stream() consumer; bounded
        # LRU so fire-and-forget submitters can't leak token queues forever
        self._done: collections.OrderedDict[int, _Request] = (
            collections.OrderedDict())
        self._done_cap = 4 * self.B + max_waiting
        self._wake = asyncio.Event()
        self._running = False
        self._task = None
        self._rng = jax.random.PRNGKey(0)
        self.error: BaseException | None = None  # fatal loop failure
        self._compiled: set = set()  # (program, shapes) seen by _call
        # speculative decoding (README § Speculative decoding): greedy
        # requests draft spec_k tokens per step (on-device n-gram
        # matcher over spec_ngram-grams, or the spec_drafter hook) and
        # the target verifies them in one fused multi-position forward
        self.spec_enable = bool(spec_enable)
        self.spec_k = int(spec_k)
        self.spec_ngram = int(spec_ngram)
        self.spec_drafter = spec_drafter
        # token-history mirror [B, max_seq_len]: hist[i, :seq_lens[i]+1]
        # holds slot i's known tokens (prompt + emitted + pending input)
        # — the drafter's context, and the rebuild source for the
        # device-resident hist carry at admission points
        self.hist = np.zeros((self.B, self.MAXP * page_size), np.int32)
        # counters for benchmarks / tests
        self.steps = 0
        self.tokens_out = 0
        self.spec_steps = 0      # speculative verify steps run
        self.spec_proposed = 0   # draft tokens proposed (live spec rows)
        self.spec_accepted = 0   # draft tokens the target accepted
        # bounded per-block log the disagg telemetry drains:
        # (n_steps, emitted, proposed, accepted) per synced spec block
        self._block_log: collections.deque = collections.deque(maxlen=256)
        # the model's own per-step sums of the last synced decode block
        # (ServePrograms.stats), as a mean per step: annotates the next
        # dispatch and admission phases
        self._last_stats: dict = {}
        # positions that block's attention attended and fetched a step
        # (kv_live, kv_read): annotates the next dispatch
        self._last_kv: dict = {}

    @property
    def kpool(self):
        """The Llama family's K pool (``cache[0]``); other families have no
        such pool and say so."""
        return self._kv_pool(0)

    @property
    def vpool(self):
        return self._kv_pool(1)

    def _kv_pool(self, i: int):
        if not self.programs.page_plane:
            raise UnsupportedByModel("a K or V pool", self.programs.family)
        return self.cache[i]

    # ----------------------------------------------------------- public API
    async def start(self):
        if self._task is None:
            self._running = True
            self._task = asyncio.get_running_loop().create_task(self._loop())

    async def stop(self):
        self._running = False
        self._wake.set()
        if self._task is not None:
            await self._task
            self._task = None
        # nothing will produce more tokens: unblock every live consumer
        self._terminate_all_streams()

    def _terminate_all_streams(self):
        for req in list(self._reqs.values()):
            req.out.put_nowait(None)
        self._reqs.clear()
        self._done.clear()
        self.waiting.clear()
        self.slot_req = [None] * self.B

    def submit(self, prompt_tokens: list[int], *, max_tokens: int = 32,
               temperature: float = 0.0, adapter: str | None = None,
               spec: bool | None = None) -> int:
        """Queue a request; returns its id. Tokens arrive on stream().
        ``spec`` overrides the engine's ``spec_enable`` default for this
        request (greedy requests only; sampled rows decode plain either
        way)."""
        if self.error is not None:
            raise RuntimeError("engine loop died") from self.error
        if len(self.waiting) >= self.max_waiting:
            raise EngineFull(f"{len(self.waiting)} requests already waiting")
        if len(prompt_tokens) + max_tokens > self.MAXP * self.PS:
            raise ValueError(
                f"prompt ({len(prompt_tokens)}) + max_tokens ({max_tokens}) "
                f"exceeds the engine's max_seq_len ({self.MAXP * self.PS})")
        n_need = -(-(len(prompt_tokens) + max_tokens) // self.PS)
        if n_need > self.n_pages - 1:
            raise ValueError(
                f"request needs {n_need} KV pages but the pool only has "
                f"{self.n_pages - 1}")
        aid = self.lora_index.get(adapter or "__base__")
        if aid is None:
            raise ValueError(f"unknown LoRA adapter {adapter!r} "
                             f"(loaded: {sorted(self.lora_index)})")
        req = _Request(next(self._req_ids), list(prompt_tokens),
                       int(max_tokens), float(temperature), aid,
                       spec=self.spec_enable if spec is None else bool(spec))
        return self._enqueue(req)

    def _enqueue(self, req: _Request) -> int:
        req.t_submit = time.perf_counter_ns()
        if tracing.enabled():
            req.trace = tracing.current()
        self._reqs[req.req_id] = req
        self.waiting.append(req)
        self._wake.set()
        return req.req_id

    def submit_prefilled(self, prompt_tokens: list[int], k_stack, v_stack,
                         first_token: int, *, max_tokens: int = 32,
                         temperature: float = 0.0,
                         adapter: str | None = None,
                         spec: bool | None = None) -> int:
        """Queue a request whose prompt KV was ALREADY produced elsewhere
        (a disaggregated prefill worker): admission scatters the adopted
        page stacks (``[L, n_pages, PS, KV, hd]`` arrays, or ``{"q","s"}``
        dicts for int8 pools — the shape ``disagg.adopt_pages`` returns)
        into this engine's pool and starts decoding at position
        ``len(prompt_tokens)`` with ``first_token`` — no prefill dispatch,
        no recompute. The stacks must cover ``ceil(len(prompt)/PS)`` pages
        of a pool with this engine's page_size and kv_dtype."""
        if not self.programs.page_plane:
            raise UnsupportedByModel("submit_prefilled (disagg adoption)",
                                     self.programs.family)
        if self.error is not None:
            raise RuntimeError("engine loop died") from self.error
        if len(self.waiting) >= self.max_waiting:
            raise EngineFull(f"{len(self.waiting)} requests already waiting")
        if len(prompt_tokens) + max_tokens > self.MAXP * self.PS:
            raise ValueError(
                f"prompt ({len(prompt_tokens)}) + max_tokens ({max_tokens}) "
                f"exceeds the engine's max_seq_len ({self.MAXP * self.PS})")
        n_cover = -(-len(prompt_tokens) // self.PS)
        n_got = (k_stack["q"] if isinstance(k_stack, dict)
                 else k_stack).shape[1]
        if n_got < n_cover:
            raise ValueError(
                f"adopted stacks cover {n_got} pages but the prompt "
                f"needs {n_cover}")
        aid = self.lora_index.get(adapter or "__base__")
        if aid is None:
            raise ValueError(f"unknown LoRA adapter {adapter!r} "
                             f"(loaded: {sorted(self.lora_index)})")
        req = _Request(next(self._req_ids), list(prompt_tokens),
                       int(max_tokens), float(temperature), aid,
                       spec=self.spec_enable if spec is None else bool(spec))
        req.prefilled = (k_stack, v_stack, int(first_token))
        return self._enqueue(req)

    def export_pages(self, req_id: int):
        """Page-export hook: seal a LIVE request's prompt KV pages into
        the local shm arena and return their ``KVPageManifest`` — how an
        aggregated engine donates a prefix to the cross-request cache.
        Must be called while the request still holds its slot (prompt
        positions are stable once prefilled; decode writes land past
        them)."""
        from ray_tpu.llm.disagg.kv_plane import ship_pages

        if not self.programs.page_plane:
            raise UnsupportedByModel("export_pages (disagg/kv_plane.py)",
                                     self.programs.family)
        req = self._reqs.get(req_id)
        if req is None or req.slot < 0:
            raise KeyError(f"request {req_id} is not holding a slot")
        n_cover = -(-len(req.prompt) // self.PS)
        page_ids = [int(p) for p in self.page_tables[req.slot, :n_cover]]
        return ship_pages(self.kpool, self.vpool, page_ids, req.prompt,
                          page_size=self.PS, kv_dtype=self.kv_dtype)

    def tokens_in_flight(self) -> int:
        """Decode tokens this engine still owes: remaining scheduled
        tokens of resident requests plus everything waiting — the
        cross-replica batching admission signal (a ring full of
        nearly-done requests drains fast; a shallow queue of long
        generations does not; request COUNTS can't tell them apart)."""
        live = sum(max(0, r.max_tokens - r.emitted)
                   for r in self.slot_req if r is not None and not r.cancelled)
        return live + sum(max(0, r.max_tokens - r.emitted)
                          for r in self.waiting if not r.cancelled)

    def spec_stats(self, drain: bool = False) -> dict:
        """Speculative-decoding counters + the per-block log. With
        ``drain`` the log is consumed (the disagg telemetry's exactly-
        once feed into the tokens_per_step / spec_accept_rate windows);
        without it this is a pure read."""
        blocks = list(self._block_log)
        if drain:
            self._block_log.clear()
        return {"spec_steps": self.spec_steps,
                "spec_proposed": self.spec_proposed,
                "spec_accepted": self.spec_accepted,
                "spec_accept_rate": (self.spec_accepted
                                     / max(1, self.spec_proposed)),
                "blocks": blocks}

    def headroom(self) -> dict:
        """Admission-control snapshot for the disagg scheduler: free KV
        pages and decode slots, queue depth, and the decode
        tokens-in-flight signal."""
        return {"free_pages": len(self.free_pages),
                "free_slots": sum(r is None for r in self.slot_req),
                "waiting": len(self.waiting),
                "tokens_in_flight": self.tokens_in_flight(),
                "n_pages": self.n_pages, "page_size": self.PS,
                "max_batch": self.B, "kv_dtype": self.kv_dtype}

    async def stream(self, req_id: int):
        """Async iterator of generated token ids for one request. Raises
        if the engine died before the request finished. The request stays
        registered until its consumer drains the terminal None here — a
        caller may finish awaiting something else before streaming and the
        already-queued tokens must still be reachable."""
        req = self._reqs.get(req_id)
        if req is None:
            req = self._done[req_id]
        try:
            while True:
                item = await req.out.get()
                if item is None:
                    if self.error is not None and not req.finished:
                        raise RuntimeError("engine loop died") from self.error
                    break
                yield item
        finally:
            # only unregister finished requests: a consumer erroring out
            # mid-stream must not make cancel() a no-op on a live request
            self._done.pop(req_id, None)

    async def stream_blocks(self, req_id: int):
        """Block-coalesced stream: lists of token ids, one per wake.

        ``_emit_block`` pushes a whole fused ``lax.scan`` block's tokens
        into the request queue in one synchronous burst, so draining the
        queue greedily after the first await yields exactly one delta per
        fused decode block (per accepted run in spec mode). This is the
        streaming-serve producer shape: one "G" chunk record per BLOCK on
        the wire instead of one per token — token-identical to
        ``stream()``, at block-granularity overhead."""
        req = self._reqs.get(req_id)
        if req is None:
            req = self._done[req_id]
        try:
            while True:
                item = await req.out.get()
                if item is None:
                    if self.error is not None and not req.finished:
                        raise RuntimeError("engine loop died") from self.error
                    return
                blk = [item]
                while True:
                    try:
                        nxt = req.out.get_nowait()
                    except asyncio.QueueEmpty:
                        break
                    if nxt is None:
                        # terminal already queued behind the block
                        yield blk
                        if self.error is not None and not req.finished:
                            raise RuntimeError(
                                "engine loop died") from self.error
                        return
                    blk.append(nxt)
                yield blk
        finally:
            self._done.pop(req_id, None)

    async def generate(self, prompt_tokens: list[int], **kw) -> list[int]:
        rid = self.submit(prompt_tokens, **kw)
        out: list[int] = []
        # block-granular drain: one loop wake per fused decode block
        # instead of one per token
        async for blk in self.stream_blocks(rid):
            out.extend(blk)
        return out

    def cancel(self, req_id: int):
        req = self._reqs.get(req_id)
        if req is not None:
            req.cancelled = True
            self._wake.set()

    # ------------------------------------------------------------ internals
    def _alloc_pages(self, n: int) -> list[int] | None:
        if len(self.free_pages) < n:
            return None
        out = self.free_pages[:n]
        del self.free_pages[:n]
        return out

    def _free_slot(self, slot: int):
        req = self.slot_req[slot]
        self.slot_req[slot] = None
        # the table holds ALL pages allocated at admission (prompt +
        # max_tokens worth), not just the ones reached — free every entry
        self.free_pages.extend(
            int(p) for p in self.page_tables[slot] if p != 0)
        self.page_tables[slot, :] = 0
        self.seq_lens[slot] = 0
        if req is not None:
            self._finish_stream(req)

    def _finish_stream(self, req: _Request) -> None:
        """Unregister a request and close its token stream: live -> the
        bounded finished-awaiting-drain map (stream() can still reach the
        queued tokens; cancel() only sees live requests; the cap bounds
        leakage from never-streamed submits)."""
        self._reqs.pop(req.req_id, None)
        self._done[req.req_id] = req
        while len(self._done) > self._done_cap:
            self._done.popitem(last=False)
        req.out.put_nowait(None)

    def _reserve_slot(self, req: _Request) -> int | None:
        """Claim a slot + pages for one waiting request (host bookkeeping
        only; the prefill itself is dispatched per wave)."""
        slot = next((i for i, r in enumerate(self.slot_req) if r is None), -1)
        if slot < 0:
            return None
        Tp = len(req.prompt)
        n_need = -(-(Tp + req.max_tokens) // self.PS)
        pages = self._alloc_pages(n_need)
        if pages is None:
            return None
        req.slot = slot
        self.slot_req[slot] = req
        self.page_tables[slot, :] = 0
        self.page_tables[slot, :n_need] = pages
        self.seq_lens[slot] = Tp
        self.temps[slot] = req.temperature
        self.aids[slot] = req.adapter
        if self.spec_enable:
            # drafter context: the prompt (first token lands at
            # _admit_wave emission, generated tokens at spec emission)
            self.hist[slot, :] = 0
            self.hist[slot, :Tp] = req.prompt
        return slot

    async def _call(self, ph, fn, *args):
        """Run one of the jitted programs above, inside the caller's open
        phase ``ph``. Its first use at new shapes compiles — tens of
        seconds at real widths — and the event loop is where the replica
        also answers health probes and streams other requests' tokens. So
        the first use lowers and compiles in a thread, and the call that
        follows finds the executable in jit's own cache. Lowering donates
        nothing: the pools stay readable (``export_pages``) meanwhile.
        Steady state costs one set lookup and never suspends; the compile
        does, so it closes ``ph`` (a phase belongs to its thread), waits
        under ``engine.compile`` and opens ``ph`` again for the call."""
        # what can differ between two calls under one engine: an array's
        # shape (pad and wave buckets) and the static step counts
        key = (fn, *(a if isinstance(a, int) else getattr(a, "shape", None)
                     for a in args[2:]))
        if key not in self._compiled:
            ph.__exit__(None, None, None)
            with tracing.phase("engine.compile", program=fn.__name__,
                               shape=str(key[1:])):
                await asyncio.get_running_loop().run_in_executor(
                    None, lambda: fn.lower(*args).compile())
            self._compiled.add(key)
            ph.__enter__()
        return fn(*args)

    _WAVE_BUCKETS = (1, 2, 4, 8, 16)

    async def _admit_wave(self) -> bool:
        """Admit every waiting request that fits, prefilling each pad
        bucket's group in ONE device dispatch (one host sync per group,
        not per request). Returns True if anything was admitted."""
        groups = await self._admit_dispatch()
        for reqs, first in groups:
            with tracing.phase("engine.prefill_sync", prompts=len(reqs)):
                first = np.asarray(first)  # ONE sync per group
            for j, req in enumerate(reqs):
                self.next_tok[req.slot] = int(first[j])
                if self.spec_enable:
                    self.hist[req.slot, len(req.prompt)] = int(first[j])
                if not req.cancelled:  # cancelled while its bucket compiled
                    self._emit(req, int(first[j]))
        return bool(groups)

    async def _admit_dispatch(self) -> list[tuple[list[_Request], object]]:
        """Reserve slots and DISPATCH batched prefills for every waiting
        request that fits; no host sync — returns [(requests,
        first-token device array)] per pad-bucket group."""
        groups: dict[int, list[_Request]] = {}
        adopted: list[_Request] = []
        with tracing.phase("engine.admit", pad=0, wave=0) as ph:
            while self.waiting:  # slot and page reservation, no device work
                nxt = self.waiting[0]
                if nxt.cancelled:
                    self.waiting.pop(0)
                    self._finish_stream(nxt)
                    continue
                if self._reserve_slot(nxt) is None:
                    break
                self.waiting.pop(0)
                if nxt.prefilled is not None:
                    adopted.append(nxt)
                    continue
                Tp_pad = -(-len(nxt.prompt) // self.PS) * self.PS
                groups.setdefault(Tp_pad, []).append(nxt)
            ph.set(prompts=len(adopted) + sum(map(len, groups.values())))
        out = []
        for req in adopted:
            # slot adoption (llm/disagg): the prompt KV was produced by a
            # prefill worker and fetched via the KV-page plane — scatter
            # it into this pool's freshly allocated pages. Runs at the
            # same admission points as prefill dispatches, so the
            # functional pool update is ordered exactly like one.
            k_stack, v_stack, first = req.prefilled
            req.prefilled = None  # release the host copies after scatter
            n_cover = -(-len(req.prompt) // self.PS)
            rows = self.page_tables[req.slot, :n_cover].copy()
            self.cache = (scatter_pages(self.cache[0], rows, k_stack),
                          scatter_pages(self.cache[1], rows, v_stack))
            req.t_admit = time.perf_counter_ns()
            out.append(([req], np.asarray([first], np.int32)))
        for Tp_pad, reqs in groups.items():
            npages = Tp_pad // self.PS
            nb = next(b for b in self._WAVE_BUCKETS if b >= len(reqs)) \
                if len(reqs) <= self._WAVE_BUCKETS[-1] else len(reqs)
            with tracing.phase("engine.admit", pad=Tp_pad, wave=nb,
                               prompts=len(reqs), **self._last_stats) as ph:
                toks = np.zeros((nb, Tp_pad), np.int32)
                pages = np.zeros((nb, npages), np.int32)  # dummy rows: junk
                aids = np.zeros(nb, np.int32)
                true_lens = np.ones(nb, np.int32)
                temps = np.zeros(nb, np.float32)
                for j, req in enumerate(reqs):
                    toks[j, :len(req.prompt)] = req.prompt
                    pages[j] = self.page_tables[req.slot, :npages]
                    aids[j] = req.adapter
                    true_lens[j] = len(req.prompt)
                    temps[j] = req.temperature
                self._rng, sub = jax.random.split(self._rng)
                first, *cache = await self._call(
                    ph, self.programs.prefill_batch, self.params, self.loras,
                    jnp.asarray(aids), jnp.asarray(toks), jnp.asarray(pages),
                    *self.cache, jnp.asarray(true_lens),
                    jnp.asarray(temps), sub, self.cfg)
                self.cache = tuple(cache)
            now = time.perf_counter_ns()
            for req in reqs:
                req.t_admit = now
            metrics.llm_prefill_waves_total.inc()
            metrics.llm_prefill_prompts_total.inc(len(reqs))
            metrics.llm_prefill_true_tokens_total.inc(
                sum(len(r.prompt) for r in reqs))
            metrics.llm_prefill_padded_tokens_total.inc(nb * Tp_pad)
            out.append((reqs, first))
        return out

    def _emit(self, req: _Request, tok: int):
        req.emitted += 1
        self.tokens_out += 1
        req.out.put_nowait(tok)
        if req.emitted == 1:
            self._stage(req, "engine::queue", req.t_submit, req.t_admit,
                        metrics.llm_queue_wait_seconds, "queue")
            req.t_first = time.perf_counter_ns()
            self._stage(req, "engine::prefill", req.t_admit, req.t_first,
                        metrics.llm_prefill_wait_seconds, "exec")
        if req.emitted >= req.max_tokens or (
                self.eos_id is not None and tok == self.eos_id):
            req.finished = True
            req.cancelled = True  # finished: reclaim on the next sweep
            self._stage(req, "engine::decode", req.t_first,
                        time.perf_counter_ns(), metrics.llm_decode_seconds,
                        "exec")
            metrics.llm_decode_tokens_total.inc(req.emitted - 1)
            if req.slot < 0:
                # planned mode already retired the slot; close the stream
                self._finish_stream(req)

    @staticmethod
    def _stage(req: _Request, name: str, t0: int, t1: int, hist,
               stage: str) -> None:
        """One of a request's three stages, once it is over: into its
        histogram and, if the submitter was inside a sampled span, as a
        retro span under that span (the replica's ``::run``), so the
        request's waterfall reaches down to the engine."""
        hist.observe((t1 - t0) * 1e-9)
        if req.trace is not None:
            from ray_tpu.llm.disagg.telemetry import _span_sink

            sink = _span_sink()
            if sink is not None:
                tracing.emit_retro(
                    name, {"trace_id": req.trace[0],
                           "parent_span_id": req.trace[1]},
                    sink, (t1 - t0) * 1e-9, end_ns=t1, stage=stage)

    async def _loop(self):
        """Engine driver. Any exception here is fatal for the engine:
        record it, fail every live stream, and exit — hung consumers on a
        silently dead loop are the worst failure mode."""
        try:
            await self._loop_inner()
        except BaseException as e:  # noqa: BLE001
            self.error = e
            self._running = False
            self._terminate_all_streams()
            import traceback

            traceback.print_exc()

    @staticmethod
    def _ramp(emitted: int) -> int:
        # per-request fusion ramp: fresh requests decode in small blocks
        # (streaming first-token latency, fast completion of short
        # requests, bounded admission latency for newcomers), deep ones
        # amortize dispatch with bigger ones. Capped at 32 — the ramp only
        # applies at low occupancy, where a 64-block would let a lone
        # generation schedule so far ahead that a newcomer queues behind
        # all of it; the 64 bucket is reserved for full batches.
        if emitted < 8:
            return 8
        if emitted < 24:
            return 16
        return 32

    def _pick_block(self, planned: bool = False) -> int:
        """Fused-steps bucket for this dispatch: the smallest bucket
        covering every active request's ramp, each capped by its exact
        remaining count (no over-decode on final blocks). A request about
        to finish therefore caps the block so it completes — and frees
        its slot for waiting admissions — without riding out a long
        batch's block (continuous-batching latency semantics).

        At high occupancy the ramp is skipped: a full batch is the
        throughput regime, where small early blocks would multiply
        dispatch round trips for no latency benefit (newcomers can't be
        admitted into a full batch anyway).

        ``planned`` counts dispatch-scheduled tokens instead of emitted
        ones (the planned loop runs ahead of emission)."""
        live = [r for r in self.slot_req
                if r is not None and not r.cancelled]
        if not live:
            return 1

        def done_count(r):
            return r.planned if planned else r.emitted

        if 2 * len(live) >= self.B:
            want = min(r.max_tokens - done_count(r) for r in live)
        else:
            want = min(min(self._ramp(done_count(r)),
                           r.max_tokens - done_count(r)) for r in live)
        want = max(1, want)
        for b in self.block_buckets:
            if want <= b:
                return b
        return self.block_buckets[-1]

    async def _dispatch_block(self, carry, planned: bool = False):
        """Pick the block size and dispatch one fused decode block from
        the device-resident ``carry`` (token, length), or from host state
        when it is None. No host sync. Returns (K, tokens [K, B] on the
        device, the next carry)."""
        with tracing.phase("engine.decode_dispatch") as ph:
            K = self._pick_block(planned)
            active = np.array([r is not None for r in self.slot_req])
            ph.set(steps=K, live=int(active.sum()), **self._last_stats,
                   **self._last_kv)
            self._rng, sub = jax.random.split(self._rng)
            # .copy() on every host array that the loops later mutate
            # (page_tables/seq_lens/next_tok/aids/temps): PJRT CPU
            # zero-copies aligned numpy buffers into device arrays, so a
            # retire/emission mutation while the async dispatch is still
            # in flight would corrupt the program's view of them (race
            # observed as garbage decode tokens under load).
            if carry is None:
                carry = (jnp.asarray(self.next_tok.copy()),
                         jnp.asarray(self.seq_lens.copy()))
            tok_d, lens_d = carry
            toks, tok_d, lens_d, *cache = await self._call(
                ph, self.programs.decode_multi, self.params, self.loras,
                jnp.asarray(self.aids.copy()), tok_d, lens_d,
                jnp.asarray(self.page_tables.copy()), *self.cache,
                jnp.asarray(active), jnp.asarray(self.temps.copy()), sub,
                self.cfg, K)
            self.cache = tuple(cache)
        return K, toks, (tok_d, lens_d)

    def _emit_block(self, entry) -> None:
        """Host-side emission of one synced decode block. The read below
        (``engine.block_sync``) blocks the calling thread — the replica's
        event loop — until the device has finished the block: up to 64
        steps, about 0.6 s at the 9 ms a step of the benchmark's chat
        cell. Nothing else of the replica runs meanwhile: no new call
        starts, no delta leaves."""
        K, toks, slot_snapshot = entry
        with tracing.phase("engine.block_sync", steps=K):
            toks = np.asarray(toks)  # [K, B]; blocks until the device is done
        if self.programs.stats:  # [K, B + stats]: the model's sums ride along
            self._observe_stats(toks[:, self.B:])
            toks = toks[:, :self.B]
        self._observe_kv_reads(K, slot_snapshot)
        self.steps += K
        with tracing.phase("engine.emit") as ph:
            before = self.tokens_out
            for i, req in enumerate(slot_snapshot):
                if req is None:
                    continue
                if self.slot_req[i] is req:
                    # planned mode may have retired + re-admitted this
                    # slot while the block was in flight; host per-slot
                    # state then belongs to the newcomer
                    self.seq_lens[i] += K
                for k in range(K):
                    if req.cancelled:
                        break  # finished/cancelled mid-block: discard rest
                    tok = int(toks[k, i])
                    if self.slot_req[i] is req:
                        self.next_tok[i] = tok
                    self._emit(req, tok)
            ph.set(tokens=self.tokens_out - before)

    def _observe_kv_reads(self, K: int, slot_snapshot) -> None:
        """What a synced block's attention attended and what it fetched
        for that (``rt_llm_decode_kv_tokens_{live,read}_total``), reckoned
        from each snapshot slot's length at the block's start: step k of a
        slot attends ``start + k + 1`` positions. A slot the planned loop
        has already handed on has its start from the request itself."""
        starts = np.array(
            [self.seq_lens[i] if self.slot_req[i] is req
             else len(req.prompt) + req.emitted - 1
             for i, req in enumerate(slot_snapshot) if req is not None],
            np.int64)
        lens = starts[:, None] + np.arange(1, K + 1)  # [live slots, K]
        live = int(lens.sum())
        if self._kv_in_place:  # whole pages, at most the whole table
            pages = -(-np.minimum(lens, self.MAXP * self.PS) // self.PS)
            read = int(pages.sum()) * self.PS
        else:
            read = K * self.B * self.MAXP * self.PS
        metrics.llm_decode_kv_tokens_live_total.inc(live)
        metrics.llm_decode_kv_tokens_read_total.inc(read)
        self._last_kv = {"kv_live": round(live / K, 2),
                         "kv_read": round(read / K, 2)}

    def _observe_stats(self, rows) -> None:
        """A synced block's per-step sums of the model's programs
        (``ServePrograms.stats``, [K, n] int32) into the ``rt_llm_*_total``
        counter of each name, and as means per step for the next phases'
        annotations."""
        for name, total in zip(self.programs.stats, rows.sum(axis=0)):
            metrics.LLM_MODEL_STATS[name].inc(int(total))
            self._last_stats[name] = round(float(total) / rows.shape[0], 2)

    def _sweep(self) -> None:
        """Give back the slot and pages of every finished or cancelled
        request (only with no block in flight: its writes would land on
        pages handed to someone else)."""
        with tracing.phase("engine.free") as ph:
            freed = 0
            for i, req in enumerate(self.slot_req):
                if req is not None and req.cancelled:
                    self._free_slot(i)
                    freed += 1
            ph.set(freed=freed)

    async def _yield(self) -> None:
        """One turn of the event loop for everything else the replica
        does — stream consumers pushing their deltas, new calls starting,
        stats and health probes. Like ``engine.idle`` a phase that spans
        a suspending await: nothing of this engine opens one meanwhile."""
        with tracing.phase("engine.yield"):
            await asyncio.sleep(0)

    async def _idle(self, timeout: float = 1.0) -> None:
        """No slot is live: sleep until a submit, a cancel or stop()
        wakes the loop."""
        with tracing.phase("engine.idle"):
            self._wake.clear()
            try:
                await asyncio.wait_for(self._wake.wait(), timeout=timeout)
            except asyncio.TimeoutError:
                pass

    async def _loop_inner(self):
        if self.spec_enable:
            # accepted counts are data-dependent: completion steps are
            # unknowable at dispatch, so spec mode always drives the
            # reactive-shaped loop (planned mode needs a schedule)
            await self._loop_spec()
        elif self.eos_id is None:
            await self._loop_planned()
        else:
            await self._loop_reactive()

    async def _loop_planned(self):
        """Fully pipelined driver for length-deterministic generation
        (no EOS): every request's completion step is known at dispatch
        time, so slots are retired and re-admitted ON SCHEDULE without
        ever draining the pipeline — prefills, carry merges and decode
        blocks stream to the device back to back, and the only host syncs
        are the trailing token emissions riding two blocks behind."""
        pending: list = []  # dispatch-ordered: ("prefill",...)|("block",...)
        carry = None

        def sync_oldest():
            kind, *rest = pending.pop(0)
            if kind == "prefill":
                reqs, first = rest
                with tracing.phase("engine.prefill_sync", prompts=len(reqs)):
                    first = np.asarray(first)
                for j, req in enumerate(reqs):
                    if not req.cancelled:  # user-cancelled: stream closed
                        self._emit(req, int(first[j]))
            else:
                self._emit_block(rest)

        while self._running:
            # retire slots whose scheduled tokens are all dispatched; their
            # in-flight junk writes land on pages ordered BEFORE any new
            # prefill, so immediate reuse is safe (see paged_decode_multi)
            with tracing.phase("engine.free") as ph:
                freed = 0
                for i, req in enumerate(self.slot_req):
                    if req is not None and (req.planned >= req.max_tokens
                                            or req.cancelled):
                        req.slot = -1  # emission closes the stream at finish
                        self.slot_req[i] = None
                        self.free_pages.extend(
                            int(p) for p in self.page_tables[i] if p != 0)
                        self.page_tables[i, :] = 0
                        self.seq_lens[i] = 0
                        freed += 1
                        if req.cancelled and not req.finished:
                            # user-cancelled: no finish emission will ever
                            # close this stream — close it here
                            self._finish_stream(req)
                ph.set(freed=freed)
            if self.waiting and any(r is None for r in self.slot_req):
                groups = await self._admit_dispatch()
                if groups:
                    if carry is None:
                        carry = (jnp.asarray(self.next_tok.copy()),
                                 jnp.asarray(self.seq_lens.copy()))
                    tok_d, lens_d = carry
                    for reqs, first in groups:
                        slots = jnp.asarray([r.slot for r in reqs],
                                            jnp.int32)
                        lens = jnp.asarray(
                            [len(r.prompt) for r in reqs], jnp.int32)
                        # device-side carry merge: no host sync
                        tok_d = tok_d.at[slots].set(first[:len(reqs)])
                        lens_d = lens_d.at[slots].set(lens)
                        for r in reqs:
                            r.planned = 1
                        pending.append(("prefill", reqs, first))
                    carry = (tok_d, lens_d)
            live = [r for r in self.slot_req if r is not None]
            if not live:
                while pending:
                    sync_oldest()
                    # yield between blocks: consumers must observe tokens
                    # in emission order, not one burst after the drain
                    await self._yield()
                carry = None
                await self._idle()
                continue
            # pace dispatch to emission + 2 entries: enough run-ahead to
            # hide the dispatch round trip under device compute, little
            # enough that a newly arriving request interleaves within a
            # couple of blocks instead of queueing behind a whole
            # pre-scheduled generation. Yield right after each sync so
            # consumers see tokens before the next dispatch (whose first
            # use may compile) occupies the loop thread.
            while len(pending) >= 2:
                sync_oldest()
                await self._yield()
            K, toks, carry = await self._dispatch_block(carry, planned=True)
            for r in live:
                r.planned = min(r.max_tokens, r.planned + K)
            pending.append(("block", K, toks, list(self.slot_req)))
            await self._yield()

    async def _loop_reactive(self):
        # pipeline of dispatched-but-unsynced decode blocks. Depth 2:
        # block N+1 is enqueued before block N's tokens come back, so the
        # host round trip rides under device compute. The (tok, pos)
        # carry chains ON DEVICE between pipelined blocks; it is rebuilt
        # from host state only after the pipeline drains at admission
        # points (a new slot changes page_tables/active for the next
        # dispatch).
        # The loop holds the event loop's thread nearly all the time:
        # every _emit_block sleeps in np.asarray (phase engine.block_sync)
        # until the device has finished a block of up to 64 steps (about
        # 0.6 s at 9 ms a step), and only the _yield() at the bottom and
        # the one after a wave let the replica's other coroutines run.
        pending: list = []
        carry = None  # (tok_dev, lens_dev) device-resident between blocks

        def drain():
            while pending:
                self._emit_block(pending.pop(0))

        while self._running:
            if not pending:  # free only with no block in flight
                self._sweep()
            if self.waiting and any(r is None for r in self.slot_req):
                drain()  # admission changes device-visible state
                self._sweep()
                if await self._admit_wave():
                    carry = None
                    # the wave just emitted each admitted request's
                    # prefill token: let consumers flush it (TTFC) before
                    # the next decode dispatch occupies the loop thread
                    await self._yield()
            if all(r is None for r in self.slot_req):
                drain()
                # idle, OR the head-of-queue request can't be admitted yet
                # (pages still held elsewhere): either way we must yield —
                # a bare continue would spin the loop without ever
                # letting consumers/stop() run
                await self._idle()
                continue
            K, toks, carry = await self._dispatch_block(carry)
            pending.append((K, toks, list(self.slot_req)))
            if len(pending) >= 2:
                self._emit_block(pending.pop(0))
            # a finished request must stop the pipeline at the next
            # admission point rather than over-decoding forever
            if any(r is not None and r.cancelled for r in self.slot_req):
                drain()
                carry = None
            # hand the loop to consumers/admitters every block
            await self._yield()

    # ------------------------------------------------------- speculative loop
    _SPEC_BUCKETS = (1, 2, 4)

    def _spec_inflight_steps(self, pending) -> list[int]:
        """Per-slot spec steps already dispatched but not yet synced."""
        steps = [0] * self.B
        for entry in pending:
            S, snap = entry[0], entry[4]
            for i, rq in enumerate(snap):
                if rq is not None and self.slot_req[i] is rq:
                    steps[i] += S
        return steps

    def _pick_spec_block(self, deficits: list[int]) -> int:
        """Fused spec-steps bucket: sized to the smallest GUARANTEED
        remaining need (each step advances >= 1 token), so a finishing
        request frees its slot without riding out a long block. Buckets
        stop at 4: a spec step can emit up to k+1 tokens, and the
        optimistic dispatch gate stops issuing blocks once in-flight
        steps COULD satisfy every request — a coarser bucket would turn
        that possibility into up to a whole wasted block of verifies."""
        want = max(1, min(deficits))
        for b in self._SPEC_BUCKETS:
            if want <= b:
                return b
        return self._SPEC_BUCKETS[-1]

    def _host_drafts(self, spec_ok):
        """Drafter-hook path: ask ``spec_drafter(context, pos, k)`` for
        up to k draft tokens per live greedy slot. ``context`` is the
        slot's token history through the pending input (a numpy view),
        ``pos`` its length minus one — the small-model-on-TPU hook rides
        here."""
        k = self.spec_k
        drafts = np.zeros((self.B, k), np.int32)
        dlens = np.zeros(self.B, np.int32)
        for i, req in enumerate(self.slot_req):
            if req is None or not spec_ok[i]:
                continue
            n = int(self.seq_lens[i])
            got = list(self.spec_drafter(self.hist[i, :n + 1], n, k))[:k]
            drafts[i, :len(got)] = got
            dlens[i] = len(got)
        return drafts, dlens

    def _emit_spec_block(self, entry) -> None:
        """Host-side emission of one synced speculative block: per step
        and slot, emit the first ``n_emit`` candidate tokens (the
        accepted drafts plus the target's correction/bonus token) and
        discard the rest — the rejected tail's rollback is exactly this
        truncation plus the seq_lens arithmetic (the junk KV those
        positions hold is overwritten when they are legitimately
        decoded)."""
        S, toks, n_emit, n_prop, snapshot, spec_snap = entry
        with tracing.phase("engine.block_sync", steps=S):
            toks = np.asarray(toks)      # [S, B, k+1]; ONE sync per block
            n_emit = np.asarray(n_emit)  # [S, B]
            n_prop = np.asarray(n_prop)
        self.steps += S
        self.spec_steps += S
        emitted = proposed = accepted = 0
        H = self.hist.shape[1]
        with tracing.phase("engine.emit") as ph:
            for s in range(S):
                for i, req in enumerate(snapshot):
                    if req is None:
                        continue
                    ne = int(n_emit[s, i])
                    if ne <= 0:
                        continue
                    live = self.slot_req[i] is req
                    if live:
                        base = int(self.seq_lens[i])
                        self.seq_lens[i] += ne
                    if spec_snap[i] and not req.cancelled:
                        proposed += int(n_prop[s, i])
                        accepted += ne - 1
                    for j in range(ne):
                        if req.cancelled:
                            break  # finished/cancelled mid-block: discard
                        tok = int(toks[s, i, j])
                        if live:
                            self.next_tok[i] = tok
                            if base + j + 1 < H:
                                self.hist[i, base + j + 1] = tok
                        emitted += 1
                        self._emit(req, tok)
            ph.set(tokens=emitted)
        self.spec_proposed += proposed
        self.spec_accepted += accepted
        self._block_log.append((S, emitted, proposed, accepted))

    async def _loop_spec(self):
        """Speculative driver (reactive shape, README § Speculative
        decoding): with the on-device n-gram drafter the whole
        draft→verify→accept cycle lives inside ``paged_decode_spec``'s
        scan, the (token, position, history) carry chains on device, and
        blocks pipeline 2-deep exactly like ``_loop_reactive``. With a
        host ``spec_drafter`` hook each dispatch is one verify step and
        syncs immediately — the drafter needs the accepted tokens before
        it can propose the next window."""
        pending: list = []
        carry = None  # (tok_dev, lens_dev, hist_dev) between blocks
        # device uploads of the per-slot tables (page_tables/aids/temps/
        # active/spec_ok): these only change at admission/free points,
        # exactly where carry resets — hoisting them out of the dispatch
        # keeps the per-block host cost at one RNG split + one append
        # (spec blocks are smaller than plain blocks, so per-dispatch
        # overhead multiplies faster here)
        statics = None
        k = self.spec_k
        host_draft = callable(self.spec_drafter)

        def drain():
            while pending:
                self._emit_spec_block(pending.pop(0))

        while self._running:
            if not pending:  # free only with no block in flight
                self._sweep()
            if self.waiting and any(r is None for r in self.slot_req):
                drain()  # admission changes device-visible state
                self._sweep()
                if await self._admit_wave():
                    carry = None
                    # flush the just-emitted prefill tokens (TTFC) before
                    # the next spec dispatch occupies the loop thread
                    await self._yield()
            active = np.array([r is not None for r in self.slot_req])
            if not active.any():
                drain()
                carry = None
                await self._idle()
                continue
            # optimistic dispatch gate: a spec step emits 1..k+1 tokens,
            # so in-flight blocks COULD have satisfied a request long
            # before the 1-token lower bound says so. Once every live
            # request's optimistic bound (emitted + (k+1) x in-flight
            # steps) covers its budget, SYNC the oldest block instead of
            # dispatching — at high accept rates this is what keeps the
            # loop from verifying junk a finished request will discard;
            # when acceptance was actually low the sync corrects the
            # bound from real emissions and dispatch resumes.
            inflight = self._spec_inflight_steps(pending)
            deficits = [r.max_tokens - r.emitted - (k + 1) * inflight[i]
                        for i, r in enumerate(self.slot_req)
                        if r is not None and not r.cancelled]
            if not deficits or max(deficits) <= 0:
                if pending:
                    self._emit_spec_block(pending.pop(0))
                else:
                    await self._idle(timeout=0.05)
                if any(r is not None and r.cancelled
                       for r in self.slot_req):
                    drain()
                    carry = None
                await self._yield()
                continue
            with tracing.phase("engine.decode_dispatch",
                               live=int(active.sum())) as ph:
                self._rng, sub = jax.random.split(self._rng)
                if carry is None:
                    carry = (jnp.asarray(self.next_tok.copy()),
                             jnp.asarray(self.seq_lens.copy()),
                             jnp.asarray(self.hist.copy()))
                    statics = None
                if statics is None:
                    spec_ok = np.array([
                        r is not None and not r.cancelled and r.spec
                        and r.temperature <= 0 for r in self.slot_req])
                    statics = (jnp.asarray(self.aids.copy()),
                               jnp.asarray(self.page_tables.copy()),
                               jnp.asarray(active),
                               jnp.asarray(spec_ok),
                               jnp.asarray(self.temps.copy()),
                               spec_ok)
                aids_d, pt_d, act_d, sok_d, tmp_d, spec_ok = statics
                tok_d, lens_d, hist_d = carry
                if host_draft:
                    drafts, dlens = self._host_drafts(spec_ok)
                    ph.set(steps=1)
                    (toks, n_emit, n_prop, tok_d, lens_d,
                     *cache) = await self._call(
                        ph, self.programs.decode_verify, self.params,
                        self.loras, aids_d, tok_d, lens_d,
                        jnp.asarray(drafts), pt_d, *self.cache,
                        jnp.asarray(dlens), act_d, tmp_d, sub, self.cfg, k)
                else:
                    S = self._pick_spec_block([d for d in deficits if d > 0])
                    ph.set(steps=S)
                    if chaos.ENABLED:
                        # "llm.spec_block": fires once per fused
                        # speculative block — a seeded kill here dies
                        # MID-speculative-window (accepted-but-unsynced
                        # tokens in flight), the recovery window
                        # tests/plans/spec_decode_kill exercises
                        chaos.point("llm.spec_block", steps=S, k=k)
                    (toks, n_emit, n_prop, tok_d, lens_d, hist_d,
                     *cache) = await self._call(
                        ph, self.programs.decode_spec, self.params,
                        self.loras, aids_d, tok_d, lens_d, hist_d, pt_d,
                        *self.cache, act_d, sok_d, tmp_d, sub, self.cfg, S,
                        k, self.spec_ngram)
                self.cache = tuple(cache)
            if host_draft:
                self._emit_spec_block((1, toks[None], n_emit[None],
                                       n_prop[None], list(self.slot_req),
                                       spec_ok))
                carry = None  # host state is authoritative per step
            else:
                carry = (tok_d, lens_d, hist_d)
                pending.append((S, toks, n_emit, n_prop,
                                list(self.slot_req), spec_ok))
                if len(pending) >= 2:
                    self._emit_spec_block(pending.pop(0))
            if any(r is not None and r.cancelled for r in self.slot_req):
                drain()
                carry = None
            await self._yield()

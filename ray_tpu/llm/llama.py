"""The serving programs of ``models/llama.py`` for the continuous-batching
engine, beside the other family's (``llm/mla_moe.py``). Imports the seam
(``llm/programs.py``), ``models/`` and ``ops/``; never the engine.

* **The cache is a K pool and a V pool** ``[L, P, PS, KV, hd]``, plain
  arrays or int8 ``{"q", "s"}`` dicts (``make_kv_pools``); ``_kv_write``,
  ``_kv_read`` and ``scatter_pages`` alone know which.
* **Every program's loop** is ``llama_project`` -> write K and V -> attend
  -> ``llama_attn_out`` -> ``llama_ffn``, on the tree as it is handed in: the
  engine's lies in the serving layout (``PROGRAMS.prepare``: wq|wk|wv and
  w_gate|w_up joined once, when it takes the tree), any other caller's as
  ``llama_init`` made it. The middle is the program's own:
  fresh K and V under a causal mask (``paged_prefill_batch``: blocked,
  ``ops/prefill_attention.py``, no ``[T, T]`` array, where the seam's rule
  and the shapes allow — a TPU, a pad of whole blocks, a head of whole lane
  tiles — and ``_gqa_attn`` over the whole square elsewhere), the
  table-ordered window (``paged_prefill_suffix``, speculative verify), the
  pool in place or the gathered window as ``_walks`` sees (decode).
  In place, the walk takes table entries that lie one after the other in the
  pool as ONE copy; which do is the table's alone, so ``paged_decode_multi``
  finds it once (``run_lengths``, before the scan over its steps) and every
  layer of every step hands it to the kernel.
* **LoRA multiplex** (ref: serve/multiplex.py): stacked low-rank adapters on
  q and v, selected per slot (``make_lora_stack``; adapter 0 = base model).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.llm.programs import (
    ServePrograms, _sample_tail, decode_frame, last_rows, reads_in_place)
from ray_tpu.models.llama import (
    LlamaConfig, llama_attn_out, llama_ffn, llama_init, llama_project,
    llama_serving_layout)
from ray_tpu.ops.basic import rms_norm, rope_freqs
from ray_tpu.ops.paged_attention import paged_decode_attention, run_lengths
from ray_tpu.ops.prefill_attention import blocks_for, gqa_prefill_attention
from ray_tpu.utils import tracing

# The seam's platform rule under this module's own name, asked through this
# global by every program here and by ``PROGRAMS.decode_in_place``: ``tests/``
# ASSIGN an answer here to run the kernels interpreted.
_reads_in_place = reads_in_place


@tracing.part("attention")
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


def _kv_shape(pool):
    return (pool["q"] if isinstance(pool, dict) else pool).shape


@tracing.part("kv_write")
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


@tracing.part("attention")
def _kv_read(pool, i, page_tables, dtype):
    """Gather an attention window ``[B, MAXP * PS, KV, hd]``: every page of
    every slot's table, live or not — a slice of the layer's pool, the
    gather itself, then one read by each contraction of ``_gqa_attn``.
    What still reads the pool this way: several query rows a slot (suffix
    prefill, speculative decode and verify), int8 pools, and the decode
    step wherever ``_walks`` says no. On the chip the decode step
    of a plain pool does not (``ops/paged_attention.py``): there this
    window cost half the device's time for a tenth of it live (PERF.md
    section 6, PR 28), and this function with ``_gqa_attn`` is the plain
    reference that kernel is tested against. int8 pools move HALF the HBM
    bytes of bf16 through it; the scale gather is hd-times smaller —
    noise."""
    def window(a):  # [L, P, PS, ...] -> [B, MAXP * PS, ...]
        w = a[i][page_tables]
        return w.reshape(w.shape[0], -1, *w.shape[3:])

    if not isinstance(pool, dict):
        return window(pool)
    return window(pool["q"]).astype(dtype) * window(pool["s"]).astype(dtype)[..., None]


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


def _pool_walkable(pool) -> bool:
    """What this family adds to the seam's rule, where the pool's layout is
    known: whether the kernel can walk THIS pool at all. Not an int8 pool
    (the kernel does not dequantise); not a single KV head under 32 bits,
    whose one-row page slice Mosaic refuses (tiling (2, 128)); not a head
    that is not whole lane tiles, whose rows lie padded in HBM (no run of
    pages is a run of rows there)."""
    return (not isinstance(pool, dict) and pool.shape[-1] % 128 == 0
            and (pool.shape[3] > 1 or pool.dtype.itemsize >= 4))


def _walks(pool) -> bool:
    """Whether the decode step's attention reads this pool where it lies
    (``paged_decode_attention``: only the pages that hold tokens) or through
    ``_kv_read``'s gathered window: the seam's rule, and a pool the kernel
    can walk."""
    return _reads_in_place() and _pool_walkable(pool)


def _decode_body(params, tokens, pos, page_tables, cache, active, temps, key,
                 cfg: LlamaConfig, runs, loras, aids):
    """One decode step for every slot (masked where inactive).

    tokens: [B] current input token; pos: [B] tokens already cached (the
    new token lands at that position); page_tables: [B, MAXP]; aids: [B]
    adapter ids; temps: [B]. Returns (next_tok [B], (kpool, vpool), None:
    the family has no stats of its own).
    Pools are either plain [L, P, PS, KV, hd] arrays (cfg dtype) or int8
    quantized dicts (see _kv_write) — the engine's kv_dtype option.

    Every layer writes the new row into the pools, then attends the
    slot's ``pos + 1`` positions: in place, page by page through the table
    (``paged_decode_attention``; an inactive slot attends nothing) where
    ``_walks`` holds, else over ``_kv_read``'s whole window with
    the positions past ``pos`` masked. ``runs``: the table's ``run_lengths``
    — which entries lie one after the other in the pool, so that the walk
    takes them as one copy — made by the program once for all its steps and
    layers (None where the kernel does not run)."""
    kpool, vpool = cache
    PS = _kv_shape(kpool)[2]
    MAXP = page_tables.shape[1]
    cos, sin = rope_freqs(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    positions = pos[:, None]
    row = jnp.take_along_axis(page_tables, (pos // PS)[:, None], axis=1)[:, 0]
    off = pos % PS
    in_place = _walks(kpool)
    if in_place:
        lengths = jnp.where(active, pos + 1, 0)
    else:
        key_idx = jnp.arange(MAXP * PS)
        mask = key_idx[None, None, :] <= pos[:, None, None]
    with tracing.part("embed"):
        x = params["tok"]["embedding"][tokens][:, None, :]
    for i in range(cfg.n_layers):
        layer = params[f"layers_{i}"]
        q, k, v = llama_project(layer, x, cos, sin, positions, cfg,
                                loras=loras, aids=aids)
        kpool = _kv_write(kpool, i, row, off, k[:, 0])
        vpool = _kv_write(vpool, i, row, off, v[:, 0])
        if in_place:
            with tracing.part("attention"):
                att = paged_decode_attention(
                    q[:, 0], kpool, vpool, i, page_tables, lengths, runs=runs)
        else:
            kb = _kv_read(kpool, i, page_tables, k.dtype)
            vb = _kv_read(vpool, i, page_tables, v.dtype)
            att = _gqa_attn(q, kb, vb, mask)
        x = llama_ffn(layer, llama_attn_out(layer, x, att))
    with tracing.part("head"):
        x = rms_norm(x, params["norm"]["scale"])
        logits = x[:, 0] @ params["lm_head"]["kernel"]
    next_tok = _sample_tail(logits, temps, key)
    return jnp.where(active, next_tok, 0), (kpool, vpool), None


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
    # the table is the program's: its runs are found once, not a layer a step
    runs = run_lengths(page_tables) if _walks(kpool) else None
    return decode_frame(_decode_body, params, tokens, seq_lens, page_tables,
                        (kpool, vpool), active, temps, key, cfg, n_steps,
                        runs, loras, aids)


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
    PS = _kv_shape(kpool)[2]
    cos, sin = rope_freqs(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    positions = jnp.arange(Tp)[None, :]
    idx = jnp.arange(Tp)
    # the other families' rule, and a head of whole lane tiles (the kernel
    # slices a KV head's query heads out of the lanes)
    blocked = (_reads_in_place() and blocks_for(Tp) is not None
               and cfg.head_dim % 128 == 0)
    mask = None if blocked else idx[None, :, None] >= idx[None, None, :]
    rows = pages[:, idx // PS]  # [N, Tp] pool row per prompt position
    offs = jnp.broadcast_to(idx % PS, (N, Tp))
    with tracing.part("embed"):
        x = params["tok"]["embedding"][tokens]  # [N, Tp, D]
    for i in range(cfg.n_layers):
        layer = params[f"layers_{i}"]
        q, k, v = llama_project(layer, x, cos, sin, positions, cfg,
                                loras=loras, aids=aids)
        kpool = _kv_write(kpool, i, rows, offs, k)
        vpool = _kv_write(vpool, i, rows, offs, v)
        # prefill attends the FRESH k/v: quantization only affects what
        # later decode steps read back
        if blocked:
            with tracing.part("attention"):
                att = gqa_prefill_attention(
                    q.reshape(N, Tp, -1), k.reshape(N, Tp, -1),
                    v.reshape(N, Tp, -1),
                    n_kv_heads=cfg.n_kv_heads).reshape(q.shape)
        else:
            att = _gqa_attn(q, k, v, mask)
        x = llama_ffn(layer, llama_attn_out(layer, x, att))
    with tracing.part("head"):
        x = rms_norm(x, params["norm"]["scale"])
        logits = last_rows(x, true_lens) @ params["lm_head"]["kernel"]  # [N, V]
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
    Ts = tokens.shape[1]
    PS = _kv_shape(kpool)[2]
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
    with tracing.part("embed"):
        x = params["tok"]["embedding"][tokens]
    for i in range(cfg.n_layers):
        layer = params[f"layers_{i}"]
        q, k, v = llama_project(layer, x, cos, sin, positions, cfg,
                                loras=loras, aids=aids)
        kpool = _kv_write(kpool, i, rows, offs, k)
        vpool = _kv_write(vpool, i, rows, offs, v)
        kb = _kv_read(kpool, i, pages, k.dtype)
        vb = _kv_read(vpool, i, pages, v.dtype)
        att = _gqa_attn(q, kb, vb, mask)
        x = llama_ffn(layer, llama_attn_out(layer, x, att))
    with tracing.part("head"):
        x = rms_norm(x, params["norm"]["scale"])
        logits = last_rows(x, true_lens) @ params["lm_head"]["kernel"]
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
    PS = _kv_shape(kpool)[2]
    MAXP = page_tables.shape[1]
    cos, sin = rope_freqs(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    rows = jnp.take_along_axis(page_tables, positions // PS, axis=1)
    offs = positions % PS
    key_idx = jnp.arange(MAXP * PS)
    mask = key_idx[None, None, :] <= positions[:, :, None]  # [B,T,MAXP*PS]
    with tracing.part("embed"):
        x = params["tok"]["embedding"][inputs]  # [B, T, D]
    for i in range(cfg.n_layers):
        layer = params[f"layers_{i}"]
        q, k, v = llama_project(layer, x, cos, sin, positions, cfg,
                                loras=loras, aids=aids)
        kpool = _kv_write(kpool, i, rows, offs, k)
        vpool = _kv_write(vpool, i, rows, offs, v)
        kb = _kv_read(kpool, i, page_tables, k.dtype)
        vb = _kv_read(vpool, i, page_tables, v.dtype)
        att = _gqa_attn(q, kb, vb, mask)
        x = llama_ffn(layer, llama_attn_out(layer, x, att))
    with tracing.part("head"):
        x = rms_norm(x, params["norm"]["scale"])
        logits = x @ params["lm_head"]["kernel"]  # [B, T, V]
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return greedy, _sample_tail(logits[:, 0], temps, key), kpool, vpool


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
    if kv_dtype not in (None, "native", "bf16"):
        raise ValueError(f"unknown kv_dtype {kv_dtype!r}")
    # "bf16": an explicit half-precision cache, regardless of cfg.dtype
    kpool = jnp.zeros(pool_shape, jnp.bfloat16 if kv_dtype == "bf16" else dtype)
    return kpool, jnp.zeros_like(kpool)

PROGRAMS = ServePrograms(
    family="llama", make_cache=make_kv_pools,
    decode_multi=paged_decode_multi, prefill_batch=paged_prefill_batch,
    init=llama_init, decode_in_place=lambda cache: _walks(cache[0]),
    prefill_suffix=paged_prefill_suffix, decode_spec=paged_decode_spec,
    decode_verify=paged_decode_verify, lora=make_lora_stack, int8_cache=True,
    page_plane=True, prepare=llama_serving_layout)

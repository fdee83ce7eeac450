"""The serving programs of ``models/sparse_moe.py`` for the continuous-
batching engine: same slots, blocks and loop as the other families, a cache
of THREE pools on one kind of page and an attention that picks its keys.

* **Three pools, one kind of page.** Keys and values ``[L, P, PS, KV, hd]``
  as the Llama family's, and the indexer's keys beside them, packed two to a
  128-lane row (``ops/paged_indexer.py``: ``[L, P, PS . dk / 128, 128]``). A
  slot's one page table indexes all three, so the engine draws, frees and
  counts pages as it does for every family and never learns of the third.
* **Decode** scores every cached position of a slot with the indexer, picks
  the ``topk`` largest (``ops/select.py``: exact, ties to the lower position)
  and attends the picked rows of K and V. On a TPU the three are a kernel
  each and both ends read the pools where they lie (``paged_index_scores``,
  ``topk_prefix_mask`` — whose passes stop at the longest live slot, not at
  the table's width — and ``paged_decode_attention``'s ``selected``);
  anywhere else, where the kernels would be interpreted, the
  gathered table in the plain form — the kernels' reference and what the CPU
  tests run (the seam's rule, bound here as ``_reads_in_place``: decided by
  what the code can see). **Walked, not gathered:** the attention fetches
  every live page and masks the rows not picked. 2,048 picked rows x 2 pools x 32 slots
  x 12 layers would be 1.6 M copies of 1 KB a step, and under seeded weights
  the picks are scattered (at 9k positions 97 % of the 16-token pages hold
  one), so skipping pages buys nothing; the walk is exact, and what it
  fetches beyond the picks is on the record (``sparse_kv_fetched``).
  **Runs found once a program.** Both walks take a block of the table whose
  pages lie one after the other in the pool as ONE copy. Which blocks those
  are is the table's alone, and the table does not change inside a decode
  program: ``_table_runs`` finds them before the scan over the steps, the
  kernels scalar-prefetch them, and what is left to a step is whether the
  block's pages hold tokens yet (``sparse_walk_blocks`` /
  ``sparse_walk_run_blocks``: how often an allocator's tables let it be).
* **Prefill** is whole-prompt per pad bucket. The picks of a layer are ONE
  kernel on a TPU (``ops/prefill_picks.py``): a tile of 128 queries is scored
  against the keys it can see — column blocks up to its last position, the
  triangle and not the square — selected with the scores still in VMEM, and
  leaves one byte a (query, key) pair; the tiles under ``topk`` pick all
  they see and are neither scored nor selected. The blocked kernel
  (``ops/prefill_attention.py``) attends each query's own picks — no float
  ``[T, T]`` array, no block of float scores in HBM either. Off the TPU, and
  for a prompt that is not whole tiles, the plain form (``_prefill_picks``):
  ``q_chunk`` queries at a time against all the prompt's keys. A wave holds
  at most ``WAVE_LIMIT`` prompts and tokens.
* **The expert layer** routes over all experts (softmax, top-k) and computes
  the held ones' part; with no shared expert, holders' parts add up to the
  layer. ``MOE_STATS`` and seven sums of the attention's own ride back with
  the tokens: positions scored, rows in the selected sets, K/V positions
  fetched, blocks the two walks made and those of them that were one copy,
  table columns the selection's passes walked (``ops/select.py``: up to the
  longest live slot of a tile of slots) and the width they are a share of.

LoRA, int8 pools, speculative decoding, suffix prefill and page export are
the Llama family's programs; ``llm/engine.py`` refuses them for this family
by name.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.llm.programs import (
    MOE_STATS, ServePrograms, _sample_tail, decode_frame, last_rows,
    moe_load_stats, reads_in_place)
from ray_tpu.models.sparse_moe import (
    SparseMoeConfig, indexer_scores, sparse_attn_out, sparse_experts,
    sparse_index, sparse_logits, sparse_moe_init, sparse_project,
    sparse_rope_freqs, sparse_select)
from ray_tpu.ops.attention import masked_attention
from ray_tpu.ops.basic import rms_norm
from ray_tpu.ops.paged_attention import (
    kv_block, paged_decode_attention, run_lengths, walk_copies)
from ray_tpu.ops.paged_indexer import (
    index_runs, keys_per_row, pack_keys, paged_index_scores, unpack_keys)
from ray_tpu.ops.prefill_attention import blocks_for, gqa_prefill_attention
from ray_tpu.ops.prefill_picks import picks_block, prefill_picks
from ray_tpu.ops.select import prefix_walked
from ray_tpu.utils import tracing

# the most prompts and tokens one prefill program may hold
WAVE_LIMIT = (8, 16384)
# a decode step's own sums, after MOE_STATS, each over layers and live slots
SPARSE_STATS = ("sparse_scored", "sparse_attended", "sparse_kv_fetched",
                "sparse_walk_blocks", "sparse_walk_run_blocks",
                "sparse_select_walked", "sparse_select_width")
# The seam's platform rule under this module's own name, asked through this
# global by every program here and by ``PROGRAMS.decode_in_place``: ``tests/``
# ASSIGN an answer here to run the kernels interpreted.
_reads_in_place = reads_in_place


def make_pools(cfg: SparseMoeConfig, page_size: int, n_pages: int, kv_dtype):
    """The model's cache: (K, V, the indexer's keys packed)."""
    if kv_dtype not in (None, "native", "bf16"):
        raise ValueError(f"unknown kv_dtype {kv_dtype!r}")
    dtype = jnp.bfloat16 if kv_dtype == "bf16" else jnp.dtype(cfg.dtype)
    per = keys_per_row(cfg.indexer_head_dim, page_size)
    kv = (cfg.n_layers, n_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
    return (jnp.zeros(kv, dtype), jnp.zeros(kv, dtype),
            jnp.zeros((cfg.n_layers, n_pages, page_size // per,
                       per * cfg.indexer_head_dim), dtype))


def _table_runs(tables, kpool):
    """The runs of a program's page tables, for the indexer's walk —
    (``index_runs``' flags [B, blocks], pages a block) — and the selected
    one — (``run_lengths`` [B, MAXP], pages a block): made ONCE a program,
    before the scan over its steps. Nothing where the kernels do not run."""
    if not _reads_in_place():
        return None
    return (index_runs(tables),
            (run_lengths(tables), kv_block(kpool, tables.shape[1])[0]))


def _walk_blocks(runs, pages_live):
    """(blocks walked, blocks fetched as one copy) by the two walks of slots
    holding ``pages_live`` [B] pages: a block is one copy where the table
    says run (bit 0 of the indexer's flags [B, blocks]; ``walk_copies`` of
    the selected walk's) and all its pages hold tokens."""
    (flags, n_pages), selected = runs
    whole = jnp.arange(flags.shape[1])[None, :] < (pages_live // n_pages)[:, None]
    copies = walk_copies(*selected, pages_live)
    return ((-(-pages_live // n_pages)).sum() + copies[0],
            (whole * (flags & 1)).sum() + copies[1])


def _decode_body(params, tokens, pos, tables, cache, active, temps, key,
                 cfg: SparseMoeConfig, runs):
    """One decode step for every slot (masked where inactive); ``runs`` is
    the tables' ``_table_runs``. Returns (next_tok [B], cache, stats)."""
    kpool, vpool, ipool = cache
    B, (MAXP, PS) = tokens.shape[0], (tables.shape[1], kpool.shape[2])
    dk, rows = cfg.indexer_head_dim, ipool.shape[2]
    freqs = sparse_rope_freqs(cfg)
    positions = pos[:, None]
    page = jnp.take_along_axis(tables, (pos // PS)[:, None], axis=1)[:, 0]
    off = pos % PS
    lane_group = jnp.arange(ipool.shape[3]) // dk
    in_place = _reads_in_place()
    if in_place:
        (index_flags, _), (selected_runs, _) = runs
    lengths = jnp.where(active, pos + 1, 0)
    limit = jnp.where(active, pos, -1)  # a slot's last candidate position
    loads = []
    with tracing.part("embed"):
        x = params["tok"]["embedding"][tokens][:, None, :]
    for i in range(cfg.n_layers):
        layer = params[f"layers_{i}"]
        with tracing.part("project"):
            h = rms_norm(x, layer["attn_norm"]["scale"], cfg.rms_norm_eps)
            q, k, v = sparse_project(layer, h, freqs, positions, cfg)
        qi, ki, w = sparse_index(layer, h, freqs, positions, cfg)
        with tracing.part("kv_write"):
            kpool = kpool.at[i, page, off].set(k[:, 0].astype(kpool.dtype))
            vpool = vpool.at[i, page, off].set(v[:, 0].astype(vpool.dtype))
            # the key's half of its packed row; the row's other keys stay
            row = jnp.where(
                lane_group[None, :] == (off // rows)[:, None],
                jnp.tile(ki[:, 0].astype(ipool.dtype),
                         (1, ipool.shape[3] // dk)),
                ipool[i, page, off % rows])
            ipool = ipool.at[i, page, off % rows].set(row)
        with tracing.part("indexer"):
            if in_place:
                scores = paged_index_scores(qi[:, 0], w[:, 0], ipool, i,
                                            tables, lengths, runs=index_flags)
            else:
                scores = indexer_scores(qi, w, unpack_keys(
                    ipool[i][tables], dk).astype(qi.dtype))[:, 0]
        picked = sparse_select(scores[:, None], limit[:, None], cfg,
                               jnp.float32)[:, 0]  # [B, MAXP * PS] of 0 / 1
        with tracing.part("attention"):
            if in_place:
                att = paged_decode_attention(
                    q[:, 0].astype(kpool.dtype), kpool, vpool, i, tables,
                    lengths, selected=picked, runs=selected_runs
                ).reshape(B, 1, -1).astype(x.dtype)
            else:
                att = masked_attention(
                    q, kpool[i][tables].reshape(
                        B, MAXP * PS, *kpool.shape[3:]).astype(q.dtype),
                    vpool[i][tables].reshape(
                        B, MAXP * PS, *vpool.shape[3:]).astype(q.dtype),
                    picked[:, None] != 0)
        x = x + sparse_attn_out(layer, att)
        with tracing.part("ffn"):
            h = rms_norm(x, layer["ffn_norm"]["scale"], cfg.rms_norm_eps)
        y, load = sparse_experts(layer, h, cfg, valid=active[:, None])
        loads.append(load)
        x = x + y
    logits = sparse_logits(params, x[:, 0], cfg)
    next_tok = _sample_tail(logits, temps, key)
    pages_live = -(-lengths // PS)
    if in_place:
        fetched = (pages_live * PS).sum()
        walked = _walk_blocks(runs, pages_live)
    else:  # the gathered form: every slot's table, and no walk
        fetched, walked = jnp.asarray(B * MAXP * PS), [jnp.asarray(0)] * 2
    sparse = cfg.n_layers * jnp.stack([
        lengths.sum(), jnp.minimum(lengths, cfg.topk).sum(), fetched, *walked,
        prefix_walked(limit, MAXP * PS), jnp.asarray(B * MAXP * PS)])
    return (jnp.where(active, next_tok, 0), (kpool, vpool, ipool),
            jnp.concatenate([
                moe_load_stats(loads, B * cfg.n_experts_per_tok),
                sparse.astype(jnp.int32)]))


@partial(jax.jit, static_argnames=("cfg", "n_steps"),
         donate_argnums=(6, 7, 8))
def sparse_moe_decode_multi(params, loras, aids, tokens, seq_lens, tables,
                            kpool, vpool, ipool, active, temps, key,
                            cfg: SparseMoeConfig, n_steps: int):
    """``n_steps`` fused decode steps as one device program: the contract of
    ``ServePrograms.decode_multi`` with three pools, rows of ``[B tokens |
    MOE_STATS | SPARSE_STATS]``. ``loras``/``aids`` are the engine's (None /
    zeros here: refused at construction)."""
    return decode_frame(_decode_body, params, tokens, seq_lens, tables,
                        (kpool, vpool, ipool), active, temps, key, cfg,
                        n_steps, _table_runs(tables, kpool))


@tracing.part("indexer")
def _prefill_picks(qi, w, ki, cfg: SparseMoeConfig):
    """Every query's selected set over its own prompt, one byte a pair:
    [N, T, T] int8. On a TPU, for a prompt of whole tiles, ONE kernel a layer
    (``ops/prefill_picks.py``) that scores and selects over the triangle — a
    tile of queries against the keys it can see, none for the tiles under
    ``topk`` — and keeps the scores in VMEM. Anywhere else the plain form,
    which is the kernel's reference: ``q_chunk`` queries at a time against
    all the prompt's keys, so the float scores of one block are all that
    ever exist."""
    N, T = ki.shape[:2]
    if _reads_in_place() and picks_block(T) is not None:
        with tracing.part("select"):
            return prefill_picks(qi, w, ki, cfg.topk)
    idx = jnp.arange(T)
    bq = cfg.q_chunk if T % cfg.q_chunk == 0 else T

    def block(c):
        q_pos = jnp.broadcast_to(c[2][None, :], (N, bq))
        return sparse_select(indexer_scores(c[0], c[1], ki), q_pos, cfg)

    def blocks(a):  # [N, T, ...] -> [T / bq, N, bq, ...]
        return jnp.moveaxis(a.reshape(N, T // bq, bq, *a.shape[2:]), 1, 0)

    picked = jax.lax.map(block, (blocks(qi), blocks(w), idx.reshape(-1, bq)))
    return jnp.moveaxis(picked, 0, 1).reshape(N, T, T)


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(5, 6, 7))
def sparse_moe_prefill_batch(params, loras, aids, tokens, pages, kpool, vpool,
                             ipool, true_lens, temps, key,
                             cfg: SparseMoeConfig):
    """Prefill a whole admission wave as one batched forward: the contract
    of ``ServePrograms.prefill_batch``, writing all three pools. Returns
    (first tokens [N], the three pools)."""
    N, Tp = tokens.shape
    PS = kpool.shape[2]
    freqs = sparse_rope_freqs(cfg)
    idx = jnp.arange(Tp)
    positions = jnp.broadcast_to(idx[None, :], (N, Tp))
    rows = pages[:, idx // PS]
    offs = jnp.broadcast_to(idx % PS, (N, Tp))
    valid = idx[None, :] < true_lens[:, None]  # padding is routed nowhere
    blocked = _reads_in_place() and blocks_for(Tp) is not None
    with tracing.part("embed"):
        x = params["tok"]["embedding"][tokens]
    for i in range(cfg.n_layers):
        layer = params[f"layers_{i}"]
        with tracing.part("project"):
            h = rms_norm(x, layer["attn_norm"]["scale"], cfg.rms_norm_eps)
            q, k, v = sparse_project(layer, h, freqs, positions, cfg)
        qi, ki, w = sparse_index(layer, h, freqs, positions, cfg)
        with tracing.part("kv_write"):
            kpool = kpool.at[i, rows, offs].set(k.astype(kpool.dtype))
            vpool = vpool.at[i, rows, offs].set(v.astype(vpool.dtype))
            ipool = ipool.at[i, pages].set(
                pack_keys(ki.astype(ipool.dtype), PS))
        picked = _prefill_picks(qi, w, ki, cfg)
        with tracing.part("attention"):
            if blocked:
                att = gqa_prefill_attention(
                    q.reshape(N, Tp, -1), k.reshape(N, Tp, -1),
                    v.reshape(N, Tp, -1), n_kv_heads=cfg.n_kv_heads,
                    picked=picked)
            else:
                att = masked_attention(q, k, v, picked != 0)
        x = x + sparse_attn_out(layer, att)
        with tracing.part("ffn"):
            h = rms_norm(x, layer["ffn_norm"]["scale"], cfg.rms_norm_eps)
        y, _ = sparse_experts(layer, h, cfg, valid=valid)
        x = x + y
    logits = sparse_logits(params, last_rows(x, true_lens), cfg)
    return _sample_tail(logits, temps, key), kpool, vpool, ipool


PROGRAMS = ServePrograms(
    family="sparse_moe", make_cache=make_pools,
    decode_multi=sparse_moe_decode_multi,
    prefill_batch=sparse_moe_prefill_batch, init=sparse_moe_init,
    stats=MOE_STATS + SPARSE_STATS,
    decode_in_place=lambda cache: _reads_in_place(),
    prefill_wave_limit=WAVE_LIMIT, attends_most=lambda cfg: cfg.topk,
    caches="K, V and the indexer's key pages, of which a step attends the "
           "rows it picks")

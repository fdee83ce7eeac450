"""The serving programs of ``models/eva.py`` for the continuous-batching
engine: same slots, blocks and loop as the other families, a cache of TWO
KINDS of pages of which one holds a row for every CHUNK of positions.

* **Two kinds, one page shape.** Every layer's exact keys and values live in
  ``window`` pools ``[L, P, PS, H, hd]`` and a slot's table there is a RING of
  exactly ``window_size / PS`` entries: attention is exact only inside the
  query's own ALIGNED window, so the ring holds one window, the page of
  positions ``[p * PS, (p + 1) * PS)`` at entry ``p % entries``, and a step
  that crosses a multiple of ``window_size`` starts writing over entry 0 — the
  whole old window is dropped at once. Every layer's pooled pairs live in
  ``summary`` pools of the same page shape whose ROW is a chunk: chunk ``c``
  of a slot lies at row ``c % PS`` of its table's entry ``c // PS``, so a
  page stands for ``PS * chunk_size`` positions. The engine draws both
  (``page_kinds``: ``PageKind.stride`` and ``aligned``) and learns nothing of
  what a row holds.
* **Decode** writes the new K and V row, attends the window's rows ``[W * (t
  // W), t]`` and the pairs ``[0, (W / C) * (t // W))`` under ONE softmax, and
  — when the step fills a chunk — pools that chunk's rows into its pair and
  writes it (visible to attention only once the window has closed, by the
  length the walk is given). On a TPU the two tables are two walks of
  ``ops/paged_attention.py``, each given out with its running maximum and
  sum, joined exactly (``merge_attention_parts``): two calls of the walk the
  other families run, rather than one work list over two tables, because the
  pools are two arrays and the walk's block and buffers are one pool pair's.
  Anywhere else, the gathered tables with position masks and one softmax
  written out: the plain form and what the CPU tests run (the seam's rule,
  bound here as ``_reads_in_place``). A chunk is pooled as it fills, not a
  window at its boundary: the same mathematics at 1/128 of the burst, and a
  step that closes a window costs what any other does.
* **Prefill** is whole-prompt per pad bucket: the pairs of every chunk that
  is complete at the prompt's TRUE length are made and written (a pad
  position never enters a pair), attention over the fresh keys and pairs is
  blocked (``ops/prefill_attention.py`` ``eva_prefill_attention``: no ``[T,
  T]`` array), and of the exact rows only those of the window the next
  position lies in are written — positions ``[W * (n // W), n)`` of a prompt
  of ``n``. A wave holds at most ``WAVE_LIMIT`` prompts and tokens.
* **The head.** The served path samples head 0, the model's next-token
  distribution; the other heads are the no-cache forward's.

LoRA, int8 pools, speculative decoding, suffix prefill and page export take a
prefix of a slot's pages for a prefix of its sequence, which neither a ring
of one window nor a table of pooled pairs is; ``llm/engine.py`` refuses them
for this family by name.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.llm.programs import (
    PageKind, ServePrograms, _sample_tail, decode_frame, last_rows,
    reads_in_place)
from ray_tpu.models.eva import (
    EvaConfig, eva_attend_plain, eva_attn_out, eva_ffn, eva_init, eva_logits,
    eva_pairs_seen, eva_project, eva_reach, eva_rope_freqs, eva_summarize)
from ray_tpu.ops.paged_attention import (
    merge_attention_parts, paged_attention_part, run_lengths)
from ray_tpu.ops.prefill_attention import eva_blocks_for, eva_prefill_attention
from ray_tpu.utils import tracing

# the most prompts and tokens one prefill program may hold, as the other
# families with long prompts
WAVE_LIMIT = (8, 16384)
# pairs written a step, summed over layers
STATS = ("eva_pairs",)
# The seam's platform rule under this module's own name, asked through this
# global by every program here and by ``PROGRAMS.decode_in_place``: ``tests/``
# ASSIGN an answer here to run the kernels interpreted.
_reads_in_place = reads_in_place


def ring_entries(cfg: EvaConfig, page_size: int) -> int:
    """Entries of a slot's window table: exactly one window's pages."""
    if cfg.window_size % page_size or page_size % cfg.chunk_size:
        raise ValueError(
            f"pages of {page_size} are not whole chunks of {cfg.chunk_size} "
            f"that fill a window of {cfg.window_size}")
    return cfg.window_size // page_size


def page_kinds(cfg: EvaConfig, page_size: int, max_seq_len: int):
    """What a slot holds of each kind (``ServePrograms.page_kinds``): the
    ring of its own window's exact rows, and a row a chunk of pooled pairs
    of which a query attends the whole windows' before its own."""
    entries = ring_entries(cfg, page_size)
    return (PageKind("window", cfg.n_layers,
                     min(-(-max_seq_len // page_size), entries),
                     reach=cfg.window_size, aligned=True),
            PageKind("summary", cfg.n_layers,
                     -(-max_seq_len // (page_size * cfg.chunk_size)),
                     reach=cfg.window_size, stride=cfg.chunk_size,
                     aligned=True))


def make_pools(cfg: EvaConfig, page_size: int, n_pages, kv_dtype):
    """The model's cache: (K window, V window, K^ summary, V^ summary), one
    page shape. ``n_pages``: one count for both kinds, or ``{"window": n,
    "summary": m}``."""
    if kv_dtype not in (None, "native", "bf16"):
        raise ValueError(f"unknown kv_dtype {kv_dtype!r}")
    ring_entries(cfg, page_size)
    dtype = jnp.bfloat16 if kv_dtype == "bf16" else jnp.dtype(cfg.dtype)
    if not isinstance(n_pages, dict):
        n_pages = {"window": n_pages, "summary": n_pages}
    return tuple(
        jnp.zeros((cfg.n_layers, n_pages[kind], page_size, cfg.n_heads,
                   cfg.head_dim), dtype)
        for kind in ("window", "summary") for _ in range(2))


def _attend_gathered(q, kw, vw, ks, vs, t_win, t_sum, pos, cfg: EvaConfig):
    """The plain form of a decode step's attention: both tables gathered,
    each row masked by what it holds — entry e of the ring the positions
    ``W * (pos // W) + e * PS + [0, PS)``, row r of the pairs chunk r. q: [B,
    1, H, hd]; the pools one layer's [P, PS, H, hd]; t_win: [B, entries];
    t_sum: [B, pages of pairs]; pos: [B]. Returns [B, 1, H * hd]."""
    B, PS = t_win.shape[0], kw.shape[1]

    def rows(pool, table):
        return pool[table].reshape(B, table.shape[1] * PS, *pool.shape[2:])

    start = pos // cfg.window_size * cfg.window_size
    k_pos = start[:, None] + jnp.arange(t_win.shape[1] * PS)[None, :]
    seen = jnp.arange(t_sum.shape[1] * PS)[None, :] < eva_pairs_seen(
        pos, cfg)[:, None]
    return eva_attend_plain(
        q, rows(kw, t_win), rows(vw, t_win), rows(ks, t_sum), rows(vs, t_sum),
        eva_reach(pos[:, None], k_pos, cfg)[:, None], seen[:, None])


def _decode_body(params, tokens, pos, tables, cache, active, temps, key,
                 cfg: EvaConfig, runs):
    """One decode step for every slot (masked where inactive); ``runs``: the
    two tables' ``run_lengths`` (None each where the kernel does not run).
    Returns (next_tok [B], cache, stats)."""
    t_win, t_sum = tables
    kw, vw, ks, vs = cache
    B, PS = tokens.shape[0], kw.shape[2]
    W, C = cfg.window_size, cfg.chunk_size
    cos, sin = eva_rope_freqs(cfg)
    off = pos % PS
    page = jnp.take_along_axis(
        t_win, (pos // PS % t_win.shape[1])[:, None], axis=1)[:, 0]
    # the step fills chunk pos // C: its pair goes to row (pos // C) % PS of
    # the slot's page of pairs; every other slot's to the junk page
    fills = active & ((pos + 1) % C == 0)
    chunk = pos // C
    pair_page = jnp.where(fills, jnp.take_along_axis(
        t_sum, (chunk // PS)[:, None], axis=1)[:, 0], 0)
    in_place = _reads_in_place()
    lengths = jnp.where(active, pos + 1, 0)
    starts = pos // W * W
    n_pairs = jnp.where(active, eva_pairs_seen(pos, cfg), 0)
    with tracing.part("embed"):
        x = params["tok"]["embedding"][tokens][:, None, :].astype(jnp.float32)
    for i in range(cfg.n_layers):
        layer = params[f"layers_{i}"]
        q, k, v = eva_project(layer, x, cos, sin, pos[:, None], cfg)
        with tracing.part("kv_write"):
            kw = kw.at[i, page, off].set(k[:, 0].astype(kw.dtype))
            vw = vw.at[i, page, off].set(v[:, 0].astype(vw.dtype))
        if in_place:
            with tracing.part("attention"):
                q1 = q[:, 0].astype(kw.dtype)
                att = merge_attention_parts(
                    paged_attention_part(q1, kw, vw, i, t_win, lengths,
                                         starts=starts, runs=runs[0]),
                    paged_attention_part(q1, ks, vs, i, t_sum, n_pairs,
                                         runs=runs[1]))
                att = att.reshape(B, 1, -1).astype(q.dtype)
        else:
            att = _attend_gathered(q, kw[i], vw[i], ks[i], vs[i], t_win,
                                   t_sum, pos, cfg)
        with tracing.part("summary"):
            def filled(pool):  # the chunk the step wrote into, [B, C, H, hd]
                rows = pool[i, page].reshape(B, PS // C, C, *pool.shape[3:])
                return jnp.take_along_axis(
                    rows, (off // C)[:, None, None, None, None], axis=1)[:, 0]

            kh, vh = eva_summarize(layer, filled(kw), filled(vw), ks.dtype)
            ks = ks.at[i, pair_page, chunk % PS].set(kh)
            vs = vs.at[i, pair_page, chunk % PS].set(vh)
        x = eva_ffn(layer, eva_attn_out(layer, x, att), cfg)
    logits = eva_logits(params, x[:, 0], cfg, heads=1)[:, 0]
    next_tok = _sample_tail(logits, temps, key)
    stats = (fills.sum() * cfg.n_layers).astype(jnp.int32)[None]
    return jnp.where(active, next_tok, 0), (kw, vw, ks, vs), stats


@partial(jax.jit, static_argnames=("cfg", "n_steps"),
         donate_argnums=(6, 7, 8, 9))
def eva_decode_multi(params, loras, aids, tokens, seq_lens, tables, kw, vw,
                     ks, vs, active, temps, key, cfg: EvaConfig, n_steps: int):
    """``n_steps`` fused decode steps as one device program: the contract of
    ``ServePrograms.decode_multi`` with one table a kind (window, summary)
    and four pools, rows of ``[B tokens | STATS]``. ``loras``/``aids`` are
    the engine's (None / zeros here: refused at construction)."""
    # (window, summary): found once a program, not a layer a step
    runs = [run_lengths(t) if _reads_in_place() else None for t in tables]
    return decode_frame(_decode_body, params, tokens, seq_lens, tables,
                        (kw, vw, ks, vs), active, temps, key, cfg, n_steps,
                        runs)


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(5, 6, 7, 8))
def eva_prefill_batch(params, loras, aids, tokens, pages, kw, vw, ks, vs,
                      true_lens, temps, key, cfg: EvaConfig):
    """Prefill a whole admission wave as one batched forward: the contract
    of ``ServePrograms.prefill_batch`` with ``pages`` one array a kind —
    window ``[N, min(pad / PS, ring entries)]``, summary ``[N, pages of the
    pad's pairs]``. Returns (first tokens [N], the four pools)."""
    p_win, p_sum = pages
    N, Tp = tokens.shape
    PS, W, C = kw.shape[2], cfg.window_size, cfg.chunk_size
    cos, sin = eva_rope_freqs(cfg)
    idx = jnp.arange(Tp)
    positions = jnp.broadcast_to(idx[None, :], (N, Tp))
    # the exact rows the ring holds at the prompt's end: those of the window
    # its NEXT position lies in; a pad's and an earlier window's go to junk
    kept = ((idx[None, :] >= (true_lens // W * W)[:, None])
            & (idx[None, :] < true_lens[:, None]))
    rows = jnp.where(kept, p_win[:, idx // PS % ring_entries(cfg, PS)], 0)
    offs = jnp.broadcast_to(idx % PS, (N, Tp))
    # the pairs of the chunks complete at the prompt's true length
    chunks = jnp.arange(Tp // C)
    whole = (chunks[None, :] + 1) * C <= true_lens[:, None]
    pair_rows = jnp.where(whole, p_sum[:, chunks // PS], 0)
    pair_offs = jnp.broadcast_to(chunks % PS, (N, Tp // C))
    blocked = _reads_in_place() and eva_blocks_for(Tp, W) is not None
    if not blocked:
        mask = jnp.broadcast_to(
            eva_reach(idx[:, None], idx[None, :], cfg), (N, Tp, Tp))
        seen = jnp.broadcast_to(
            chunks[None, :] < eva_pairs_seen(idx, cfg)[:, None],
            (N, Tp, Tp // C))
    with tracing.part("embed"):
        x = params["tok"]["embedding"][tokens].astype(jnp.float32)
    for i in range(cfg.n_layers):
        layer = params[f"layers_{i}"]
        q, k, v = eva_project(layer, x, cos, sin, positions, cfg)
        k, v = k.astype(kw.dtype), v.astype(vw.dtype)
        with tracing.part("kv_write"):
            kw = kw.at[i, rows, offs].set(k)
            vw = vw.at[i, rows, offs].set(v)
        with tracing.part("summary"):
            kh, vh = eva_summarize(
                layer, k.reshape(N, Tp // C, C, *k.shape[2:]),
                v.reshape(N, Tp // C, C, *v.shape[2:]))
            ks = ks.at[i, pair_rows, pair_offs].set(kh)
            vs = vs.at[i, pair_rows, pair_offs].set(vh)
        if blocked:
            with tracing.part("attention"):
                att = eva_prefill_attention(
                    q.reshape(N, Tp, -1).astype(k.dtype), k.reshape(N, Tp, -1),
                    v.reshape(N, Tp, -1), kh.reshape(N, Tp // C, -1),
                    vh.reshape(N, Tp // C, -1), n_heads=cfg.n_heads, window=W,
                    chunk=C).astype(q.dtype)
        else:
            att = eva_attend_plain(q, k.astype(q.dtype), v.astype(q.dtype),
                                   kh, vh, mask, seen)
        x = eva_ffn(layer, eva_attn_out(layer, x, att), cfg)
        # the layer's writes land before the next layer starts: left to
        # itself the compiler puts every layer's scatter at the program's end
        # and keeps all their keys and values until then (2 GB at 15,360 rows)
        x, kw, vw, ks, vs = jax.lax.optimization_barrier((x, kw, vw, ks, vs))
    logits = eva_logits(params, last_rows(x, true_lens), cfg, heads=1)[:, 0]
    return _sample_tail(logits, temps, key), kw, vw, ks, vs


PROGRAMS = ServePrograms(
    family="eva", make_cache=make_pools, decode_multi=eva_decode_multi,
    prefill_batch=eva_prefill_batch, init=eva_init, stats=STATS,
    decode_in_place=lambda cache: _reads_in_place(),
    page_kinds=page_kinds, prefill_wave_limit=WAVE_LIMIT,
    caches="a ring of exact K and V pages for its own window alone and one "
           "pooled pair for every chunk before it: a prefix of its pages is "
           "no prefix of the sequence")

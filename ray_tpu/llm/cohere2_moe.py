"""The serving programs of ``models/cohere2_moe.py`` for the continuous-
batching engine: same slots, blocks and loop as the other families, a cache
of TWO KINDS of pages and a parallel attention + expert layer.

* **Two kinds of pages.** The full-attention layers' keys and values live
  in pools ``[n_full, P_full, PS, KV, hd]`` and a slot holds ``ceil(n / PS)``
  of those pages, as a Llama slot does. The window layers' live in pools
  ``[n_window, P_window, PS, KV, hd]`` of their own, and a slot's table
  there is a RING of ``window / PS + 1`` entries: the page of positions
  ``[p * PS, (p + 1) * PS)`` lies at entry ``p % entries``, so a decode
  step that crosses into a new page writes over the page the window has
  just slid past. A slot never holds more window pages than that, however
  long it grows, and the engine draws them once (``page_kinds``): it keeps a
  table and a free list a kind and never learns what a page holds.
* **Decode** reads every layer's pages where they lie
  (``ops/paged_attention.py``): a full layer the slot's ``pos + 1`` rows, a
  window layer from the page that holds ``pos + 1 - window`` on — nothing
  before it is fetched. Off the TPU, where the kernel would be interpreted,
  the gathered table with a position mask: the kernel's plain reference and
  what the CPU tests run (the seam's one rule, bound here as
  ``_reads_in_place``).
* **Prefill** is whole-prompt per pad bucket. Attention over the fresh keys
  is blocked (``ops/prefill_attention.py``: no ``[T, T]`` array, the window's
  lower bound skips blocks); of a prompt longer than the ring, only the
  pages the ring still holds at the prompt's end are written. A wave holds
  at most ``WAVE_LIMIT`` prompts and tokens: the engine splits a pad group.
* **The expert layer** routes over all experts and computes the held ones'
  part plus the shared experts' mean; that partial sum goes on. The four
  ``MOE_STATS`` columns ride back with the tokens, as ``llm/mla_moe.py``'s.

LoRA, int8 pools, speculative decoding, suffix prefill and page export
assume one K and one V pool over every layer; ``llm/engine.py`` refuses them
for this family by name.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.llm.programs import (
    MOE_STATS, PageKind, ServePrograms, _sample_tail, decode_frame, last_rows,
    moe_load_stats, reads_in_place)
from ray_tpu.models.cohere2_moe import (
    Cohere2MoeConfig, cohere2_attn_out, cohere2_experts, cohere2_logits,
    cohere2_moe_init, cohere2_project, cohere2_reach, cohere2_rope_freqs)
from ray_tpu.ops.attention import gathered_attention, masked_attention
from ray_tpu.ops.basic import layer_norm
from ray_tpu.ops.paged_attention import paged_decode_attention, run_lengths
from ray_tpu.ops.prefill_attention import blocks_for, gqa_prefill_attention
from ray_tpu.utils import tracing

# the most prompts and tokens one prefill program may hold: eight waiting
# 12,288-token prompts would otherwise be one 98k-token program
WAVE_LIMIT = (8, 16384)
# The seam's platform rule under this module's own name, asked through this
# global by every program here and by ``PROGRAMS.decode_in_place``:
# ``benchmarks/sizing_cohere2_moe.py`` (to compile the chip's branch on a CPU)
# and ``tests/`` (to run the kernels interpreted) ASSIGN an answer here.
_reads_in_place = reads_in_place


def ring_entries(cfg: Cohere2MoeConfig, page_size: int) -> int:
    """Entries of a slot's window table: the pages a window can touch."""
    if cfg.sliding_window % page_size:
        raise ValueError(f"a window of {cfg.sliding_window} is not whole "
                         f"pages of {page_size}")
    return cfg.sliding_window // page_size + 1


def page_kinds(cfg: Cohere2MoeConfig, page_size: int, max_seq_len: int):
    """What a slot holds of each kind of page (``ServePrograms.page_kinds``):
    the table of the full kind first."""
    maxp = -(-max_seq_len // page_size)
    return (PageKind("full", len(cfg.layers_of(False)), maxp),
            PageKind("window", len(cfg.layers_of(True)),
                     min(maxp, ring_entries(cfg, page_size)),
                     reach=cfg.sliding_window))


def make_pools(cfg: Cohere2MoeConfig, page_size: int, n_pages, kv_dtype):
    """The model's cache: (K full, V full, K window, V window). ``n_pages``:
    one count for both kinds, or ``{"full": n, "window": m}``."""
    if kv_dtype not in (None, "native", "bf16"):
        raise ValueError(f"unknown kv_dtype {kv_dtype!r}")
    dtype = jnp.bfloat16 if kv_dtype == "bf16" else jnp.dtype(cfg.dtype)
    if not isinstance(n_pages, dict):
        n_pages = {"full": n_pages, "window": n_pages}
    out = []
    for window in (False, True):
        shape = (len(cfg.layers_of(window)),
                 n_pages["window" if window else "full"], page_size,
                 cfg.n_kv_heads, cfg.head_dim)
        out += [jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)]
    return tuple(out)


def _decode_body(params, tokens, pos, tables, cache, active, temps, key,
                 cfg: Cohere2MoeConfig, runs):
    """One decode step for every slot (masked where inactive); ``runs``: the
    two tables' ``run_lengths`` (None each where the kernel does not run).
    Returns (next_tok [B], cache, stats)."""
    t_full, t_win = tables
    kf, vf, kw, vw = cache
    B, PS = tokens.shape[0], kf.shape[2]
    cos, sin = cohere2_rope_freqs(cfg)
    positions = pos[:, None]
    off = pos % PS
    rows = {False: jnp.take_along_axis(
                t_full, (pos // PS)[:, None], axis=1)[:, 0],
            True: jnp.take_along_axis(
                t_win, (pos // PS % t_win.shape[1])[:, None], axis=1)[:, 0]}
    in_place = _reads_in_place()
    lengths = jnp.where(active, pos + 1, 0)
    starts = jnp.maximum(lengths - cfg.sliding_window, 0)
    at = {False: 0, True: 0}  # the layer's place in its kind's pools
    loads = []
    with tracing.part("embed"):
        x = params["tok"]["embedding"][tokens][:, None, :]
    for i in range(cfg.n_layers):
        layer, window = params[f"layers_{i}"], cfg.is_window(i)
        j, at[window] = at[window], at[window] + 1
        with tracing.part("project"):
            h = layer_norm(x, layer["norm"]["scale"], cfg.layer_norm_eps)
            q, k, v = cohere2_project(layer, h, cos, sin, positions, cfg,
                                      window)
        kp, vp = (kw, vw) if window else (kf, vf)
        with tracing.part("kv_write"):
            kp = kp.at[j, rows[window], off].set(k[:, 0].astype(kp.dtype))
            vp = vp.at[j, rows[window], off].set(v[:, 0].astype(vp.dtype))
        table = t_win if window else t_full
        if in_place:
            with tracing.part("attention"):
                att = paged_decode_attention(
                    q[:, 0].astype(kp.dtype), kp, vp, j, table, lengths,
                    starts=starts if window else None, runs=runs[window])
                att = att.reshape(B, 1, -1).astype(x.dtype)
        else:
            att = gathered_attention(
                q, kp[j], vp[j], table, pos,
                cfg.sliding_window if window else None)
        if window:
            kw, vw = kp, vp
        else:
            kf, vf = kp, vp
        y, load = cohere2_experts(layer, h, cfg, valid=active[:, None])
        loads.append(load)
        x = x + cohere2_attn_out(layer, att) + y
    logits = cohere2_logits(params, x[:, 0], cfg)
    next_tok = _sample_tail(logits, temps, key)
    return (jnp.where(active, next_tok, 0), (kf, vf, kw, vw),
            moe_load_stats(loads, B * cfg.n_experts_per_tok))


@partial(jax.jit, static_argnames=("cfg", "n_steps"),
         donate_argnums=(6, 7, 8, 9))
def cohere2_moe_decode_multi(params, loras, aids, tokens, seq_lens, tables,
                             kf, vf, kw, vw, active, temps, key,
                             cfg: Cohere2MoeConfig, n_steps: int):
    """``n_steps`` fused decode steps as one device program: the contract of
    ``ServePrograms.decode_multi`` with one table a kind (full, window) and
    four pools, rows of ``[B tokens | MOE_STATS]``. ``loras``/``aids`` are
    the engine's (None / zeros here: refused at construction)."""
    # (full, window): found once a program, not a layer a step
    runs = [run_lengths(t) if _reads_in_place() else None for t in tables]
    return decode_frame(_decode_body, params, tokens, seq_lens, tables,
                        (kf, vf, kw, vw), active, temps, key, cfg, n_steps,
                        runs)


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(5, 6, 7, 8))
def cohere2_moe_prefill_batch(params, loras, aids, tokens, pages, kf, vf, kw,
                              vw, true_lens, temps, key,
                              cfg: Cohere2MoeConfig):
    """Prefill a whole admission wave as one batched forward: the contract
    of ``ServePrograms.prefill_batch`` with ``pages`` one array a kind —
    full ``[N, pad / PS]``, window ``[N, min(pad / PS, ring entries)]``.
    Returns (first tokens [N], the four pools)."""
    p_full, p_win = pages
    N, Tp = tokens.shape
    PS = kf.shape[2]
    ring = ring_entries(cfg, PS)
    cos, sin = cohere2_rope_freqs(cfg)
    idx = jnp.arange(Tp)
    positions = jnp.broadcast_to(idx[None, :], (N, Tp))
    page = idx // PS
    offs = jnp.broadcast_to(idx % PS, (N, Tp))
    # of a prompt longer than the ring, the pages it still holds at the
    # prompt's end; the rest go to the junk page
    last = ((true_lens - 1) // PS)[:, None]
    kept = (page[None, :] <= last) & (page[None, :] > last - ring)
    rows = {False: p_full[:, page],
            True: jnp.where(kept, p_win[:, page % ring], 0)}
    valid = idx[None, :] < true_lens[:, None]  # padding is routed nowhere
    blocked = _reads_in_place() and blocks_for(Tp) is not None
    at = {False: 0, True: 0}
    with tracing.part("embed"):
        x = params["tok"]["embedding"][tokens]
    for i in range(cfg.n_layers):
        layer, window = params[f"layers_{i}"], cfg.is_window(i)
        j, at[window] = at[window], at[window] + 1
        with tracing.part("project"):
            h = layer_norm(x, layer["norm"]["scale"], cfg.layer_norm_eps)
            q, k, v = cohere2_project(layer, h, cos, sin, positions, cfg,
                                      window)
        with tracing.part("kv_write"):
            if window:
                kw = kw.at[j, rows[True], offs].set(k.astype(kw.dtype))
                vw = vw.at[j, rows[True], offs].set(v.astype(vw.dtype))
            else:
                kf = kf.at[j, rows[False], offs].set(k.astype(kf.dtype))
                vf = vf.at[j, rows[False], offs].set(v.astype(vf.dtype))
        if blocked:
            with tracing.part("attention"):
                att = gqa_prefill_attention(
                    q.reshape(N, Tp, -1), k.reshape(N, Tp, -1),
                    v.reshape(N, Tp, -1), n_kv_heads=cfg.n_kv_heads,
                    window=cfg.sliding_window if window else None)
        else:
            mask = jnp.broadcast_to(
                cohere2_reach(idx[:, None], idx[None, :], cfg, window),
                (N, Tp, Tp))
            att = masked_attention(q, k, v, mask)
        y, _ = cohere2_experts(layer, h, cfg, valid=valid)
        x = x + cohere2_attn_out(layer, att) + y
    logits = cohere2_logits(params, last_rows(x, true_lens), cfg)
    return _sample_tail(logits, temps, key), kf, vf, kw, vw


PROGRAMS = ServePrograms(
    family="cohere2_moe", make_cache=make_pools,
    decode_multi=cohere2_moe_decode_multi,
    prefill_batch=cohere2_moe_prefill_batch, init=cohere2_moe_init,
    stats=MOE_STATS, decode_in_place=lambda cache: _reads_in_place(),
    page_kinds=page_kinds, prefill_wave_limit=WAVE_LIMIT,
    caches="full layers' pages and a ring of the window layers' pages, which "
           "holds the last window alone")

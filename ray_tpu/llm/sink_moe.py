"""The serving programs of ``models/sink_moe.py`` for the continuous-batching
engine: same slots, blocks and loop as the other families, a cache of TWO
KINDS of pages of TWO GEOMETRIES, and a sink beside every window walk.

* **Two kinds of pages, four shapes.** The full-attention layers' keys live
  in a pool ``[n_full, P_full, PS, KV_full, key lanes]`` and their values in
  ``[n_full, P_full, PS, KV_full, hv]``; a slot holds ``ceil(n / PS)`` of
  those pages. The window layers' live in ``[n_window, P_window, PS,
  KV_window, key lanes]`` and ``[..., KV_window, hv]`` — another KV-head
  count — and a slot's table there is a RING of ``window / PS + 1`` entries
  (``llm/cohere2_moe.py`` has the ring's rule: the page of positions ``[p *
  PS, (p + 1) * PS)`` lies at entry ``p % entries``). A key is ``head_dim``
  lanes and is kept in ``key_lanes`` = the whole lane tiles it lies in (192
  in 256: what the device's layout pads a row of 192 to anyway, written out
  so that a page is one plain run and a walk's copy of it one descriptor),
  the lanes past ``head_dim`` zeros; a value is ``hv`` lanes as it is. The
  engine keeps a table and a free list a kind (``page_kinds``) and never
  learns what a page holds.
* **Decode** reads every layer's pages where they lie (``ops/
  paged_attention.py``): a full layer the slot's ``pos + 1`` rows; a window
  layer from the page that holds ``pos + 1 - window`` on, AS A PART of a
  softmax (``paged_attention_part``: output, running maximum and sum), joined
  exactly with the part that is the layer's sink, ``(0, sink[h], 1)``
  (``merge_attention_parts``): the sink takes its share of the mass and adds
  no value. The walk's kernel is the one every ring walk runs; nothing of it
  knows a sink. Off the TPU, where the kernel would be interpreted, the
  gathered table with a position mask and the sink as a dropped column (the
  seam's one rule, bound here as ``_reads_in_place``).
* **Prefill** is whole-prompt per pad bucket. Attention over the fresh keys
  is blocked (``ops/prefill_attention.py``: keys of 192 against values of 128,
  the window's lower bound skips blocks, a window layer's running maximum
  and sum start at its sink); of a prompt longer than the ring, only the
  pages the ring still holds at the prompt's end are written. A wave holds
  at most ``WAVE_LIMIT`` prompts and tokens: the engine splits a pad group.
* **The second half** of layer 0 is a dense SwiGLU; every other layer routes
  over all experts and computes the held ones' part, which goes on. The four
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
from ray_tpu.models.sink_moe import (
    SinkMoeConfig, sink_attn_out, sink_ffn, sink_logits, sink_moe_init,
    sink_project, sink_reach, sink_rope_freqs)
from ray_tpu.ops.attention import gathered_attention, masked_attention
from ray_tpu.ops.basic import rms_norm
from ray_tpu.ops.paged_attention import (
    merge_attention_parts, paged_attention_part, paged_decode_attention,
    run_lengths)
from ray_tpu.ops.prefill_attention import blocks_for, gqa_prefill_attention
from ray_tpu.utils import tracing

# the most prompts and tokens one prefill program may hold: eight waiting
# 16,384-token prompts would otherwise be one 131k-token program
WAVE_LIMIT = (8, 16384)
# The seam's platform rule under this module's own name, asked through this
# global by every program here and by ``PROGRAMS.decode_in_place``:
# ``benchmarks/sizing_sink_moe.py`` (to compile the chip's branch on a CPU)
# and ``tests/`` (to run the kernels interpreted) ASSIGN an answer here.
_reads_in_place = reads_in_place


def key_lanes(cfg: SinkMoeConfig) -> int:
    """Lanes a cached key lies in: its head's whole lane tiles."""
    return -(-cfg.head_dim // 128) * 128


def ring_entries(cfg: SinkMoeConfig, page_size: int) -> int:
    """Entries of a slot's window table: the pages a window can touch."""
    if cfg.sliding_window % page_size:
        raise ValueError(f"a window of {cfg.sliding_window} is not whole "
                         f"pages of {page_size}")
    return cfg.sliding_window // page_size + 1


def page_kinds(cfg: SinkMoeConfig, page_size: int, max_seq_len: int):
    """What a slot holds of each kind of page (``ServePrograms.page_kinds``):
    the table of the full kind first."""
    maxp = -(-max_seq_len // page_size)
    return (PageKind("full", len(cfg.layers_of(False)), maxp),
            PageKind("window", len(cfg.layers_of(True)),
                     min(maxp, ring_entries(cfg, page_size)),
                     reach=cfg.sliding_window))


def make_pools(cfg: SinkMoeConfig, page_size: int, n_pages, kv_dtype):
    """The model's cache: (K full, V full, K window, V window), each kind
    with its own KV heads, a K row ``key_lanes`` wide and a V row
    ``v_head_dim``. ``n_pages``: one count for both kinds, or ``{"full": n,
    "window": m}``."""
    if kv_dtype not in (None, "native", "bf16"):
        raise ValueError(f"unknown kv_dtype {kv_dtype!r}")
    dtype = jnp.bfloat16 if kv_dtype == "bf16" else jnp.dtype(cfg.dtype)
    if not isinstance(n_pages, dict):
        n_pages = {"full": n_pages, "window": n_pages}
    out = []
    for window in (False, True):
        lead = (len(cfg.layers_of(window)),
                n_pages["window" if window else "full"], page_size,
                cfg.kv_heads(window))
        out += [jnp.zeros((*lead, key_lanes(cfg)), dtype),
                jnp.zeros((*lead, cfg.v_head_dim), dtype)]
    return tuple(out)


def _key_rows(k, pool):
    """Fresh keys [..., hd] as the rows of ``pool``: its dtype, zeros in the
    lanes past the head's."""
    short = pool.shape[-1] - k.shape[-1]
    return jnp.pad(k.astype(pool.dtype), ((0, 0),) * (k.ndim - 1) + ((0, short),))


def _decode_body(params, tokens, pos, tables, cache, active, temps, key,
                 cfg: SinkMoeConfig, runs):
    """One decode step for every slot (masked where inactive); ``runs``: the
    two tables' ``run_lengths`` (None each where the kernel does not run).
    Returns (next_tok [B], cache, stats)."""
    t_full, t_win = tables
    kf, vf, kw, vw = cache
    B, PS = tokens.shape[0], kf.shape[2]
    ropes = sink_rope_freqs(cfg)
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
            h = rms_norm(x, layer["attn_norm"]["scale"], cfg.rms_norm_eps)
            q, k, v = sink_project(layer, h, ropes, positions, cfg, window)
        kp, vp = (kw, vw) if window else (kf, vf)
        with tracing.part("kv_write"):
            kp = kp.at[j, rows[window], off].set(_key_rows(k[:, 0], kp))
            vp = vp.at[j, rows[window], off].set(v[:, 0].astype(vp.dtype))
        table, sink = t_win if window else t_full, layer.get("sink")
        if in_place:
            with tracing.part("attention"):
                walk = dict(starts=starts if window else None,
                            runs=runs[window])
                qr = q[:, 0].astype(kp.dtype)
                if sink is None:
                    att = paged_decode_attention(qr, kp, vp, j, table,
                                                 lengths, **walk)
                else:  # the sink: one more part, a score with no value
                    o, m, l = paged_attention_part(qr, kp, vp, j, table,
                                                   lengths, **walk)
                    att = merge_attention_parts((o, m, l), (
                        jnp.zeros_like(o),
                        jnp.broadcast_to(sink.astype(jnp.float32), m.shape),
                        jnp.ones_like(l)))
                att = att.reshape(B, 1, -1).astype(x.dtype)
        else:
            att = gathered_attention(
                q, kp[j], vp[j], table, pos,
                cfg.sliding_window if window else None, sink)
        if window:
            kw, vw = kp, vp
        else:
            kf, vf = kp, vp
        x = x + sink_attn_out(layer, att)
        with tracing.part("project"):
            g = rms_norm(x, layer["ffn_norm"]["scale"], cfg.rms_norm_eps)
        y, load = sink_ffn(layer, g, cfg, valid=active[:, None])
        if load is not None:
            loads.append(load)
        x = x + y
    logits = sink_logits(params, x[:, 0], cfg)
    next_tok = _sample_tail(logits, temps, key)
    return (jnp.where(active, next_tok, 0), (kf, vf, kw, vw),
            moe_load_stats(loads, B * cfg.n_experts_per_tok))


@partial(jax.jit, static_argnames=("cfg", "n_steps"),
         donate_argnums=(6, 7, 8, 9))
def sink_moe_decode_multi(params, loras, aids, tokens, seq_lens, tables,
                          kf, vf, kw, vw, active, temps, key,
                          cfg: SinkMoeConfig, n_steps: int):
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
def sink_moe_prefill_batch(params, loras, aids, tokens, pages, kf, vf, kw,
                           vw, true_lens, temps, key, cfg: SinkMoeConfig):
    """Prefill a whole admission wave as one batched forward: the contract
    of ``ServePrograms.prefill_batch`` with ``pages`` one array a kind —
    full ``[N, pad / PS]``, window ``[N, min(pad / PS, ring entries)]``.
    Returns (first tokens [N], the four pools)."""
    p_full, p_win = pages
    N, Tp = tokens.shape
    PS = kf.shape[2]
    ring = ring_entries(cfg, PS)
    ropes = sink_rope_freqs(cfg)
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
            h = rms_norm(x, layer["attn_norm"]["scale"], cfg.rms_norm_eps)
            q, k, v = sink_project(layer, h, ropes, positions, cfg, window)
        with tracing.part("kv_write"):
            if window:
                kw = kw.at[j, rows[True], offs].set(_key_rows(k, kw))
                vw = vw.at[j, rows[True], offs].set(v.astype(vw.dtype))
            else:
                kf = kf.at[j, rows[False], offs].set(_key_rows(k, kf))
                vf = vf.at[j, rows[False], offs].set(v.astype(vf.dtype))
        if blocked:
            with tracing.part("attention"):
                att = gqa_prefill_attention(
                    q.reshape(N, Tp, -1), k.reshape(N, Tp, -1),
                    v.reshape(N, Tp, -1), n_kv_heads=cfg.kv_heads(window),
                    window=cfg.sliding_window if window else None,
                    sink=layer.get("sink"))
        else:
            mask = jnp.broadcast_to(
                sink_reach(idx[:, None], idx[None, :], cfg, window),
                (N, Tp, Tp))
            att = masked_attention(q, k, v, mask, layer.get("sink"))
        x = x + sink_attn_out(layer, att)
        with tracing.part("project"):
            g = rms_norm(x, layer["ffn_norm"]["scale"], cfg.rms_norm_eps)
        x = x + sink_ffn(layer, g, cfg, valid=valid)[0]
    logits = sink_logits(params, last_rows(x, true_lens), cfg)
    return _sample_tail(logits, temps, key), kf, vf, kw, vw


PROGRAMS = ServePrograms(
    family="sink_moe", make_cache=make_pools,
    decode_multi=sink_moe_decode_multi,
    prefill_batch=sink_moe_prefill_batch, init=sink_moe_init,
    stats=MOE_STATS, decode_in_place=lambda cache: _reads_in_place(),
    page_kinds=page_kinds, prefill_wave_limit=WAVE_LIMIT,
    caches="full layers' pages and a ring of the window layers' pages, each "
           "kind with its own KV heads, keys wider than values")

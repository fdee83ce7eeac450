"""The serving programs of ``models/cca_moe.py`` for the continuous-batching
engine: same slots, blocks and loop as the other families, and a cache of two
kinds that EVERY layer owns both of.

* **Two kinds, one layer.** A layer's keys (as they are attended: after the
  mean, the convolutions, the norm, the temperature and the rotation) and
  values live in pools ``[L, P, PS, KV, hd]`` and a slot holds ``ceil(n /
  PS)`` of those pages, as a Llama slot does — at 2 key heads of 128, a
  quarter of the bytes. Beside them the layer keeps, for a slot, ONE row that
  does not grow: ``u``, ``c0`` and ``v2`` of the position before (``ops/
  cca.py``: what the two convolutions and the shifted value need of the
  past), a pool ``[L, R, row width]`` of ROWS. The engine draws a slot one
  row as it draws pages — a kind of page whose table has one entry
  (``page_kinds``), as ``llm/ssm_moe.py``'s state — and learns nothing about
  it. Row 0 is the junk row, as page 0 is the junk page: dead decode slots
  and a wave's dummy prompts write there and no live slot reads it.
* **Decode** advances each live slot's row one position a step, exactly, and
  writes the position's ``k``, ``v`` into its page. The rows are updated
  where they lie: the step's new rows are laid out by row, a row of no live
  slot stays bit for bit (``_mix_step``; a scatter of the slots' rows became
  a loop over the slots in ``llm/kda_moe.py``). Attention reads the pages
  where they lie (``ops/paged_attention.py``) or, off the TPU, the gathered
  table with a position mask: one switch, the seam's rule bound here as
  ``_reads_in_place``.
* **Prefill** is whole-prompt per pad bucket from NOTHING before position 0
  (a reused row is overwritten, never read), blocked attention over the fresh
  keys where the pad is whole blocks, and the row written **at each prompt's
  true length** — pad positions leave nothing. A wave holds at most
  ``WAVE_LIMIT`` prompts and tokens.
* **The expert sublayer** routes by ``parallel/moe.py`` ``mlp_top1_route``,
  whose stream ``r`` runs through the layer loop beside the residual — inside
  a step, never across steps — and hands ONE expert a token to
  ``moe_experts``: a step's rows and a short wave's stream their experts
  through ``ops/grouped_swiglu.py``, longer waves go through ``ragged_dot``
  (``_streams_experts``). At one expert a token the sorted rows ARE the
  tokens (33 MB at 8,192 x 2048), so no wave is chunked. ``MOE_STATS`` and
  ``cca_row_updates`` (rows read and written a step) ride back with the
  tokens.

LoRA, int8 pools, speculative decoding, suffix prefill and page export take
a prefix of a slot's pages for a prefix of its sequence, which it is not
while a row stands beside them; ``llm/engine.py`` refuses them for this
family by name.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.llm.programs import (
    MOE_STATS, PageKind, ServePrograms, _sample_tail, decode_frame, last_rows,
    moe_load_stats, reads_in_place)
from ray_tpu.models.cca_moe import (
    CcaMoeConfig, cca_experts, cca_in, cca_moe_init, cca_moe_logits, cca_out,
    cca_rope)
from ray_tpu.ops import cca
from ray_tpu.ops.attention import gathered_attention, masked_attention
from ray_tpu.ops.paged_attention import paged_decode_attention, run_lengths
from ray_tpu.ops.prefill_attention import blocks_for, gqa_prefill_attention
from ray_tpu.utils import tracing

# the most prompts and tokens one prefill program may hold
WAVE_LIMIT = (8, 8192)
# after MOE_STATS: rows read and written a step, live slots x layers
STATS = MOE_STATS + ("cca_row_updates",)
# The seam's platform rule under this module's own name, asked through this
# global by every program here and by ``PROGRAMS.decode_in_place``: ``tests/``
# ASSIGN an answer here to run the kernels interpreted.
_reads_in_place = reads_in_place


def page_kinds(cfg: CcaMoeConfig, page_size: int, max_seq_len: int):
    """What a slot holds of each kind (``ServePrograms.page_kinds``): K and V
    pages AND one row, which holds no positions, of every layer."""
    return (PageKind("kv", cfg.n_layers, -(-max_seq_len // page_size)),
            PageKind("row", cfg.n_layers, 1, positions=False))


def make_pools(cfg: CcaMoeConfig, page_size: int, n_pages, kv_dtype):
    """The model's cache: (K, V, rows). ``n_pages``: one count for both
    kinds, or ``{"kv": pages, "row": rows}``."""
    if kv_dtype not in (None, "native", "bf16"):
        raise ValueError(f"unknown kv_dtype {kv_dtype!r}")
    dtype = jnp.bfloat16 if kv_dtype == "bf16" else jnp.dtype(cfg.dtype)
    if not isinstance(n_pages, dict):
        n_pages = {"kv": n_pages, "row": n_pages}
    kv = (cfg.n_layers, n_pages["kv"], page_size, cfg.n_kv_heads, cfg.head_dim)
    return (jnp.zeros(kv, dtype), jnp.zeros(kv, dtype),
            jnp.zeros((cfg.n_layers, n_pages["row"], cca.row_width(cfg)),
                      jnp.dtype(cfg.dtype)))


@tracing.part("mix")
def _mix_step(layer, z, j, row, owner, owned, rows, cos, sin, pos,
              cfg: CcaMoeConfig):
    """One position of layer ``j``'s mixing for every slot, through the
    slots' rows. z: [B, 1, in_width]; row: [B] int32 (0, the junk row, for a
    slot that is not live); owner: [R] int32, the slot that holds each row,
    and owned: [R] bool, whether a LIVE slot does. Every row of the layer
    takes its slot's new row where it lies; a row of no live slot stays.
    Returns (q, k, v, rows)."""
    old = rows[j]
    q, k, v, new = cca.cca_mix_step(layer, z, old[row], cos, sin, pos, cfg)
    rows = rows.at[j].set(jnp.where(owned[:, None], new[owner], old))
    return q, k, v, rows


def _decode_body(params, tokens, pos, tables, cache, active, temps, key,
                 cfg: CcaMoeConfig, runs):
    """One decode step for every slot (masked where inactive); ``runs`` is
    the K/V table's ``run_lengths`` (None where the kernels do not run).
    Returns (next_tok [B], cache, stats)."""
    t_kv, t_row = tables
    kp, vp, rows = cache
    B, PS = tokens.shape[0], kp.shape[2]
    cos, sin = cca_rope(cfg)
    off = pos % PS
    page = jnp.take_along_axis(t_kv, (pos // PS)[:, None], axis=1)[:, 0]
    row = jnp.where(active, t_row[:, 0], 0)  # a dead slot: the junk row
    # each row's slot, and whether a live one holds it: the junk row's is
    # whichever dead slot wrote last, and never live
    R = rows.shape[1]
    owner = jnp.zeros((R,), jnp.int32).at[row].set(
        jnp.arange(B, dtype=jnp.int32))
    owned = jnp.zeros((R,), bool).at[row].set(active)
    lengths = jnp.where(active, pos + 1, 0)
    in_place = _reads_in_place()
    loads, r = [], None  # the router's stream: this step's own, layer to layer
    with tracing.part("embed"):
        x = params["tok"]["embedding"][tokens][:, None, :]
    for j in range(cfg.n_layers):
        layer = params[f"layers_{j}"]
        q, k, v, rows = _mix_step(layer, cca_in(layer, x, cfg), j, row, owner,
                                  owned, rows, cos, sin, pos, cfg)
        with tracing.part("kv_write"):
            kp = kp.at[j, page, off].set(k[:, 0].astype(kp.dtype))
            vp = vp.at[j, page, off].set(v[:, 0].astype(vp.dtype))
        if in_place:
            with tracing.part("attention"):
                att = paged_decode_attention(
                    q[:, 0].astype(kp.dtype), kp, vp, j, t_kv, lengths,
                    runs=runs)
                att = att.reshape(B, 1, -1).astype(x.dtype)
        else:
            att = gathered_attention(q, kp[j], vp[j], t_kv, pos)
        x = cca_out(layer, x, att)
        x, r, load = cca_experts(layer, x, r, cfg, valid=active[:, None])
        loads.append(load)
    logits = cca_moe_logits(params, x[:, 0], cfg)
    next_tok = _sample_tail(logits, temps, key)
    stats = jnp.concatenate([
        moe_load_stats(loads, B),
        (active.sum() * cfg.n_layers).astype(jnp.int32)[None]])
    return (jnp.where(active, next_tok, 0), (kp, vp, rows), stats)


@partial(jax.jit, static_argnames=("cfg", "n_steps"), donate_argnums=(6, 7, 8))
def cca_moe_decode_multi(params, loras, aids, tokens, seq_lens, tables, kp, vp,
                         rows, active, temps, key, cfg: CcaMoeConfig,
                         n_steps: int):
    """``n_steps`` fused decode steps as one device program: the contract of
    ``ServePrograms.decode_multi`` with one table a kind (K/V pages, rows)
    and three pools, rows of ``[B tokens | STATS]``. ``loras``/``aids`` are
    the engine's (None / zeros here: refused at construction)."""
    runs = run_lengths(tables[0]) if _reads_in_place() else None
    return decode_frame(_decode_body, params, tokens, seq_lens, tables,
                        (kp, vp, rows), active, temps, key, cfg, n_steps, runs)


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(5, 6, 7))
def cca_moe_prefill_batch(params, loras, aids, tokens, pages, kp, vp, rows,
                          true_lens, temps, key, cfg: CcaMoeConfig):
    """Prefill a whole admission wave as one batched forward: the contract
    of ``ServePrograms.prefill_batch`` with ``pages`` one array a kind — K/V
    ``[N, pad / PS]``, row ``[N, 1]``. Every prompt's row is written at its
    true length. Returns (first tokens [N], the three pools)."""
    p_kv, p_row = pages
    N, Tp = tokens.shape
    PS = kp.shape[2]
    cos, sin = cca_rope(cfg)
    idx = jnp.arange(Tp)
    positions = jnp.broadcast_to(idx[None, :], (N, Tp))
    at = p_kv[:, idx // PS]
    offs = jnp.broadcast_to(idx % PS, (N, Tp))
    valid = idx[None, :] < true_lens[:, None]  # padding is routed nowhere
    row = p_row[:, 0]
    blocked = _reads_in_place() and blocks_for(Tp) is not None
    r = None
    with tracing.part("embed"):
        x = params["tok"]["embedding"][tokens]
    for j in range(cfg.n_layers):
        layer = params[f"layers_{j}"]
        z = cca_in(layer, x, cfg)
        with tracing.part("mix"):
            q, k, v, left = cca.cca_mix(layer, z, cos, sin, positions, cfg,
                                        tails=true_lens)
            rows = rows.at[j, row].set(left.astype(rows.dtype))
        with tracing.part("kv_write"):
            kp = kp.at[j, at, offs].set(k.astype(kp.dtype))
            vp = vp.at[j, at, offs].set(v.astype(vp.dtype))
        if blocked:
            with tracing.part("attention"):
                att = gqa_prefill_attention(
                    q.reshape(N, Tp, -1), k.reshape(N, Tp, -1),
                    v.reshape(N, Tp, -1), n_kv_heads=cfg.n_kv_heads)
        else:
            att = masked_attention(q, k, v, jnp.broadcast_to(
                idx[:, None] >= idx[None, :], (N, Tp, Tp)))
        x = cca_out(layer, x, att)
        x, r, _ = cca_experts(layer, x, r, cfg, valid=valid)
    logits = cca_moe_logits(params, last_rows(x, true_lens), cfg)
    return _sample_tail(logits, temps, key), kp, vp, rows


PROGRAMS = ServePrograms(
    family="cca_moe", make_cache=make_pools,
    decode_multi=cca_moe_decode_multi, prefill_batch=cca_moe_prefill_batch,
    init=cca_moe_init, stats=STATS,
    decode_in_place=lambda cache: _reads_in_place(), page_kinds=page_kinds,
    prefill_wave_limit=WAVE_LIMIT,
    caches="K and V pages of every layer and, beside them, one row of every "
           "layer that holds the position before and no positions")

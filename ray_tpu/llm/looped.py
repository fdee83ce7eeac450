"""The serving programs of ``models/looped.py`` for the continuous-batching
engine: same slots, blocks and loop as the other families, and a cache with
MORE PLANES THAN THE MODEL HAS LAYERS.

* **A plane a pass a layer.** The stack of ``n_layers`` layers runs
  ``n_passes`` times a token with the same weights, and pass ``u`` of layer
  ``l`` attends the keys and values that pass ``u`` of layer ``l`` made —
  never another pass's. So the pools are ``[n_passes * n_layers, P, PS, KV,
  hd]``: 192 planes under 48 layers of weights, plane ``u * n_layers + l``,
  the same position at every pass. A slot holds ``ceil(n / PS)`` pages, each
  a page of EVERY plane (1.5 MiB a position at the published widths: the
  pages, not the slots, bound the batch). The engine keeps one table and one
  free list and learns nothing of this (``page_kinds``: one kind whose reads
  weigh ``planes`` layers).
* **The passes are a loop of the program**, not 192 layers of text: one
  ``lax.fori_loop`` over ``u`` whose body holds the 48 layers once, the carry
  the residual, the pools and the exit rule's state. The plane is a value of
  that loop: ``ops/paged_attention.py`` takes the layer to read as a
  prefetched scalar and the page write as a scatter's index, so a traced
  plane costs neither anything. The pools are updated in place through both
  loops (the steps' scan, the passes' loop).
* **Decode** writes each live slot's new row into its plane's page and reads
  the plane's pages where they lie (``paged_decode_attention``) or, off the
  TPU, the gathered table with a position mask: one switch, the seam's rule
  bound here as ``_reads_in_place``. A pass closes with the final norm and
  the gate; the exit rule picks, a slot, the state the head reads — ONCE,
  after the loop: every pass runs whatever it picks.
* **Prefill** is whole-prompt per pad bucket, the same loop with whole
  sequences, blocked attention over the fresh keys where the pad is whole
  blocks and the masked plain form elsewhere; the gate is asked at each
  prompt's last position alone. A wave holds at most ``WAVE_LIMIT`` prompts
  and tokens.

LoRA, int8 pools, speculative decoding, suffix prefill, prefix sharing and
page export take a plane a weight layer; ``llm/engine.py`` refuses them for
this family by name.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.llm.programs import (
    PageKind, ServePrograms, _sample_tail, decode_frame, last_rows,
    reads_in_place)
from ray_tpu.models.looped import (
    LoopedConfig, looped_attn_out, looped_close, looped_exit,
    looped_exit_start, looped_ffn, looped_init, looped_logits, looped_project,
    looped_rope)
from ray_tpu.ops.attention import gathered_attention, masked_attention
from ray_tpu.ops.paged_attention import paged_decode_attention, run_lengths
from ray_tpu.ops.prefill_attention import blocks_for, gqa_prefill_attention
from ray_tpu.utils import tracing

# the most prompts and tokens one prefill program may hold
WAVE_LIMIT = (4, 1536)
# after the tokens of a step's row: live slots (a share of the engine's
# steps x max_batch) and the exit passes of the live slots summed
LOOPED_STATS = ("looped_live_slots", "looped_exit_depth")
# The seam's platform rule under this module's own name, asked through this
# global by every program here and by ``PROGRAMS.decode_in_place``: ``tests/``
# ASSIGN an answer here to run the kernels interpreted.
_reads_in_place = reads_in_place


def page_kinds(cfg: LoopedConfig, page_size: int, max_seq_len: int):
    """One kind of page, of every plane (``ServePrograms.page_kinds``)."""
    return (PageKind("kv", cfg.planes, -(-max_seq_len // page_size)),)


def make_pools(cfg: LoopedConfig, page_size: int, n_pages, kv_dtype):
    """The model's cache: (K, V), a plane a pass a layer."""
    if kv_dtype not in (None, "native", "bf16"):
        raise ValueError(f"unknown kv_dtype {kv_dtype!r}")
    dtype = jnp.bfloat16 if kv_dtype == "bf16" else jnp.dtype(cfg.dtype)
    if isinstance(n_pages, dict):
        n_pages = n_pages["kv"]
    shape = (cfg.planes, n_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def _passes(params, x, kp, vp, cfg: LoopedConfig, rows, layer_half):
    """The model's ``n_passes`` passes as ONE traced body. ``layer_half(layer,
    x, kp, vp, plane) -> (x, kp, vp)``: a layer's first half — project, write
    the plane, attend it, ``W_o`` — as the program has it; ``rows``: the rows
    of a pass's closed state whose exit is asked for. Returns (the chosen
    states, their passes, kp, vp)."""
    def one_pass(u, carry):
        x, kp, vp, state = carry
        for l in range(cfg.n_layers):
            layer = params[f"layers_{l}"]
            x, kp, vp = layer_half(layer, x, kp, vp, u * cfg.n_layers + l)
            x = looped_ffn(layer, x, cfg)
        x, g, lam = looped_close(params, x, cfg, rows)
        return x, kp, vp, looped_exit(state, g, lam, u, cfg)

    _, kp, vp, (_, _, chosen, depth) = jax.lax.fori_loop(
        0, cfg.n_passes, one_pass, (x, kp, vp, looped_exit_start(rows(x))))
    return chosen, depth, kp, vp


def _decode_body(params, tokens, pos, tables, cache, active, temps, key,
                 cfg: LoopedConfig, runs):
    """One decode step for every slot (masked where inactive); ``runs`` is
    the table's ``run_lengths`` (None where the kernel does not run).
    Returns (next_tok [B], cache, stats)."""
    kp, vp = cache
    B, PS = tokens.shape[0], kp.shape[2]
    cos, sin = looped_rope(cfg)
    positions = pos[:, None]
    off = pos % PS
    # a dead slot's table is zeros: it writes the junk page
    page = jnp.take_along_axis(tables, (pos // PS)[:, None], axis=1)[:, 0]
    lengths = jnp.where(active, pos + 1, 0)
    in_place = _reads_in_place()

    def layer_half(layer, x, kp, vp, plane):
        q, k, v = looped_project(layer, x, cos, sin, positions, cfg)
        with tracing.part("kv_write"):
            kp = kp.at[plane, page, off].set(k[:, 0].astype(kp.dtype))
            vp = vp.at[plane, page, off].set(v[:, 0].astype(vp.dtype))
        if in_place:
            with tracing.part("attention"):
                att = paged_decode_attention(
                    q[:, 0].astype(kp.dtype), kp, vp, plane, tables, lengths,
                    runs=runs)
                att = att.reshape(B, 1, -1).astype(x.dtype)
        else:
            att = gathered_attention(q, kp[plane], vp[plane], tables, pos)
        return looped_attn_out(layer, x, att, cfg), kp, vp

    with tracing.part("embed"):
        x = params["tok"]["embedding"][tokens][:, None, :]
    chosen, depth, kp, vp = _passes(params, x, kp, vp, cfg,
                                    lambda h: h[:, 0], layer_half)
    next_tok = _sample_tail(looped_logits(params, chosen), temps, key)
    with tracing.part("head"):
        stats = jnp.stack([active.sum(), jnp.where(active, depth, 0).sum()]
                          ).astype(jnp.int32)
    return jnp.where(active, next_tok, 0), (kp, vp), stats


@partial(jax.jit, static_argnames=("cfg", "n_steps"), donate_argnums=(6, 7))
def looped_decode_multi(params, loras, aids, tokens, seq_lens, tables, kp, vp,
                        active, temps, key, cfg: LoopedConfig, n_steps: int):
    """``n_steps`` fused decode steps as one device program: the contract of
    ``ServePrograms.decode_multi`` with two pools of ``cfg.planes`` planes,
    rows of ``[B tokens | LOOPED_STATS]``. ``loras``/``aids`` are the
    engine's (None / zeros here: refused at construction)."""
    runs = run_lengths(tables) if _reads_in_place() else None
    return decode_frame(_decode_body, params, tokens, seq_lens, tables,
                        (kp, vp), active, temps, key, cfg, n_steps, runs)


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(5, 6))
def looped_prefill_batch(params, loras, aids, tokens, pages, kp, vp,
                         true_lens, temps, key, cfg: LoopedConfig):
    """Prefill a whole admission wave as one batched forward: the contract
    of ``ServePrograms.prefill_batch``; every pass writes its own planes'
    rows. Returns (first tokens [N], the two pools)."""
    N, Tp = tokens.shape
    PS = kp.shape[2]
    cos, sin = looped_rope(cfg)
    idx = jnp.arange(Tp)
    positions = jnp.broadcast_to(idx[None, :], (N, Tp))
    at = pages[:, idx // PS]
    offs = jnp.broadcast_to(idx % PS, (N, Tp))
    blocked = _reads_in_place() and blocks_for(Tp) is not None
    causal = None if blocked else jnp.broadcast_to(
        idx[:, None] >= idx[None, :], (N, Tp, Tp))

    def layer_half(layer, x, kp, vp, plane):
        q, k, v = looped_project(layer, x, cos, sin, positions, cfg)
        with tracing.part("kv_write"):
            kp = kp.at[plane, at, offs].set(k.astype(kp.dtype))
            vp = vp.at[plane, at, offs].set(v.astype(vp.dtype))
        if blocked:
            with tracing.part("attention"):
                att = gqa_prefill_attention(
                    q.reshape(N, Tp, -1), k.reshape(N, Tp, -1),
                    v.reshape(N, Tp, -1), n_kv_heads=cfg.n_kv_heads)
        else:
            att = masked_attention(q, k, v, causal)
        return looped_attn_out(layer, x, att, cfg), kp, vp

    with tracing.part("embed"):
        x = params["tok"]["embedding"][tokens]
    chosen, _, kp, vp = _passes(params, x, kp, vp, cfg,
                                lambda h: last_rows(h, true_lens), layer_half)
    return _sample_tail(looped_logits(params, chosen), temps, key), kp, vp


PROGRAMS = ServePrograms(
    family="looped", make_cache=make_pools,
    decode_multi=looped_decode_multi, prefill_batch=looped_prefill_batch,
    init=looped_init, stats=LOOPED_STATS,
    decode_in_place=lambda cache: _reads_in_place(), page_kinds=page_kinds,
    prefill_wave_limit=WAVE_LIMIT,
    caches="K and V pages of every layer at every pass")

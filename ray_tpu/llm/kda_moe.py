"""The serving programs of ``models/kda_moe.py`` for the continuous-batching
engine: same slots, blocks and loop as the other families, a cache of two
kinds of which ONE DOES NOT GROW, and a mixer by the layer's place.

* **Two kinds.** The MLA layers' latent rows live in one pool ``[n_mla, P, PS,
  r + rope]`` and a slot holds ``ceil(n / PS)`` of those pages, as a slot of
  ``llm/mla_moe.py`` does. The KDA layers keep, for a slot, a state ``[heads,
  128, 128]`` in float32 and the convolution's last ``K - 1`` inputs, whatever
  the slot's length: pools ``[n_kda, R, ...]`` of ROWS, and the engine draws a
  slot one row of them as it draws pages — the state is a kind of page whose
  table has one entry (``page_kinds``), as ``llm/ssm_moe.py``'s. Row 0 is the
  junk row, as page 0 is the junk page: dead decode slots and a wave's dummy
  prompts write there. The conv rows lie flat, ``[R, (K - 1) . 3 . d_inner]``.
* **Decode** advances each live slot's row one position a step, exactly. The
  state is updated where it lies: the step's small inputs are laid out by
  row, every row takes the update (``beta`` 0 and decay 1 where no live slot
  owns it: unchanged bit for bit), and only the outputs come back by slot
  (``_kda_step``). On a TPU that is ONE pass over a layer's pool —
  ``ops/kda_pool.py``'s kernel reads a row, decays it, takes the delta,
  reads it out and writes it; anywhere else ``ops/kda.py``'s one-step form,
  plain XLA operations and the kernel's reference. The MLA layers write the
  new latent row and attend the slot's rows absorbed, in the pool page by
  page (``paged_latent_attention``) or over the gathered window: one switch,
  the seam's rule bound here as ``_reads_in_place``.
* **Prefill** is whole-prompt per pad bucket: the chunked scan from a zero
  state (a reused row is overwritten; on a TPU ``ops/kda_chunk.py``'s kernel,
  else ``ops/kda.py``'s plain form), expanded attention over the wave's fresh
  latent rows, and the state written **at each prompt's true length** —
  positions at or past it get ``beta`` 0 and decay 1, the convolution's saved
  inputs are the last ``K - 1`` true ones (zeros where the prompt is
  shorter). A wave holds at most ``WAVE_LIMIT`` prompts and tokens.
* **The expert layer** routes over all experts inside the token's routing
  groups and computes the held ones' part plus the shared expert: a decode
  step's rows stream their touched experts through ``ops/grouped_swiglu.py``,
  a prompt's go through ``ragged_dot`` (``parallel/moe.py``
  ``_streams_experts``). ``MOE_STATS``, ``delta_updates`` (state rows read and
  written a step) and ``moe_tokens_here`` (live tokens that chose any held
  expert, summed over expert layers) ride back with the tokens.

LoRA, int8 pools, speculative decoding, suffix prefill and page export take
a prefix of a slot's pages for a prefix of its sequence, which a state is
not; ``llm/engine.py`` refuses them for this family by name.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.llm.programs import (
    MOE_STATS, PageKind, ServePrograms, _sample_tail, decode_frame, last_rows,
    moe_load_stats, reads_in_place)
from ray_tpu.models.kda_moe import (
    KDA, MLA, KdaMoeConfig, conv_taps, dense_ffn, ffn_norm, kda_decay, kda_in,
    kda_mixer, kda_moe_ffn, kda_moe_init, kda_moe_logits, kda_out, kda_qkv,
    mixer_out, mla_in, route_kw)
from ray_tpu.ops import kda, ssm
from ray_tpu.ops.basic import rope_freqs
from ray_tpu.ops.kda_pool import kda_pool_step
from ray_tpu.ops.mla import (
    mla_absorb, mla_attend_absorbed, mla_attend_expanded, mla_expand)
from ray_tpu.ops.paged_attention import paged_latent_attention
from ray_tpu.parallel.moe import moe_experts, moe_route, tokens_here
from ray_tpu.utils import tracing

# the most prompts and tokens one prefill program may hold, as the other
# expert families
WAVE_LIMIT = (8, 16384)
# after MOE_STATS: state rows read and written, and live tokens that chose at
# least one held expert (summed over the expert layers)
STATS = MOE_STATS + ("delta_updates", "moe_tokens_here")
# The seam's platform rule under this module's own name, asked through this
# global by every program here and by ``PROGRAMS.decode_in_place``: ``tests/``
# ASSIGN an answer here to run the kernels interpreted.
_reads_in_place = reads_in_place


def page_kinds(cfg: KdaMoeConfig, page_size: int, max_seq_len: int):
    """What a slot holds of each kind (``ServePrograms.page_kinds``): latent
    pages of the MLA layers, and ONE row of the KDA layers' state, which
    holds no positions."""
    return (PageKind("latent", len(cfg.layers_of(MLA)),
                     -(-max_seq_len // page_size)),
            PageKind("state", len(cfg.layers_of(KDA)), 1, positions=False))


def make_pools(cfg: KdaMoeConfig, page_size: int, n_pages, kv_dtype):
    """The model's cache: (latent pool, states, conv rows). ``n_pages``: one
    count for both kinds, or ``{"latent": pages, "state": rows}``."""
    if kv_dtype not in (None, "native", "bf16"):
        raise ValueError(f"unknown kv_dtype {kv_dtype!r}")
    dtype = jnp.bfloat16 if kv_dtype == "bf16" else jnp.dtype(cfg.dtype)
    if not isinstance(n_pages, dict):
        n_pages = {"latent": n_pages, "state": n_pages}
    n_kda, rows = len(cfg.layers_of(KDA)), n_pages["state"]
    return (jnp.zeros((len(cfg.layers_of(MLA)), n_pages["latent"], page_size,
                       cfg.latent_width), dtype),
            jnp.zeros((n_kda, rows, cfg.n_heads, cfg.head_dim, cfg.head_dim),
                      jnp.float32),
            jnp.zeros((n_kda, rows, (cfg.conv_kernel - 1) * cfg.conv_width),
                      jnp.dtype(cfg.dtype)))


def _kda_step(layer, x, j, row, owner, owned, states, convs,
              cfg: KdaMoeConfig):
    """One position of KDA layer ``j`` (its place among the KDA layers) for
    every slot, through the slots' rows. x: [B, 1, D]; row: [B] int32 (0,
    the junk row, for a slot that is not live); owner: [R] int32, the slot
    that holds each row, and owned: [R] bool, whether a LIVE slot does
    (``_decode_body`` finds both once a step). The state pool is updated
    WHERE IT LIES, as ``llm/ssm_moe.py`` ``_mamba_step`` does and for its
    reasons: the step's small inputs (q, k, v, g, beta of a slot) are laid
    out by row, every row of the layer's pool takes the update — a row of
    no live slot has ``g`` 0 and ``beta`` 0, so it stays bit for bit — and
    only the outputs are gathered back by slot. Returns (y [B, 1, D],
    states, convs)."""
    B, R = x.shape[0], states.shape[1]
    C = cfg.conv_width
    u, a, beta, gate = kda_in(layer, x, cfg)

    def by_row(a):  # [B, ...] of the slots -> [R, ...] of the rows
        return jnp.where(owned.reshape((R,) + (1,) * (a.ndim - 1)),
                         a[owner], 0)

    with tracing.part("conv"):
        # the conv rows shift where they lie too: every row of the layer
        # drops its oldest input and takes its slot's new one, a row of no
        # live slot stays. (A scatter of the slots' rows into the pool became
        # a loop over the slots, 96 turns a layer a step.)
        old = convs[j]
        window = jnp.concatenate(
            [old[row].reshape(B, cfg.conv_kernel - 1, C), u], axis=1)
        xc = ssm.conv_step(window, *conv_taps(layer, cfg))
        shifted = jnp.concatenate([old[:, C:], by_row(u[:, 0])], axis=1)
        convs = convs.at[j].set(jnp.where(owned[:, None], shifted, old))
    with tracing.part("delta"):
        step = tuple(by_row(t) for t in (
            *kda_qkv(xc, cfg), *kda_decay(layer, a[:, 0], beta[:, 0], cfg)))
        if _reads_in_place():
            states, o = kda_pool_step(states, j, *step)
        else:
            S, o = kda.kda_step(states[j], *step)
            states = states.at[j].set(S)
        y = kda_out(layer, o[row], gate[:, 0], cfg, x.dtype)
    return mixer_out(layer, y)[:, None], states, convs


def _ffn_step(layer, x, cfg: KdaMoeConfig, valid):
    """A decode step's feed-forward on the residual x [B, 1, D]: ``models/
    kda_moe.py`` ``kda_moe_ffn`` with the router's choices in hand, so that
    the tokens that chose a held expert are counted. Returns (x, load,
    here), the last two None for a dense layer."""
    h = ffn_norm(layer, x, cfg)
    if "moe" not in layer:
        return x + dense_ffn(layer, h), None, None
    with tracing.part("ffn"):
        flat, ok = h[:, 0], valid[:, 0]
        idx, w = moe_route(flat, layer["moe"], **route_kw(cfg))
        y, load = moe_experts(flat, idx, w, layer["moe"], cfg.held, ok)
        with tracing.part("router"):
            here = tokens_here(idx, cfg.held, ok)
    return x + y[:, None], load, here


def _decode_body(params, tokens, pos, tables, cache, active, temps, key,
                 cfg: KdaMoeConfig):
    """One decode step for every slot (masked where inactive). Returns
    (next_tok [B], cache, stats)."""
    t_lat, t_state = tables
    pool, states, convs = cache
    B = tokens.shape[0]
    PS, W = pool.shape[2:]
    MAXP = t_lat.shape[1]
    cos, sin = rope_freqs(cfg.qk_rope_head_dim, cfg.max_seq_len, cfg.rope_theta)
    positions = pos[:, None]
    page = jnp.take_along_axis(t_lat, (pos // PS)[:, None], axis=1)[:, 0]
    off = pos % PS
    row = jnp.where(active, t_state[:, 0], 0)  # a dead slot: the junk row
    # each row's slot, and whether a live one holds it: the junk row's is
    # whichever dead slot wrote last, and never live
    R = states.shape[1]
    owner = jnp.zeros((R,), jnp.int32).at[row].set(jnp.arange(B, dtype=jnp.int32))
    owned = jnp.zeros((R,), bool).at[row].set(active)
    in_place = _reads_in_place()
    if in_place:
        lengths = jnp.where(active, pos + 1, 0)
    else:
        mask = jnp.arange(MAXP * PS)[None, None, :] <= pos[:, None, None]
    at = {KDA: 0, MLA: 0}  # the layer's place in its kind's pools
    loads, here = [], []
    with tracing.part("embed"):
        x = params["tok"]["embedding"][tokens][:, None, :]
    for i in range(cfg.n_layers):
        layer, kind = params[f"layers_{i}"], cfg.mixer(i)
        j, at[kind] = at[kind], at[kind] + 1
        if kind == KDA:
            y, states, convs = _kda_step(
                layer, x, j, row, owner, owned, states, convs, cfg)
        else:
            q, latent, gate = mla_in(layer, x, cos, sin, positions, cfg)
            with tracing.part("kv_write"):
                pool = pool.at[j, page, off].set(latent[:, 0].astype(pool.dtype))
            with tracing.part("attention"):
                if in_place:
                    o_lat = paged_latent_attention(
                        mla_absorb(layer, q, cfg)[:, 0].astype(pool.dtype),
                        pool, j, t_lat, lengths, v_width=cfg.kv_lora_rank,
                        sm_scale=cfg.qk_head_dim ** -0.5)
                    att = mla_expand(layer, o_lat[:, None].astype(x.dtype),
                                     cfg, gate)
                else:
                    window = pool[j][t_lat].reshape(
                        B, MAXP * PS, W).astype(x.dtype)
                    att = mla_attend_absorbed(layer, q, window, mask, cfg, gate)
            y = mixer_out(layer, att.astype(x.dtype))
        x, load, n_here = _ffn_step(layer, x + y, cfg, active[:, None])
        if load is not None:
            loads.append(load)
            here.append(n_here)
    logits = kda_moe_logits(params, x[:, 0], cfg)
    next_tok = _sample_tail(logits, temps, key)
    stats = jnp.concatenate([
        moe_load_stats(loads, B * cfg.n_experts_per_tok),
        jnp.stack([active.sum() * at[KDA], sum(here)]).astype(jnp.int32)])
    return (jnp.where(active, next_tok, 0), (pool, states, convs), stats)


@partial(jax.jit, static_argnames=("cfg", "n_steps"), donate_argnums=(6, 7, 8))
def kda_moe_decode_multi(params, loras, aids, tokens, seq_lens, tables, pool,
                         states, convs, active, temps, key, cfg: KdaMoeConfig,
                         n_steps: int):
    """``n_steps`` fused decode steps as one device program: the contract of
    ``ServePrograms.decode_multi`` with one table a kind (latent pages, state
    rows) and three pools, rows of ``[B tokens | STATS]``. ``loras``/``aids``
    are the engine's (None / zeros here: refused at construction)."""
    return decode_frame(_decode_body, params, tokens, seq_lens, tables,
                        (pool, states, convs), active, temps, key, cfg,
                        n_steps)


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(5, 6, 7))
def kda_moe_prefill_batch(params, loras, aids, tokens, pages, pool, states,
                          convs, true_lens, temps, key, cfg: KdaMoeConfig):
    """Prefill a whole admission wave as one batched forward: the contract
    of ``ServePrograms.prefill_batch`` with ``pages`` one array a kind —
    latent ``[N, pad / PS]``, state ``[N, 1]``. Every prompt's state row is
    written at its true length; attention is over the wave's FRESH latent
    rows, expanded. Returns (first tokens [N], the three pools)."""
    p_lat, p_state = pages
    N, Tp = tokens.shape
    PS = pool.shape[2]
    cos, sin = rope_freqs(cfg.qk_rope_head_dim, cfg.max_seq_len, cfg.rope_theta)
    idx = jnp.arange(Tp)
    positions = jnp.broadcast_to(idx[None, :], (N, Tp))
    mask = jnp.broadcast_to(idx[None, :, None] >= idx[None, None, :],
                            (N, Tp, Tp))
    rows = p_lat[:, idx // PS]
    offs = jnp.broadcast_to(idx % PS, (N, Tp))
    valid = idx[None, :] < true_lens[:, None]  # padding moves no state
    row = p_state[:, 0]                        # and is routed nowhere
    at = {KDA: 0, MLA: 0}
    with tracing.part("embed"):
        x = params["tok"]["embedding"][tokens]
    for i in range(cfg.n_layers):
        layer, kind = params[f"layers_{i}"], cfg.mixer(i)
        j, at[kind] = at[kind], at[kind] + 1
        if kind == KDA:
            y, S, saved = kda_mixer(layer, x, cfg, valid, tails=true_lens,
                                    kernel=_reads_in_place())
            with tracing.part("delta"):
                states = states.at[j, row].set(S)
            with tracing.part("conv"):
                convs = convs.at[j, row].set(saved.reshape(N, -1))
        else:
            q, latent, gate = mla_in(layer, x, cos, sin, positions, cfg)
            with tracing.part("kv_write"):
                pool = pool.at[j, rows, offs].set(latent.astype(pool.dtype))
            y = mixer_out(layer, mla_attend_expanded(
                layer, q, latent, mask, cfg, gate).astype(x.dtype))
        x, _ = kda_moe_ffn(layer, x + y, cfg, valid=valid)
    logits = kda_moe_logits(params, last_rows(x, true_lens), cfg)
    return _sample_tail(logits, temps, key), pool, states, convs


PROGRAMS = ServePrograms(
    family="kda_moe", make_cache=make_pools,
    decode_multi=kda_moe_decode_multi, prefill_batch=kda_moe_prefill_batch,
    init=kda_moe_init, stats=STATS,
    decode_in_place=lambda cache: _reads_in_place(), page_kinds=page_kinds,
    prefill_wave_limit=WAVE_LIMIT,
    caches="latent pages of its attention layers and one state row of its "
           "delta-rule layers, which holds no positions")

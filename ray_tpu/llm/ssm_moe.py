"""The serving programs of ``models/ssm_moe.py`` for the continuous-batching
engine: same slots, blocks and loop as the other families, a cache of two
kinds of which ONE DOES NOT GROW, and a block a pattern character.

* **Two kinds.** The attention blocks' keys and values live in pools
  ``[n_attention, P, PS, KV, hd]`` and a slot holds ``ceil(n / PS)`` of those
  pages, as a Llama slot does. The Mamba-2 blocks keep, for a slot, a state
  ``[heads, head width, state]`` in float32 and the convolution's last
  ``K - 1`` inputs, whatever the slot's length: pools ``[n_mamba, R, ...]`` of
  ROWS, and the engine draws a slot one row of them as it draws pages — the
  state is a kind of page whose table has one entry (``page_kinds``), so
  ``prefill_batch`` and ``decode_multi`` find a slot's row through the tables
  the engine already hands over, the free list and the ``rt_llm_pages_*``
  gauges count rows, and the engine learns nothing about a state. Row 0 is
  the junk row, as page 0 is the junk page: a wave's dummy prompts write
  there, and a dead decode slot points there and leaves it as it is. The
  conv rows lie flat, ``[R, (K - 1) . C]``: a second-minor axis of 3 would
  pad to a whole sublane tile on the device.
* **Decode** advances each live slot's row one position a step, exactly.
  Which slot owns each row, and whether a live one does, is found once a
  step (``_decode_body``: ``owner``, ``owned``); both pools are then
  updated where they lie. The state: the step's small inputs are laid out
  by row, every row takes the update (``dt`` 0 where no live slot owns it:
  unchanged), and only the outputs come back by slot (``_mamba_step``). The
  conv rows: every owned row of ONE block's slab drops its oldest input and
  takes its owner's new one, a row of no live slot stays bit for bit. (The
  device keeps ``[n_mamba, R, ...]`` with the BLOCK axis in the sublanes
  where ``R`` would pad a tile, so a scatter of the slots' rows rewrote the
  conv rows of all blocks, every block of every step: 0.716 ms of a 14.4 ms
  step at 8 blocks of 129 rows.) On a TPU the state's update is ONE pass
  over a block's pool — ``ops/ssm_pool.py``'s kernel reads a row, advances
  it, reads it out and writes it; anywhere else ``ops/ssm.py``'s one-step
  form, plain XLA operations and the kernel's
  reference. Attention likewise reads its pages where they lie
  (``ops/paged_attention.py``) or, off the TPU, the gathered table with a
  position mask: one switch, the seam's rule bound here as ``_reads_in_place``.
  Which of a table's entries lie one after the other in the pool — what the
  walk fetches as ONE copy, at 8 KB a page the difference between a sixth of
  the bandwidth and most of it — is found once a program
  (``ssm_moe_decode_multi``: ``run_lengths`` before the scan over the steps)
  and counted (``walk_blocks`` / ``walk_run_blocks``).
* **Prefill** is whole-prompt per pad bucket: the chunked scan from a zero
  state (a reused row is overwritten, never read), blocked attention over
  the fresh keys, and the state written **at each prompt's true length** —
  positions at or past it get ``dt = 0``, which decays nothing and adds
  nothing, and the convolution's saved inputs are the last ``K - 1`` true
  ones (zeros where the prompt is shorter). A wave holds at most
  ``WAVE_LIMIT`` prompts and tokens.
* **The expert block** routes over all experts and computes the held ones'
  part plus the shared expert; its experts are two matrices: a decode step's
  few rows meet every held expert in one batched product, a prompt's rows
  are sorted and go through ``ragged_dot`` twice (``parallel/moe.py``
  ``_applies_every_expert``). ``MOE_STATS`` and ``ssm_updates`` (state rows read
  and written a step) ride back with the tokens.

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
from ray_tpu.models.ssm_moe import (
    ATTENTION, MAMBA, SsmMoeConfig, attn_project, expert_block, gated_norm,
    mamba_decay, mamba_dt, mamba_in, mamba_mixer, mixer_out, split_conv,
    ssm_moe_init, ssm_moe_logits)
from ray_tpu.ops import ssm
from ray_tpu.ops.attention import gathered_attention, masked_attention
from ray_tpu.ops.paged_attention import (
    kv_block, paged_decode_attention, run_lengths, walk_copies)
from ray_tpu.ops.prefill_attention import blocks_for, gqa_prefill_attention
from ray_tpu.ops.ssm_pool import ssm_pool_step
from ray_tpu.utils import tracing

# the most prompts and tokens one prefill program may hold, as the other
# expert families
WAVE_LIMIT = (8, 16384)
# after MOE_STATS: state rows read and written, and the copies of the
# attention blocks' walks — sub-runs of a block fetched, and those of them
# that came as ONE copy or inside a block's — each over blocks and live slots
STATS = MOE_STATS + ("ssm_updates", "walk_blocks", "walk_run_blocks")
# The seam's platform rule under this module's own name, asked through this
# global by every program here and by ``PROGRAMS.decode_in_place``: ``tests/``
# ASSIGN an answer here to run the kernels interpreted.
_reads_in_place = reads_in_place


def page_kinds(cfg: SsmMoeConfig, page_size: int, max_seq_len: int):
    """What a slot holds of each kind (``ServePrograms.page_kinds``): K and
    V pages of the attention blocks, and ONE row of the Mamba-2 blocks'
    state, which holds no positions."""
    return (PageKind("kv", len(cfg.blocks_of(ATTENTION)),
                     -(-max_seq_len // page_size)),
            PageKind("state", len(cfg.blocks_of(MAMBA)), 1, positions=False))


def make_pools(cfg: SsmMoeConfig, page_size: int, n_pages, kv_dtype):
    """The model's cache: (K, V, states, conv rows). ``n_pages``: one count
    for both kinds, or ``{"kv": pages, "state": rows}``."""
    if kv_dtype not in (None, "native", "bf16"):
        raise ValueError(f"unknown kv_dtype {kv_dtype!r}")
    dtype = jnp.bfloat16 if kv_dtype == "bf16" else jnp.dtype(cfg.dtype)
    if not isinstance(n_pages, dict):
        n_pages = {"kv": n_pages, "state": n_pages}
    kv = (len(cfg.blocks_of(ATTENTION)), n_pages["kv"], page_size,
          cfg.n_kv_heads, cfg.head_dim)
    n_mamba, rows = len(cfg.blocks_of(MAMBA)), n_pages["state"]
    return (jnp.zeros(kv, dtype), jnp.zeros(kv, dtype),
            jnp.zeros((n_mamba, rows, cfg.mamba_heads, cfg.mamba_head_dim,
                       cfg.ssm_state), jnp.float32),
            jnp.zeros((n_mamba, rows, (cfg.conv_kernel - 1) * cfg.conv_width),
                      jnp.dtype(cfg.dtype)))


def _mamba_step(layer, x, j, row, owner, owned, states, convs,
                cfg: SsmMoeConfig):
    """One position of Mamba-2 block ``j`` (its place among the Mamba-2
    blocks) for every slot, through the slots' rows. x: [B, 1, D]; row: [B]
    int32 (0, the junk row, for a slot that is not live); owner: [R] int32,
    the slot that holds each row, and owned: [R] bool, whether a LIVE slot
    does (``_decode_body`` finds both once a step). The state pool is updated
    WHERE IT LIES: the step's small inputs (x, dt, B, C of a slot) are laid
    out by row, every row of the block's pool takes the update — a row of no
    live slot has ``dt`` 0, which decays nothing and adds nothing, so it
    stays bit for bit — and only the outputs are gathered back by slot. A
    gather of the rows, the update and a scatter back moved the state six
    times a step and took 3.4 ms a block at 128 slots; the plain form in
    place moves it twice and reads it a third time for ``y`` (1.19 ms);
    ``ssm_pool_step`` reads it once and writes it once (0.83 ms: PERF.md
    section 6, PRs 38 and 39). The conv pool likewise: block ``j``'s slab is
    read, every owned row of it shifted by one input, and the slab written
    back where it lay — no row is scattered into the pool (part ``conv``
    below says why). Returns (y [B, 1, D], states, convs)."""
    B, R = x.shape[0], states.shape[1]
    C = cfg.conv_width
    z, u, dt = mamba_in(layer, x, cfg)

    def by_row(a):  # [B, ...] of the slots -> [R, ...] of the rows
        return jnp.where(owned.reshape((R,) + (1,) * (a.ndim - 1)),
                         a[owner], 0)

    with tracing.part("conv"):
        # the conv rows shift where they lie too: every row of the block
        # drops its oldest input and takes its slot's new one, a row of no
        # live slot (the junk row too) stays. (The device keeps the pool
        # with the BLOCK axis in the sublanes, so a scatter of the slots'
        # rows into it rewrote the conv rows of ALL blocks, 76 MB a block,
        # 0.716 ms a step at 8 blocks of 129 rows: PERF.md section 6, PR 60.)
        old = convs[j]
        window = jnp.concatenate(
            [old[row].reshape(B, cfg.conv_kernel - 1, C), u], axis=1)
        xbc = ssm.conv_step(window, layer["conv"]["kernel"],
                            layer["conv"]["bias"])
        shifted = jnp.concatenate([old[:, C:], by_row(u[:, 0])], axis=1)
        convs = convs.at[j].set(jnp.where(owned[:, None], shifted, old))
    with tracing.part("ssm"):
        xs, Bm, Cm = split_conv(xbc, cfg)
        step = (by_row(xs), by_row(mamba_dt(layer, dt[:, 0])),
                mamba_decay(layer), by_row(Bm), by_row(Cm), layer["D"])
        if _reads_in_place():
            states, y = ssm_pool_step(states, j, *step)
        else:
            S, y = ssm.ssm_step(states[j], *step)
            states = states.at[j].set(S)
        y = gated_norm(layer, y[row], z[:, 0], cfg, x.dtype)
    return mixer_out(layer, y, "out_proj")[:, None], states, convs


def _decode_body(params, tokens, pos, tables, cache, active, temps, key,
                 cfg: SsmMoeConfig, runs):
    """One decode step for every slot (masked where inactive); ``runs`` is
    the K/V table's ``run_lengths`` (None where the kernels do not run).
    Returns (next_tok [B], cache, stats)."""
    t_kv, t_state = tables
    kp, vp, states, convs = cache
    B, PS = tokens.shape[0], kp.shape[2]
    off = pos % PS
    page = jnp.take_along_axis(t_kv, (pos // PS)[:, None], axis=1)[:, 0]
    row = jnp.where(active, t_state[:, 0], 0)  # a dead slot: the junk row
    # each row's slot, and whether a live one holds it: the junk row's is
    # whichever dead slot wrote last, and never live
    R = states.shape[1]
    owner = jnp.zeros((R,), jnp.int32).at[row].set(
        jnp.arange(B, dtype=jnp.int32))
    owned = jnp.zeros((R,), bool).at[row].set(active)
    lengths = jnp.where(active, pos + 1, 0)
    in_place = _reads_in_place()
    at = {MAMBA: 0, ATTENTION: 0}  # the block's place in its kind's pools
    loads = []
    with tracing.part("embed"):
        x = params["tok"]["embedding"][tokens][:, None, :]
    for i, kind in enumerate(cfg.pattern):
        layer = params[f"layers_{i}"]
        if kind == MAMBA:
            j, at[kind] = at[kind], at[kind] + 1
            y, states, convs = _mamba_step(
                layer, x, j, row, owner, owned, states, convs, cfg)
        elif kind == ATTENTION:
            j, at[kind] = at[kind], at[kind] + 1
            q, k, v = attn_project(layer, x, cfg)
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
            y = mixer_out(layer, att, "wo")
        else:
            y, load = expert_block(layer, x, cfg, valid=active[:, None])
            loads.append(load)
        x = x + y
    logits = ssm_moe_logits(params, x[:, 0], cfg)
    next_tok = _sample_tail(logits, temps, key)
    # by the kernel's own rule, a sub-run the unit of a copy; the gathered
    # form walks nothing
    walked = walk_copies(runs, kv_block(kp, t_kv.shape[1])[1],
                         -(-lengths // PS)) if in_place else [jnp.int32(0)] * 2
    stats = jnp.concatenate([
        moe_load_stats(loads, B * cfg.n_experts_per_tok, gated=False),
        jnp.stack([active.sum() * at[MAMBA],
                   *(at[ATTENTION] * n for n in walked)]).astype(jnp.int32)])
    return (jnp.where(active, next_tok, 0), (kp, vp, states, convs), stats)


@partial(jax.jit, static_argnames=("cfg", "n_steps"),
         donate_argnums=(6, 7, 8, 9))
def ssm_moe_decode_multi(params, loras, aids, tokens, seq_lens, tables,
                         kp, vp, states, convs, active, temps, key,
                         cfg: SsmMoeConfig, n_steps: int):
    """``n_steps`` fused decode steps as one device program: the contract of
    ``ServePrograms.decode_multi`` with one table a kind (K/V pages, state
    rows) and four pools, rows of ``[B tokens | STATS]``. ``loras``/``aids``
    are the engine's (None / zeros here: refused at construction)."""
    runs = run_lengths(tables[0]) if _reads_in_place() else None
    return decode_frame(_decode_body, params, tokens, seq_lens, tables,
                        (kp, vp, states, convs), active, temps, key, cfg,
                        n_steps, runs)


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(5, 6, 7, 8))
def ssm_moe_prefill_batch(params, loras, aids, tokens, pages, kp, vp, states,
                          convs, true_lens, temps, key, cfg: SsmMoeConfig):
    """Prefill a whole admission wave as one batched forward: the contract
    of ``ServePrograms.prefill_batch`` with ``pages`` one array a kind — K/V
    ``[N, pad / PS]``, state ``[N, 1]``. Every prompt's state row is written
    at its true length. Returns (first tokens [N], the four pools)."""
    p_kv, p_state = pages
    N, Tp = tokens.shape
    PS = kp.shape[2]
    idx = jnp.arange(Tp)
    rows = p_kv[:, idx // PS]
    offs = jnp.broadcast_to(idx % PS, (N, Tp))
    valid = idx[None, :] < true_lens[:, None]  # padding advances no state
    row = p_state[:, 0]                        # and is routed nowhere
    blocked = _reads_in_place() and blocks_for(Tp) is not None
    at = {MAMBA: 0, ATTENTION: 0}
    with tracing.part("embed"):
        x = params["tok"]["embedding"][tokens]
    for i, kind in enumerate(cfg.pattern):
        layer = params[f"layers_{i}"]
        if kind == MAMBA:
            j, at[kind] = at[kind], at[kind] + 1
            y, S, saved = mamba_mixer(layer, x, cfg, valid, tails=true_lens)
            with tracing.part("ssm"):
                states = states.at[j, row].set(S)
            with tracing.part("conv"):
                convs = convs.at[j, row].set(saved.reshape(N, -1))
        elif kind == ATTENTION:
            j, at[kind] = at[kind], at[kind] + 1
            q, k, v = attn_project(layer, x, cfg)
            with tracing.part("kv_write"):
                kp = kp.at[j, rows, offs].set(k.astype(kp.dtype))
                vp = vp.at[j, rows, offs].set(v.astype(vp.dtype))
            if blocked:
                with tracing.part("attention"):
                    att = gqa_prefill_attention(
                        q.reshape(N, Tp, -1), k.reshape(N, Tp, -1),
                        v.reshape(N, Tp, -1), n_kv_heads=cfg.n_kv_heads)
            else:
                att = masked_attention(q, k, v, jnp.broadcast_to(
                    idx[:, None] >= idx[None, :], (N, Tp, Tp)))
            y = mixer_out(layer, att, "wo")
        else:
            y, _ = expert_block(layer, x, cfg, valid=valid)
        x = x + y
    logits = ssm_moe_logits(params, last_rows(x, true_lens), cfg)
    return _sample_tail(logits, temps, key), kp, vp, states, convs


PROGRAMS = ServePrograms(
    family="ssm_moe", make_cache=make_pools,
    decode_multi=ssm_moe_decode_multi, prefill_batch=ssm_moe_prefill_batch,
    init=ssm_moe_init, stats=STATS,
    decode_in_place=lambda cache: _reads_in_place(), page_kinds=page_kinds,
    prefill_wave_limit=WAVE_LIMIT,
    caches="K and V pages of its attention blocks and one state row of its "
           "recurrent blocks, which holds no positions")

"""The seam between the engine and the model families. ``llm/engine.py``
owns slots, pages, admission and the loops and knows no model: it asks
``serving_programs(cfg)`` for the family's ``ServePrograms`` and threads the
family's cache through unseen. This file names no family either: a config
class says whose programs serve it (``family = "eva"``), and
``ray_tpu.llm.<family>`` is imported when such a config is served.

A new family supplies ``models/<family>.py`` (a config type with its
``family``, a seeded init, its layer's halves) and ``llm/<family>.py`` (a
cache, a ``_decode_body``, two jitted programs and ``PROGRAMS``), with its
stats' counters in ``utils/metrics.py`` ``LLM_MODEL_STATS`` and its parts in
``tracing.PARTS`` — and no line of this file, the engine or another family:
a family's module imports this file, its own ``models/`` module and ``ops/``,
never the engine and never another family. What a slot caches is the family's:
pages that grow with the sequence (K and V, of one geometry, a kind's own or a
pass's own; a latent; a window's ring; an indexer's keys), rows that each stand
for a stride of positions, or a row of fixed size: beside such pages, or in them.

What the families share is here, once: the platform rule (``reads_in_place``),
the frame of a fused decode program (``decode_frame``), a prefill's last rows
(``last_rows``), the sampling tail and the expert layers' stats.
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ray_tpu.parallel.moe import expert_passes
from ray_tpu.utils import tracing


class UnsupportedByModel(NotImplementedError):
    """A feature of the engine that a model family's programs do not have,
    refused by name (never a silent read of a pool that is not there)."""

    def __init__(self, feature: str, programs: ServePrograms):
        family, caches = programs.family, programs.caches
        super().__init__(
            f"{feature} is not supported for the {family!r} model family: "
            f"it is a program of the Llama family's (what a slot caches is "
            f"K and V pages of every layer, so a prefix of its pages is a "
            f"prefix of the sequence), and this family's programs have no "
            f"such form" + (f" (a slot of it caches {caches})" if caches else ""))
        self.feature, self.family = feature, family


@dataclass(frozen=True)
class PageKind:
    """One kind of page of a family's cache, as far as the engine sees it:
    a name, how many layers' rows its pools hold (the weight of its reads
    in the read counters), how many entries a slot's table of it has, and
    how far back a layer of it attends (None: to position 0). A slot of
    ``n`` positions holds ``min(ceil(n / PS), table)`` of its pages: a
    table shorter than the sequence is the family's ring, and a table of
    ONE entry a row that does not grow with the sequence at all — a
    recurrent layer's state. Such a kind holds no ``positions``: nothing
    attends it, and the read counters leave it out. ``stride``: the positions
    ONE ROW of a page stands for (1: a row a position; a chunk's pooled pair:
    the chunk), so a slot holds ``ceil(n / (PS * stride))`` such pages.
    ``aligned``: the reach is counted from its last multiple, not from the
    query — position ``t`` attends the rows of positions ``[reach * (t //
    reach), t]`` of a kind of stride 1 (whose ring is then exactly ``reach /
    PS`` pages, dropped whole at the boundary), and of a strided kind the rows
    of the WHOLE reaches before that, ``[0, reach * (t // reach))``."""
    name: str
    layers: int
    table: int
    reach: int | None = None
    positions: bool = True
    stride: int = 1
    aligned: bool = False


# extra int32 columns of a decode step's token row of the families with
# expert layers, each summed over the expert layers: rows routed to held
# experts, distinct held experts that got any, the largest expert's rows,
# held experts x expert layers (what "touched" is a share of), and how often
# the grouped product put an expert's matrices through the MXU (once a
# touched expert, or the kernel's row chunks: ``parallel/moe.py``)
MOE_STATS = ("moe_assignments", "moe_experts_touched", "moe_max_load",
             "moe_expert_slots", "moe_passes")


@tracing.part("router")
def moe_load_stats(loads, rows: int, gated: bool = True):
    """A step's rows per held expert, one [held] array an expert layer, of
    ``rows`` assignments a layer -> the MOE_STATS sums. ``gated``: the
    experts' form, which decides the grouped product that ran."""
    if not loads:
        return jnp.zeros((len(MOE_STATS),), jnp.int32)
    load = jnp.stack(loads)
    return jnp.stack([load.sum(), (load > 0).sum(), load.max(axis=-1).sum(),
                      jnp.asarray(load.size),
                      expert_passes(load, rows, gated)]).astype(jnp.int32)


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
    ``page_kinds(cfg, page_size, max_seq_len) -> (PageKind, ...)``: the
    kinds of pages of a cache that has more than one (window and full
    layers; K/V pages and state rows). The engine then keeps a table and a
    free list a kind, and hands the programs a TUPLE of tables where it
    hands one (``page_tables`` in decode, ``pages`` in prefill, in the
    kinds' order); None is one kind for every layer.
    ``prefill_wave_limit = (prompts, tokens)``: the most one prefill program
    may hold, so a pad group is split; None splits nothing.
    ``attends_most(cfg) -> int``: the most positions a decode step's
    attention ATTENDS of a slot however many it fetches (a model that
    picks its keys: the read counters then say what was attended and what
    was fetched for it); None attends everything within reach.
    ``lora(cfg, adapters, rank) -> (stack, name -> index)`` stacks named
    adapters. It and the rest are the Llama family's and None elsewhere: the
    engine refuses what needs them.
    ``prepare(params, cfg) -> params``: the tree in the layout the programs
    read fastest, derived ONCE, when the engine takes a tree (its ``params``
    setter), so that no program lays a weight out again; it may re-lay the
    tree it is given in place. None: the programs read the tree as it comes.
    ``init(key, cfg) -> params``: the family's seeded tree. ``caches``: what
    a slot caches where that is not K and V pages of every layer — the
    reason a refusal gives (``UnsupportedByModel``)."""
    family: str
    make_cache: callable
    decode_multi: callable
    prefill_batch: callable
    init: callable
    stats: tuple = ()
    decode_in_place: callable = None
    page_kinds: callable = None
    prefill_wave_limit: tuple | None = None
    attends_most: callable = None
    prefill_suffix: callable = None
    decode_spec: callable = None
    decode_verify: callable = None
    lora: callable = None
    int8_cache: bool = False
    page_plane: bool = False   # export_pages / submit_prefilled (disagg)
    prepare: callable = None
    caches: str = ""


def serving_programs(cfg) -> ServePrograms:
    """The programs that serve ``cfg``: those of the family its class names.
    No option chooses, and only that family's module is imported."""
    family = getattr(type(cfg), "family", None)
    if not isinstance(family, str):
        raise TypeError(f"no serving programs for a {type(cfg).__name__}")
    return importlib.import_module(f"ray_tpu.llm.{family}").PROGRAMS


def reads_in_place() -> bool:
    """THE platform rule: the programs attend (score, select, advance a
    state) through the Pallas kernels on a TPU and in the plain form — the
    gathered table with a position mask, the kernels' reference and what the
    CPU tests run — anywhere else, where a kernel would be interpreted.
    Decided by what the code can see, no option. Every family binds it under
    its own ``_reads_in_place`` and asks through that name."""
    return jax.default_backend() == "tpu"


def decode_frame(body, params, tokens, seq_lens, tables, cache, active, temps,
                 key, cfg, n_steps: int, *closed):
    """``n_steps`` fused decode steps as one ``lax.scan``: what every
    family's ``decode_multi`` is around its own step. ``body(params, tok,
    pos, tables, cache, active, temps, key, cfg, *closed) -> (next [B],
    cache, stats)``: ``cache`` the tuple of the family's pools, ``key`` the
    program's with the step's index folded in, ``stats`` the step's own
    int32 sums (None: a family without) — they ride behind the tokens in
    the step's row. ``closed``: what the family found once for all steps
    (its tables' runs). Returns ``(rows [n_steps, B + stats], tok, pos,
    *cache)``, the last token and position on the device for the next
    block."""
    def step(carry, k):
        tok, pos, cache = carry
        nxt, cache, stats = body(params, tok, pos, tables, cache, active,
                                 temps, jax.random.fold_in(key, k), cfg,
                                 *closed)
        return (nxt, pos + 1, cache), (
            nxt if stats is None else jnp.concatenate([nxt, stats]))

    (tok, pos, cache), rows = jax.lax.scan(
        step, (tokens, seq_lens, cache), jnp.arange(n_steps))
    return (rows, tok, pos, *cache)


@jax.jit
def merge_carry(tokens, seq_lens, first, slots, lens):
    """A prefill wave's first tokens and prompt lengths into the decode
    carry, ON THE DEVICE: what the engine's loops do at every admission, so
    that no block in flight is waited for. tokens, seq_lens: [B], the carry
    between blocks; first: [N], the prefill program's own output, dummy rows
    included; slots, lens: [N], each prompt's slot and length, a dummy row's
    slot out of range (dropped). One program a wave bucket ``N``."""
    return (tokens.at[slots].set(first, mode="drop"),
            seq_lens.at[slots].set(lens, mode="drop"))


def last_rows(x, true_lens):
    """Each prompt's row at its last true position. x: [N, T, D] -> [N, D]."""
    return jnp.take_along_axis(
        x, (true_lens - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]


@tracing.part("sample")
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

"""Continuous-batching LLM engine with a paged KV cache and LoRA multiplex.

TPU-native counterpart of the reference's delegated vLLM engine (ref:
python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_engine.py:95 —
there Ray wires vLLM; here the engine is owned). Design maps the vLLM
ideas onto XLA's static-shape world:

* **Fixed decode slots, one admission path.** A decode block advances ALL
  ``max_batch`` slots (inactive ones masked) by its ``K`` steps in one
  program. A request's end by ``max_tokens`` is SCHEDULED when its last block
  is dispatched: its slot is swept then, refilled by a prefill queued BEHIND
  that block, and the newcomer's first token and length are merged into the
  carry on the device; no block is waited for. Only an EOS or a cancel, which
  emission alone finds, drains.
* **Paged cache.** The family's pools (``self.cache``: K and V ``[layers,
  n_pages, page_size, kv, hd]`` for the Llama family, other pools for
  others) and, for each KIND of page the family declares, a page table a
  slot and a free list. Prefill scatters a prompt's rows into freshly drawn
  pages; a decode step writes one row and attends the slot's pages WHERE
  THEY LIE — on a TPU a Pallas walk fetches only the pages that hold tokens,
  anywhere else the programs gather the table and mask it (the seam's one
  rule, ``llm/programs.py`` ``reads_in_place``; ``_kv_in_place`` tells the
  read counters which ran). Shapes never depend on sequence length, so XLA
  compiles one decode program a block size and one prefill program a
  (wave, prompt-pad) bucket.
* **Streaming.** Every request gets an asyncio queue; tokens land there
  the step they are sampled.
* **LoRA multiplex** (ref: serve/multiplex.py): stacked low-rank adapters
  on the q/v projections, selected per slot — different requests in one
  decode batch can use different adapters (adapter 0 = base model).
* **The cache is the model's.** The engine owns slots, pages, tables,
  admission, blocks and the loop; what a page HOLDS is the model family's
  (``llm/programs.py``: ``ServePrograms``, those of the family the config's
  class names; the engine branches on no family). It
  is carried as one tuple of pools (``self.cache``), handed to every program
  and taken back donated. Where the family declares kinds of pages
  (``ServePrograms.page_kinds``) the engine keeps a table and a free list a
  kind, and a kind's rows are one of three things (``PageKind``): a row a
  POSITION (K and V, a latent, an indexer's key; the table as long as the
  sequence, or a ring of one window's pages); a row a STRIDE of positions (a
  chunk's pooled pair: a page of ``PS`` rows stands for ``PS * stride``
  positions); or a row with NO positions (a recurrent state: one entry,
  whatever the length). A slot HOLDS what its ``n`` positions reach, drawn as
  it grows, and admission waits for the kind whose TIMELINE passes its pool
  (``_timeline``). No device program here: engine -> seam -> programs -> ``ops/``.
"""
from __future__ import annotations

import asyncio
import collections.abc
import concurrent.futures
import itertools
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.devtools import chaos
# benchmarks/sizing.py imports these two from here (a benchmark file, not
# this PR's to edit); nothing in this module uses them
from ray_tpu.llm.llama import (  # noqa: F401
    paged_decode_multi, paged_prefill_batch)
from ray_tpu.llm.programs import (
    PageKind, UnsupportedByModel, merge_carry, serving_programs)
from ray_tpu.utils import metrics, tracing


@dataclass
class _Request:
    req_id: int
    prompt: list[int]
    max_tokens: int
    temperature: float
    adapter: int
    out: asyncio.Queue = field(default_factory=asyncio.Queue)
    slot: int = -1
    emitted: int = 0
    planned: int = 0  # tokens scheduled: dispatched, emitted or not yet
    cancelled: bool = False
    finished: bool = False  # completed normally (max_tokens or eos)
    # disaggregated admission (llm/disagg): (k_stack, v_stack, first_tok)
    # adopted from a prefill worker's KVPageManifest — admission scatters
    # the stacks into this engine's pool instead of running a prefill
    prefilled: tuple | None = None
    # speculative decoding: this request's draft state rides here — the
    # opt-in flag (greedy-only; sampled rows always decode plain) plus
    # its slice of the engine's token-history mirror (the drafter's
    # context), which _reserve_slot/_emit_spec_block maintain
    spec: bool = False
    # perf_counter_ns stamps: submit, prefill dispatched (or pages
    # adopted), first token on the host. Observed once a request into
    # rt_llm_queue_wait / prefill_wait / decode_seconds
    t_submit: int = 0
    t_admit: int = 0
    t_first: int = 0
    # (trace_id, span_id) of the submitter's sampled span, captured once
    # in submit; None = no retro spans for this request
    trace: tuple | None = None


class EngineFull(Exception):
    """No free slot/pages and the waiting queue is at capacity."""


# one thread reads the compiled programs' texts (``tracing.compiled_parts``):
# one at a time, so that the loop never shares the interpreter with two
_PARTS_READER = concurrent.futures.ThreadPoolExecutor(
    1, thread_name_prefix="program-parts")


class ContinuousBatchingEngine:
    """Single-process engine; drive with ``await engine.start()`` then
    ``submit`` / ``stream`` from the same event loop."""

    def __init__(self, params, cfg, *, max_batch: int = 8,
                 page_size: int = 16, n_pages: int = 256,
                 max_seq_len: int = 512, eos_id: int | None = None,
                 lora_adapters: dict[str, dict] | None = None,
                 lora_rank: int = 8, max_waiting: int = 256,
                 block_buckets: tuple[int, ...] = (4, 8, 16, 32, 64),
                 kv_dtype: str | None = None, spec_enable: bool = False,
                 spec_k: int = 4, spec_ngram: int = 2,
                 spec_drafter=None):
        self.cfg = cfg
        self.B = max_batch
        self.PS = page_size
        self.MAXP = -(-max_seq_len // page_size)
        self.eos_id = eos_id
        self.max_waiting = max_waiting
        # fused-decode block sizes (one compiled program per bucket); the
        # loop picks the smallest bucket covering the longest remaining
        # request, so short interactive requests stay low-latency while
        # long generations amortize dispatch 64x
        self.block_buckets = tuple(sorted(block_buckets))
        self.programs = P = serving_programs(cfg)
        for feature, asked, has in (
                ("kv_dtype='int8'", kv_dtype == "int8", P.int8_cache),
                ("lora_adapters", bool(lora_adapters), P.lora is not None),
                ("spec_enable", bool(spec_enable), P.decode_spec is not None)):
            if asked and not has:
                raise UnsupportedByModel(feature, P)
        self.params = params  # laid out for serving: stage weights_prepare
        with tracing.stage("pools"):  # the model's cache, one tuple of pools
            self.cache = tuple(P.make_cache(cfg, page_size, n_pages, kv_dtype))
        self.kv_dtype = kv_dtype or "native"
        self._kv_in_place = bool(
            P.decode_in_place and P.decode_in_place(self.cache))
        self.n_pages = n_pages
        # the kinds of pages a slot holds: one for every layer unless the
        # family declares its own. n_pages is then a count a kind's name
        # (or one count for all); a table, a free list and page 0 as the
        # junk page, a kind
        self.kinds = (P.page_kinds(cfg, page_size, self.MAXP * page_size)
                      if P.page_kinds else (PageKind("kv", 1, self.MAXP),))
        counts = [n_pages[k.name] if isinstance(n_pages, dict) else n_pages
                  for k in self.kinds]
        self.free = [_FreePages(n) for n in counts]
        self.capacity = [n - 1 for n in counts]
        self.loras = None
        self.lora_index = {"__base__": 0}
        if lora_adapters:
            self.loras, self.lora_index = P.lora(
                cfg, lora_adapters, lora_rank)
        # slot state (host side)
        self.slot_req: list[_Request | None] = [None] * self.B
        self.tables = [np.zeros((self.B, k.table), np.int32)
                       for k in self.kinds]
        self.seq_lens = np.zeros(self.B, np.int32)
        self.next_tok = np.zeros(self.B, np.int32)
        self.temps = np.zeros(self.B, np.float32)
        self.aids = np.zeros(self.B, np.int32)
        self.waiting: list[_Request] = []
        self._req_ids = itertools.count(1)
        self._reqs: dict[int, _Request] = {}
        # finished requests not yet drained by a stream() consumer; bounded
        # LRU so fire-and-forget submitters can't leak token queues forever
        self._done: collections.OrderedDict[int, _Request] = (
            collections.OrderedDict())
        self._done_cap = 4 * self.B + max_waiting
        self._wake = asyncio.Event()
        self._running = False
        self._task = None
        self._rng = jax.random.PRNGKey(0)
        self.error: BaseException | None = None  # fatal loop failure
        # (program, shapes) seen by _call -> future of its compiled_parts;
        # and of each, in that order, where it came from (program_builds)
        self._compiled, self._builds = {}, collections.deque(maxlen=256)
        # speculative decoding (README § Speculative decoding): greedy
        # requests draft spec_k tokens per step (on-device n-gram
        # matcher over spec_ngram-grams, or the spec_drafter hook) and
        # the target verifies them in one fused multi-position forward
        self.spec_enable = bool(spec_enable)
        self.spec_k = int(spec_k)
        self.spec_ngram = int(spec_ngram)
        self.spec_drafter = spec_drafter
        # token-history mirror [B, max_seq_len]: hist[i, :seq_lens[i]+1]
        # holds slot i's known tokens (prompt + emitted + pending input)
        # — the drafter's context, and the rebuild source for the
        # device-resident hist carry at admission points
        self.hist = np.zeros((self.B, self.MAXP * page_size), np.int32)
        # counters for benchmarks / tests
        self.steps = 0
        self.tokens_out = 0
        self.spec_steps = 0      # speculative verify steps run
        self.spec_proposed = 0   # draft tokens proposed (live spec rows)
        self.spec_accepted = 0   # draft tokens the target accepted
        # bounded per-block log the disagg telemetry drains:
        # (n_steps, emitted, proposed, accepted) per synced spec block
        self._block_log: collections.deque = collections.deque(maxlen=256)
        # the model's own per-step sums of the last synced decode block
        # (ServePrograms.stats), as a mean per step: annotates the next
        # dispatch and admission phases
        self._last_stats: dict = {}
        # positions that block's attention attended and fetched a step
        # (kv_live, kv_read): annotates the next dispatch
        self._last_kv: dict = {}

    @property
    def kpool(self):
        """The Llama family's K pool (``cache[0]``); other families have no
        such pool and say so."""
        return self._kv_pool(0)

    @property
    def vpool(self):
        return self._kv_pool(1)

    def _kv_pool(self, i: int):
        if not self.programs.page_plane:
            raise UnsupportedByModel("a K or V pool", self.programs)
        return self.cache[i]

    # ----------------------------------------------------------- public API
    async def start(self):
        if self._task is None:
            self._running = True
            self._task = asyncio.get_running_loop().create_task(self._loop())

    async def stop(self):
        self._running = False
        self._wake.set()
        if self._task is not None:
            await self._task
            self._task = None
        # nothing will produce more tokens: unblock every live consumer
        self._terminate_all_streams()

    def _terminate_all_streams(self):
        for req in list(self._reqs.values()):
            req.out.put_nowait(None)
        self._reqs.clear()
        self._done.clear()
        self.waiting.clear()
        self.slot_req = [None] * self.B

    def submit(self, prompt_tokens: list[int], *, max_tokens: int = 32,
               temperature: float = 0.0, adapter: str | None = None,
               spec: bool | None = None) -> int:
        """Queue a request; returns its id. Tokens arrive on stream().
        ``spec`` overrides the engine's ``spec_enable`` default for this
        request (greedy requests only; sampled rows decode plain either
        way)."""
        if self.error is not None:
            raise RuntimeError("engine loop died") from self.error
        if len(self.waiting) >= self.max_waiting:
            raise EngineFull(f"{len(self.waiting)} requests already waiting")
        if len(prompt_tokens) + max_tokens > self.MAXP * self.PS:
            raise ValueError(
                f"prompt ({len(prompt_tokens)}) + max_tokens ({max_tokens}) "
                f"exceeds the engine's max_seq_len ({self.MAXP * self.PS})")
        for kind, n_need, has in zip(
                self.kinds, self._pages_of(len(prompt_tokens) + max_tokens),
                self.capacity):
            if n_need > has:
                raise ValueError(
                    f"request needs {n_need} KV pages but the pool only has "
                    f"{has}" + ("" if len(self.kinds) == 1
                                else f" of the {kind.name!r} kind"))
        aid = self.lora_index.get(adapter or "__base__")
        if aid is None:
            raise ValueError(f"unknown LoRA adapter {adapter!r} "
                             f"(loaded: {sorted(self.lora_index)})")
        req = _Request(next(self._req_ids), list(prompt_tokens),
                       int(max_tokens), float(temperature), aid,
                       spec=self.spec_enable if spec is None else bool(spec))
        return self._enqueue(req)

    def _enqueue(self, req: _Request) -> int:
        req.t_submit = time.perf_counter_ns()
        if tracing.enabled():
            req.trace = tracing.current()
        self._reqs[req.req_id] = req
        self.waiting.append(req)
        self._wake.set()
        return req.req_id

    def submit_prefilled(self, prompt_tokens: list[int], k_stack, v_stack,
                         first_token: int, *, max_tokens: int = 32,
                         temperature: float = 0.0,
                         adapter: str | None = None,
                         spec: bool | None = None) -> int:
        """Queue a request whose prompt KV was ALREADY produced elsewhere
        (a disaggregated prefill worker): admission scatters the adopted
        page stacks (``[L, n_pages, PS, KV, hd]`` arrays, or ``{"q","s"}``
        dicts for int8 pools — the shape ``disagg.adopt_pages`` returns)
        into this engine's pool and starts decoding at position
        ``len(prompt_tokens)`` with ``first_token`` — no prefill dispatch,
        no recompute. The stacks must cover ``ceil(len(prompt)/PS)`` pages
        of a pool with this engine's page_size and kv_dtype."""
        if not self.programs.page_plane:
            raise UnsupportedByModel("submit_prefilled (disagg adoption)",
                                     self.programs)
        if self.error is not None:
            raise RuntimeError("engine loop died") from self.error
        if len(self.waiting) >= self.max_waiting:
            raise EngineFull(f"{len(self.waiting)} requests already waiting")
        if len(prompt_tokens) + max_tokens > self.MAXP * self.PS:
            raise ValueError(
                f"prompt ({len(prompt_tokens)}) + max_tokens ({max_tokens}) "
                f"exceeds the engine's max_seq_len ({self.MAXP * self.PS})")
        n_cover = -(-len(prompt_tokens) // self.PS)
        n_got = (k_stack["q"] if isinstance(k_stack, dict)
                 else k_stack).shape[1]
        if n_got < n_cover:
            raise ValueError(
                f"adopted stacks cover {n_got} pages but the prompt "
                f"needs {n_cover}")
        aid = self.lora_index.get(adapter or "__base__")
        if aid is None:
            raise ValueError(f"unknown LoRA adapter {adapter!r} "
                             f"(loaded: {sorted(self.lora_index)})")
        req = _Request(next(self._req_ids), list(prompt_tokens),
                       int(max_tokens), float(temperature), aid,
                       spec=self.spec_enable if spec is None else bool(spec))
        req.prefilled = (k_stack, v_stack, int(first_token))
        return self._enqueue(req)

    def export_pages(self, req_id: int):
        """Page-export hook: seal a LIVE request's prompt KV pages into
        the local shm arena and return their ``KVPageManifest`` — how an
        aggregated engine donates a prefix to the cross-request cache.
        Must be called while the request still holds its slot (prompt
        positions are stable once prefilled; decode writes land past
        them)."""
        from ray_tpu.llm.disagg.kv_plane import ship_pages

        if not self.programs.page_plane:
            raise UnsupportedByModel("export_pages (disagg/kv_plane.py)",
                                     self.programs)
        req = self._reqs.get(req_id)
        if req is None or req.slot < 0:
            raise KeyError(f"request {req_id} is not holding a slot")
        n_cover = -(-len(req.prompt) // self.PS)
        page_ids = [int(p) for p in self.tables[0][req.slot, :n_cover]]
        return ship_pages(self.kpool, self.vpool, page_ids, req.prompt,
                          page_size=self.PS, kv_dtype=self.kv_dtype)

    def tokens_in_flight(self) -> int:
        """Decode tokens this engine still owes: remaining scheduled
        tokens of resident requests plus everything waiting — the
        cross-replica batching admission signal (a ring full of
        nearly-done requests drains fast; a shallow queue of long
        generations does not; request COUNTS can't tell them apart)."""
        live = sum(max(0, r.max_tokens - r.emitted)
                   for r in self.slot_req if r is not None and not r.cancelled)
        return live + sum(max(0, r.max_tokens - r.emitted)
                          for r in self.waiting if not r.cancelled)

    def spec_stats(self, drain: bool = False) -> dict:
        """Speculative-decoding counters + the per-block log. With
        ``drain`` the log is consumed (the disagg telemetry's exactly-
        once feed into the tokens_per_step / spec_accept_rate windows);
        without it this is a pure read."""
        blocks = list(self._block_log)
        if drain:
            self._block_log.clear()
        return {"spec_steps": self.spec_steps,
                "spec_proposed": self.spec_proposed,
                "spec_accepted": self.spec_accepted,
                "spec_accept_rate": (self.spec_accepted
                                     / max(1, self.spec_proposed)),
                "blocks": blocks}

    @property
    def free_pages(self) -> "_FreePages":
        """The first kind's free pages, under the name that callers who know
        one kind of page read them by (the benchmark's replicas, the tests)."""
        return self.free[0]

    def headroom(self) -> dict:
        """Admission-control snapshot for the disagg scheduler: the KV pages
        a newcomer could still be PROMISED (the pool less the peak of what
        the residents will hold, ``_timeline``: a page not yet drawn is not a
        page to spare; ``free_pages_now`` is the count not drawn), decode
        slots, queue depth, and the decode tokens-in-flight signal."""
        spare = [int(c - t) for c, t in zip(self.capacity, self._timeline())]
        return {"free_pages": spare[0], "free_pages_now": len(self.free[0]),
                "free_pages_by_kind": {k.name: n for k, n in
                                       zip(self.kinds, spare)},
                "free_slots": sum(r is None for r in self.slot_req),
                "waiting": len(self.waiting),
                "tokens_in_flight": self.tokens_in_flight(),
                "n_pages": self.n_pages, "page_size": self.PS,
                "max_batch": self.B, "kv_dtype": self.kv_dtype}

    async def stream(self, req_id: int):
        """Async iterator of generated token ids for one request. Raises
        if the engine died before the request finished. The request stays
        registered until its consumer drains the terminal None here — a
        caller may finish awaiting something else before streaming and the
        already-queued tokens must still be reachable."""
        req = self._reqs.get(req_id)
        if req is None:
            req = self._done[req_id]
        try:
            while True:
                item = await req.out.get()
                if item is None:
                    if self.error is not None and not req.finished:
                        raise RuntimeError("engine loop died") from self.error
                    break
                yield item
        finally:
            # only unregister finished requests: a consumer erroring out
            # mid-stream must not make cancel() a no-op on a live request
            self._done.pop(req_id, None)

    async def stream_blocks(self, req_id: int):
        """Block-coalesced stream: lists of token ids, one per wake.

        ``_emit_block`` pushes a whole fused ``lax.scan`` block's tokens
        into the request queue in one synchronous burst, so draining the
        queue greedily after the first await yields exactly one delta per
        fused decode block (per accepted run in spec mode). This is the
        streaming-serve producer shape: one "G" chunk record per BLOCK on
        the wire instead of one per token — token-identical to
        ``stream()``, at block-granularity overhead."""
        req = self._reqs.get(req_id)
        if req is None:
            req = self._done[req_id]
        try:
            while True:
                item = await req.out.get()
                if item is None:
                    if self.error is not None and not req.finished:
                        raise RuntimeError("engine loop died") from self.error
                    return
                blk = [item]
                while True:
                    try:
                        nxt = req.out.get_nowait()
                    except asyncio.QueueEmpty:
                        break
                    if nxt is None:
                        # terminal already queued behind the block
                        yield blk
                        if self.error is not None and not req.finished:
                            raise RuntimeError(
                                "engine loop died") from self.error
                        return
                    blk.append(nxt)
                yield blk
        finally:
            self._done.pop(req_id, None)

    async def generate(self, prompt_tokens: list[int], **kw) -> list[int]:
        rid = self.submit(prompt_tokens, **kw)
        out: list[int] = []
        # block-granular drain: one loop wake per fused decode block
        # instead of one per token
        async for blk in self.stream_blocks(rid):
            out.extend(blk)
        return out

    def cancel(self, req_id: int):
        req = self._reqs.get(req_id)
        if req is not None:
            req.cancelled = True
            self._wake.set()

    # ------------------------------------------------------------ internals
    def _pages_of(self, n) -> list:
        """Pages of each kind a slot of ``n`` positions holds (or an array)."""
        return [np.minimum(-(-n // (self.PS * k.stride)), k.table)
                for k in self.kinds]

    def _draw(self, slot: int, n: int, whole: int, grown: bool) -> bool:
        """Fill ``slot``'s tables up to what ``n`` positions hold, every kind,
        or draw nothing if ANY kind has too few left. ``whole``: the positions
        of the request's end, for the place (``_place``). ``grown``: after
        admission (``rt_llm_pages_grown_total``, of every family)."""
        have = [int(np.count_nonzero(t[slot])) for t in self.tables]
        need = [int(m) - h for m, h in zip(self._pages_of(n), have)]
        if any(len(f) < m for f, m in zip(self.free, need)):
            return False
        for i, (h, m, end) in enumerate(zip(have, need, self._pages_of(whole))):
            if m > 0:
                self.tables[i][slot, h:h + m] = self._place(
                    i, slot, h, m, int(end) - h)
                self._count_pages(i, drawn=m)
                if grown:
                    metrics.llm_pages_grown_total.inc(
                        m, {"kind": self.kinds[i].name})
        return True

    def _place(self, i: int, slot: int, have: int, m: int, rest: int):
        """Take ``m`` free pages of kind ``i`` for ``slot``'s table entries
        from ``have`` on, so that the walks' runs survive
        (``ops/paged_attention.py`` ``run_lengths``: consecutive pool pages
        are one copy): the page after the slot's last one while that is free,
        else from the start of a hole (``_hole``) for the ``rest`` of the
        request — these ``m`` and what it draws until its end. The count is
        the timeline's; the place is best effort."""
        free, out = self.free[i].is_free, np.empty(m, np.int32)
        at = int(self.tables[i][slot, have - 1]) + 1 if have else 0
        got = 0
        while got < m:
            if not (at and at < len(free) and free[at]):
                at = self._hole(i, slot, rest - got)
            run = free[at:at + m - got]
            n = len(run) if run.all() else int(run.argmin())
            out[got:got + n] = np.arange(at, at + n)
            free[at:at + n] = False
            at, got = at + n, got + n
        self.free[i].count -= m
        return out

    def _hole(self, i: int, slot: int, rest: int) -> int:
        """Where ``slot`` starts a new run of kind ``i``: the first free run
        that holds ``rest`` pages, else the longest — of the pages no OTHER
        slot is about to grow into (every resident's hole: as many pages
        after its last one as it still draws), and only where none of those
        is left, of all the free pages. Where pages do not bind, each slot so
        grows inside a hole of its own and its table is one run; where they
        do, the pool is shared in time and not in place, and a table is what
        it gets (the looped family's walk, the one cell where they do, reads
        pages of 64 KB at the same speed scattered: PERF.md section 6, PR 58)."""
        free = self.free[i].is_free
        spare = free.copy()
        for other, r in enumerate(self.slot_req):
            if r is None or other == slot:
                continue
            row = self.tables[i][other]
            have = int(np.count_nonzero(row))
            more = int(self._pages_of(len(r.prompt) + r.max_tokens)[i]) - have
            if have and more > 0:
                spare[row[have - 1] + 1:row[have - 1] + 1 + more] = False
        for mask in (spare, free):
            edges = np.flatnonzero(np.diff(mask, prepend=False, append=False))
            starts, lengths = edges[::2], edges[1::2] - edges[::2]
            if len(starts):
                return int(starts[np.argmax(lengths >= rest) if
                                  (lengths >= rest).any() else lengths.argmax()])
        raise AssertionError("no free page: _draw counts before it places")

    def _count_pages(self, i: int, drawn: int = 0) -> None:
        """Kind ``i``'s pages drawn and held, where a family has kinds."""
        if len(self.kinds) == 1:
            return
        tags = {"kind": self.kinds[i].name}
        if drawn:
            metrics.llm_pages_drawn_total.inc(drawn, tags)
        metrics.llm_pages_held.set(
            self.capacity[i] - len(self.free[i]), tags)

    @staticmethod
    def _reach(r: _Request, K: int) -> int:
        """The positions a block of ``K`` steps draws pages for in ``r``'s
        slot: none past its end — where a request all dispatched stays, the
        steps the loop runs on included (a request of ONE token so draws the
        page of that token's position, which a step run on writes) — and none
        at all (0) once it is cancelled or over."""
        if r.cancelled:
            return 0
        return len(r.prompt) + min(r.planned + K, r.max_tokens)

    def _growth(self, K: int) -> list:
        """Pages of each kind that every slot draws before a block of ``K``
        steps ([B] a kind): what the positions the block reaches hold — none
        past the request's end — less what the slot's table holds."""
        n = np.array([0 if r is None else self._reach(r, K)
                      for r in self.slot_req], np.int64)
        return [np.maximum(pages - np.count_nonzero(t, axis=1), 0)
                for pages, t in zip(self._pages_of(n), self.tables)]

    def _lacking(self, K: int) -> bool:
        """Whether a block of ``K`` steps would step into a page that the
        free pages cannot give this instant."""
        return any(g.sum() > len(f)
                   for g, f in zip(self._growth(K), self.free))

    def _grow(self, K: int) -> None:
        """Draw, for every slot that steps in a block of ``K``, the pages the
        block steps into. A table entry still 0 there would send the step's K
        and V to the junk page, lost without a sound: hence the assertion."""
        for slot in np.flatnonzero(np.any(self._growth(K), axis=0)):
            r = self.slot_req[slot]
            drawn = self._draw(slot, self._reach(r, K),
                               len(r.prompt) + r.max_tokens, grown=True)
            assert drawn, f"slot {slot} steps into a page nobody can give it"

    def _release_pages(self, slot: int, reached: int) -> None:
        """Give back every page that ``slot``'s tables hold, all kinds.
        ``reached``: its positions, for the count of pages a ring reused."""
        for i, (kind, free, table) in enumerate(
                zip(self.kinds, self.free, self.tables)):
            free.give(table[slot][table[slot] != 0])
            table[slot, :] = 0
            self._count_pages(i)
            if kind.reach is not None:  # a ring: the pages it wrote over
                metrics.llm_window_pages_released_total.inc(
                    max(0, -(-reached // (self.PS * kind.stride)) - kind.table))
        self.seq_lens[slot] = 0

    def _finish_stream(self, req: _Request) -> None:
        """Unregister a request and close its token stream: live -> the
        bounded finished-awaiting-drain map (stream() can still reach the
        queued tokens; cancel() only sees live requests; the cap bounds
        leakage from never-streamed submits)."""
        self._reqs.pop(req.req_id, None)
        self._done[req.req_id] = req
        while len(self._done) > self._done_cap:
            self._done.popitem(last=False)
        req.out.put_nowait(None)

    def _slot_for_the_head(self) -> bool:
        """Whether someone waits and a slot stands empty: the turns on which
        the loop tries an admission. A turn on which the head of the queue
        waits for a SLOT is counted here, one on which it waits for pages
        where the timeline refuses it (``_reserve_slot``)."""
        if not self.waiting:
            return False
        if any(r is None for r in self.slot_req):
            return True
        metrics.llm_admit_deferred_total.inc(1, {"for": "slots"})
        return False

    def _timeline(self, req: _Request | None = None):
        """The most pages of each kind ([kinds]) that the residents, and
        ``req`` admitted beside them, hold at any step still to be dispatched
        — what admission checks against the pool. Every live slot advances one
        position a dispatched step, and a request's end is known (``planned``,
        ``max_tokens``): with ``p = len(prompt) + planned`` positions drawn for
        and ``r = max_tokens - planned`` steps to go, the step ``t`` from now
        finds the pages of ``p + min(t + a, r)`` positions in every slot with
        ``r >= t`` and the others swept. That sum rises only between ends, so
        it is taken at the ends alone (and at 0: what is held now). ``a``, how
        far ahead of its step a slot draws, is nothing — a block draws what it
        steps into, and ``_pick_block`` sizes it to the free pages — but in the
        speculative loop, whose slots draw whole: there ``a`` is past every
        end. An EOS or a cancel only frees pages sooner."""
        # a newcomer's first token is planned before its first block is
        # dispatched (``_admit_behind``)
        pr = [(len(q.prompt) + max(q.planned, 1),
               0 if q.cancelled else q.max_tokens - max(q.planned, 1))
              for q in (*self.slot_req, req) if q is not None]
        if not pr:
            return np.zeros(len(self.kinds), np.int64)
        p, r = np.array(pr, np.int64).T
        ends = np.unique(np.append(r, 0))[:, None]  # [ends, 1] against [slots]
        a = self.MAXP * self.PS if self.spec_enable else 0
        held = [(pages * (r >= ends)).sum(axis=1).max() for pages in
                self._pages_of(p + np.minimum(ends + a, r))]
        return np.array(held, np.int64)

    def _reserve_slot(self, req: _Request) -> int | None:
        """Claim a slot for one waiting request and draw its prompt's pages,
        if the residents' timeline has room for it (host bookkeeping only;
        the prefill itself is dispatched per wave). The speculative loop
        advances a slot by what its drafts are worth, which no timeline
        knows: there every resident counts at its end and draws whole."""
        slot = next((i for i, r in enumerate(self.slot_req) if r is None), -1)
        if slot < 0:
            return None
        Tp = len(req.prompt)
        whole = Tp + req.max_tokens
        if (self._timeline(req) > self.capacity).any() or not self._draw(
                slot, whole if self.spec_enable else Tp, whole, grown=False):
            metrics.llm_admit_deferred_total.inc(1, {"for": "pages"})
            return None
        req.slot = slot
        self.slot_req[slot] = req
        self.seq_lens[slot] = Tp
        self.temps[slot] = req.temperature
        self.aids[slot] = req.adapter
        if self.spec_enable:
            # drafter context: the prompt (first token lands at
            # _admit_wave emission, generated tokens at spec emission)
            self.hist[slot, :] = 0
            self.hist[slot, :Tp] = req.prompt
        return slot

    async def _call(self, ph, fn, *args):
        """Run one of the jitted programs above, inside the caller's open
        phase ``ph``. Its first use at new shapes compiles — tens of
        seconds at real widths — and the event loop is where the replica
        also answers health probes and streams other requests' tokens. So
        the first use lowers and compiles in a thread, and the call that
        follows finds the executable in jit's own cache. Lowering donates
        nothing: the pools stay readable (``export_pages``) meanwhile.
        Steady state costs one dict lookup and never suspends; the compile
        does, so it closes ``ph`` (a phase belongs to its thread), waits
        under ``engine.compile`` and opens ``ph`` again for the call. The
        compiled text's layer parts (``program_parts``) are read by
        ``_PARTS_READER`` beside the program's first run (0.1-0.6 s a program,
        which the loop does not wait for)."""
        # what can differ between two calls under one engine: an array's
        # shape (pad and wave buckets) and the static step counts
        key = (fn, *(a if isinstance(a, int) else getattr(a, "shape", None)
                     for a in args[2:]))
        if key not in self._compiled:
            ph.__exit__(None, None, None)
            with tracing.phase("engine.compile", program=fn.__name__,
                               shape=str(key[1:])) as compiling:
                compiled, built = await tracing.build_in_executor(
                    None, lambda: fn.lower(*args).compile())
                compiling.set(source=built["source"])
            self._compiled[key] = _PARTS_READER.submit(
                tracing.compiled_parts, compiled)
            self._builds.append({"program": fn.__name__, "shape": str(key[1:]),
                                 **built, "t": time.monotonic()})
            ph.__enter__()
        return fn(*args)

    def program_builds(self) -> list[dict]:
        """One record a program ``_call`` got ready, in that order:
        ``{"program", "shape", "source": "cache" | "compiled", "trace_s",
        "lower_s", "cache_read_s", "compile_s", "t"}`` — whether the
        persistent compile cache had it (``"memory"``: jit's own caches did,
        from an earlier engine of this process), the seconds jax reported of
        each step in the building thread, and ``time.monotonic()`` at its
        end."""
        return list(self._builds)

    def program_parts(self) -> dict:
        """``{program: {"parts": {"<instruction>|<shape>": part}, "stale",
        "variants", "seconds"}}`` of every program ``_call`` compiled, by
        the name a profiler trace gives it (``jit_paged_decode_multi``):
        what joins a trace's device events to ``tracing.PARTS``. Waits for
        a table still being read (a program compiled this instant)."""
        return tracing.merged_parts(
            table.result() for table in list(self._compiled.values()))

    # trees taken so far: 1 after construction, one more every assignment
    weights_prepared = 0

    @property
    def params(self):
        """The parameter tree as the family's programs read it. Assigning
        one (construction does) lays it out for serving ONCE, by the
        family's ``ServePrograms.prepare``, so that no program lays a weight
        out again; the engine keeps what the hook returns and no other
        reference to what it was handed. None drops the tree (room for the
        next one)."""
        return self._params

    @params.setter
    def params(self, tree):
        if tree is not None:
            if self.programs.prepare is not None:
                with tracing.stage("weights_prepare"):
                    tree = self.programs.prepare(tree, self.cfg)
            self.weights_prepared += 1
        self._params = tree

    _WAVE_BUCKETS = (1, 2, 4, 8, 16)

    def _split_wave(self, pad: int, reqs: list) -> list[list]:
        """A pad group as the prefill programs it becomes: one, unless the
        family states the most prompts and tokens a program may hold
        (``ServePrograms.prefill_wave_limit``) — then runs of the largest
        wave bucket within both."""
        limit = self.programs.prefill_wave_limit
        if limit is None:
            return [reqs]
        most = max(1, min(limit[0], limit[1] // pad))
        most = max(b for b in self._WAVE_BUCKETS if b <= most)
        return [reqs[i:i + most] for i in range(0, len(reqs), most)]

    def _tables_arg(self, tables):
        """A slot-table argument of a program: the one array, or a tuple of
        them where the family has kinds of pages (copies: see
        ``_dispatch_block``)."""
        if len(self.kinds) == 1:
            return jnp.asarray(tables[0].copy())
        return tuple(jnp.asarray(t.copy()) for t in tables)

    async def _admit_wave(self) -> bool:
        """Admit every waiting request that fits and WAIT for each wave's
        first tokens (the speculative loop's admission, behind its drain).
        Returns True if anything was admitted."""
        groups = await self._admit_dispatch()
        for reqs, first in groups:
            self._emit_first(reqs, first)
        return bool(groups)

    def _emit_first(self, reqs: list, first) -> None:
        """Host-side emission of one synced prefill wave: one read for the
        wave (``engine.prefill_sync``), each prompt's first token to its
        stream and into the host's copy of the carry."""
        with tracing.phase("engine.prefill_sync", prompts=len(reqs)):
            first = np.asarray(first)  # ONE sync per group
        for j, req in enumerate(reqs):
            if req.slot >= 0:  # else its end was scheduled: the slot is gone
                self.next_tok[req.slot] = int(first[j])
                if self.spec_enable:
                    self.hist[req.slot, len(req.prompt)] = int(first[j])
            if not req.cancelled:  # cancelled while its bucket compiled
                self._emit(req, int(first[j]))

    async def _admit_dispatch(self, behind: bool = False
                              ) -> list[tuple[list[_Request], object]]:
        """Reserve slots and DISPATCH batched prefills for every waiting
        request that fits; no host sync — returns [(requests,
        first-token device array)] per pad-bucket group. ``behind``: a
        decode block is in flight and nothing has waited for it (what
        ``rt_llm_admit_waves_undrained_total`` counts)."""
        groups: dict[int, list[_Request]] = {}
        adopted: list[_Request] = []
        with tracing.phase("engine.admit", pad=0, wave=0) as ph:
            while self.waiting:  # slot and page reservation, no device work
                nxt = self.waiting[0]
                if nxt.cancelled:
                    self.waiting.pop(0)
                    self._finish_stream(nxt)
                    continue
                if self._reserve_slot(nxt) is None:
                    break
                self.waiting.pop(0)
                if nxt.prefilled is not None:
                    adopted.append(nxt)
                    continue
                Tp_pad = -(-len(nxt.prompt) // self.PS) * self.PS
                groups.setdefault(Tp_pad, []).append(nxt)
            waves = [(pad, reqs) for pad, group in groups.items()
                     for reqs in self._split_wave(pad, group)]
            splits = len(waves) - len(groups)
            ph.set(prompts=len(adopted) + sum(map(len, groups.values())),
                   splits=splits)
        out = []
        if adopted:
            from ray_tpu.llm.disagg.kv_plane import scatter_pages
        for req in adopted:
            # slot adoption (llm/disagg): the prompt KV was produced by a
            # prefill worker and fetched via the KV-page plane — scatter
            # it into this pool's freshly allocated pages. Runs at the
            # same admission points as prefill dispatches, so the
            # functional pool update is ordered exactly like one.
            k_stack, v_stack, first = req.prefilled
            req.prefilled = None  # release the host copies after scatter
            n_cover = -(-len(req.prompt) // self.PS)
            rows = self.tables[0][req.slot, :n_cover].copy()
            self.cache = (scatter_pages(self.cache[0], rows, k_stack),
                          scatter_pages(self.cache[1], rows, v_stack))
            req.t_admit = time.perf_counter_ns()
            out.append(([req], np.asarray([first], np.int32)))
        for Tp_pad, reqs in waves:
            npages = Tp_pad // self.PS
            nb = next(b for b in self._WAVE_BUCKETS if b >= len(reqs)) \
                if len(reqs) <= self._WAVE_BUCKETS[-1] else len(reqs)
            true_tokens = sum(len(r.prompt) for r in reqs)
            with tracing.phase("engine.admit", pad=Tp_pad, wave=nb,
                               prompts=len(reqs), tokens=true_tokens,
                               **self._last_stats) as ph:
                toks = np.zeros((nb, Tp_pad), np.int32)
                # dummy rows: junk. A kind's table may be shorter than the
                # prompt (a ring): its pages are then the whole table; a
                # page of a strided kind stands for ``stride`` pages' positions
                pages = [np.zeros((nb, min(-(-npages // k.stride), k.table)),
                                  np.int32) for k in self.kinds]
                aids = np.zeros(nb, np.int32)
                true_lens = np.ones(nb, np.int32)
                temps = np.zeros(nb, np.float32)
                for j, req in enumerate(reqs):
                    toks[j, :len(req.prompt)] = req.prompt
                    for mine, table in zip(pages, self.tables):
                        mine[j] = table[req.slot, :mine.shape[1]]
                    aids[j] = req.adapter
                    true_lens[j] = len(req.prompt)
                    temps[j] = req.temperature
                self._rng, sub = jax.random.split(self._rng)
                first, *cache = await self._call(
                    ph, self.programs.prefill_batch, self.params, self.loras,
                    jnp.asarray(aids), jnp.asarray(toks),
                    self._tables_arg(pages), *self.cache,
                    jnp.asarray(true_lens),
                    jnp.asarray(temps), sub, self.cfg)
                self.cache = tuple(cache)
            now = time.perf_counter_ns()
            for req in reqs:
                req.t_admit = now
            metrics.llm_prefill_waves_total.inc()
            metrics.llm_admit_waves_undrained_total.inc(int(behind))
            metrics.llm_prefill_prompts_total.inc(len(reqs))
            metrics.llm_prefill_true_tokens_total.inc(true_tokens)
            metrics.llm_prefill_padded_tokens_total.inc(nb * Tp_pad)
            out.append((reqs, first))
        return out

    def _emit(self, req: _Request, tok: int):
        req.emitted += 1
        self.tokens_out += 1
        req.out.put_nowait(tok)
        if req.emitted == 1:
            self._stage(req, "engine::queue", req.t_submit, req.t_admit,
                        metrics.llm_queue_wait_seconds, "queue")
            req.t_first = time.perf_counter_ns()
            self._stage(req, "engine::prefill", req.t_admit, req.t_first,
                        metrics.llm_prefill_wait_seconds, "exec")
        if req.emitted >= req.max_tokens or (
                self.eos_id is not None and tok == self.eos_id):
            req.finished = True
            req.cancelled = True  # finished: reclaim on the next sweep
            self._stage(req, "engine::decode", req.t_first,
                        time.perf_counter_ns(), metrics.llm_decode_seconds,
                        "exec")
            metrics.llm_decode_tokens_total.inc(req.emitted - 1)
            if req.slot < 0:
                # its end was scheduled and the slot handed on: close the stream
                self._finish_stream(req)

    @staticmethod
    def _stage(req: _Request, name: str, t0: int, t1: int, hist,
               stage: str) -> None:
        """One of a request's three stages, once it is over: into its
        histogram and, if the submitter was inside a sampled span, as a
        retro span under that span (the replica's ``::run``), so the
        request's waterfall reaches down to the engine."""
        hist.observe((t1 - t0) * 1e-9)
        if req.trace is not None:
            from ray_tpu.llm.disagg.telemetry import _span_sink

            sink = _span_sink()
            if sink is not None:
                tracing.emit_retro(
                    name, {"trace_id": req.trace[0],
                           "parent_span_id": req.trace[1]},
                    sink, (t1 - t0) * 1e-9, end_ns=t1, stage=stage)

    async def _loop(self):
        """Engine driver. Any exception here is fatal for the engine:
        record it, fail every live stream, and exit — hung consumers on a
        silently dead loop are the worst failure mode."""
        try:
            await self._loop_inner()
        except BaseException as e:  # noqa: BLE001
            self.error = e
            self._running = False
            self._terminate_all_streams()
            import traceback

            traceback.print_exc()

    @staticmethod
    def _ramp(emitted: int) -> int:
        # per-request fusion ramp: fresh requests decode in small blocks
        # (streaming first-token latency, fast completion of short
        # requests, bounded admission latency for newcomers), deep ones
        # amortize dispatch with bigger ones. Capped at 32 — the ramp only
        # applies at low occupancy, where a 64-block would let a lone
        # generation schedule so far ahead that a newcomer queues behind
        # all of it; the 64 bucket is reserved for full batches.
        if emitted < 8:
            return 8
        if emitted < 24:
            return 16
        return 32

    def _pick_block(self) -> int:
        """Fused-steps bucket for this dispatch: the smallest bucket
        covering every active request's ramp, each capped by its exact
        remaining count, so that a request's LAST block is the bucket that
        covers its last token (the steps of that bucket past the token write
        the junk page) and its slot can be handed on behind that block
        instead of riding out a long batch's block (continuous-batching
        latency semantics). Counted in the tokens DISPATCHED for a request
        (``planned``: the loops run ahead of emission), so a request whose
        tokens are all scheduled asks for nothing more; where that is every
        slot (a lone request nobody waits behind) the loop runs on a step at
        a time until the last token is on the host.

        At high occupancy the ramp is skipped: a full batch is the
        throughput regime, where small early blocks would multiply
        dispatch round trips for no latency benefit (newcomers can't be
        admitted into a full batch anyway). A slot draws the pages a block
        steps into before the block is dispatched (``_grow``), so the bucket
        is no larger than the free pages cover."""
        live = [r for r in self.slot_req if r is not None
                and not r.cancelled and r.planned < r.max_tokens]
        if not live:
            return 1
        if 2 * len(live) >= self.B:
            want = min(r.max_tokens - r.planned for r in live)
        else:
            want = min(min(self._ramp(r.planned), r.max_tokens - r.planned)
                       for r in live)
        cover = next((b for b in self.block_buckets if want <= b),
                     self.block_buckets[-1])
        # the slots that end INSIDE a block hold their pages until its
        # sweep: the largest bucket whose growth the free pages cover this
        # instant. One step always is, by the timeline's bound
        return next((b for b in reversed(self.block_buckets)
                     if b <= cover and not self._lacking(b)), 1)

    async def _dispatch_block(self, carry):
        """Pick the block size and dispatch one fused decode block from
        the device-resident ``carry`` (token, length), or from host state
        when it is None, and count its steps as scheduled for every slot's
        request. No host sync. Returns (K, tokens [K, B] on the device, the
        next carry)."""
        with tracing.phase("engine.decode_dispatch") as ph:
            K = self._pick_block()
            self._grow(K)
            active = np.array([r is not None for r in self.slot_req])
            ph.set(steps=K, live=int(active.sum()),
                   held=self.capacity[0] - len(self.free[0]),
                   promised=int(self._timeline()[0]), **self._last_stats,
                   **self._last_kv)
            self._rng, sub = jax.random.split(self._rng)
            # .copy() on every host array that the loops later mutate
            # (page_tables/seq_lens/next_tok/aids/temps): PJRT CPU
            # zero-copies aligned numpy buffers into device arrays, so a
            # retire/emission mutation while the async dispatch is still
            # in flight would corrupt the program's view of them (race
            # observed as garbage decode tokens under load).
            tok_d, lens_d = carry or self._host_carry()
            toks, tok_d, lens_d, *cache = await self._call(
                ph, self.programs.decode_multi, self.params, self.loras,
                jnp.asarray(self.aids.copy()), tok_d, lens_d,
                self._tables_arg(self.tables), *self.cache,
                jnp.asarray(active), jnp.asarray(self.temps.copy()), sub,
                self.cfg, K)
            self.cache = tuple(cache)
        for r in self.slot_req:
            if r is not None:
                r.planned = min(r.max_tokens, r.planned + K)
        return K, toks, (tok_d, lens_d)

    def _emit_block(self, entry) -> None:
        """Host-side emission of one synced decode block. The read below
        (``engine.block_sync``) blocks the calling thread — the replica's
        event loop — until the device has finished the block: up to 64
        steps, about 0.6 s at the 9 ms a step of the benchmark's chat
        cell. Nothing else of the replica runs meanwhile: no new call
        starts, no delta leaves."""
        K, toks, slot_snapshot = entry
        with tracing.phase("engine.block_sync", steps=K):
            toks = np.asarray(toks)  # [K, B]; blocks until the device is done
        if self.programs.stats:  # [K, B + stats]: the model's sums ride along
            self._observe_stats(toks[:, self.B:])
            toks = toks[:, :self.B]
        self._observe_kv_reads(K, slot_snapshot)
        self.steps += K
        with tracing.phase("engine.emit") as ph:
            before = self.tokens_out
            for i, req in enumerate(slot_snapshot):
                if req is None:
                    continue
                if self.slot_req[i] is req:
                    # a scheduled end may have handed this slot on while
                    # the block was in flight; host per-slot state then
                    # belongs to the newcomer
                    self.seq_lens[i] += K
                elif req.cancelled and req.req_id in self._reqs:
                    # cancelled by its user after its slot was handed on:
                    # no sweep will meet it, so its stream is closed here
                    self._finish_stream(req)
                for k in range(K):
                    if req.cancelled:
                        break  # finished/cancelled mid-block: discard rest
                    tok = int(toks[k, i])
                    if self.slot_req[i] is req:
                        self.next_tok[i] = tok
                    self._emit(req, tok)
            ph.set(tokens=self.tokens_out - before)

    def _observe_kv_reads(self, K: int, slot_snapshot) -> None:
        """What a synced block's attention attended and what it fetched
        for that (``rt_llm_decode_kv_tokens_{live,read}_total``), reckoned
        from each snapshot slot's length at the block's start: step k of a
        slot attends ``start + k + 1`` positions, or as many of them as a
        layer of the kind reaches back, counted in the kind's ROWS
        (``_rows_within``); over kinds, the mean over layers. A
        kind that holds no positions (a slot's state: ``PageKind.positions``)
        is no part of either. A slot that a scheduled end
        has already handed on has its start from the request itself."""
        starts = np.array(
            [self.seq_lens[i] if self.slot_req[i] is req
             else len(req.prompt) + req.emitted - 1
             for i, req in enumerate(slot_snapshot) if req is not None],
            np.int64)
        lens = starts[:, None] + np.arange(1, K + 1)  # [live slots, K]
        live = read = 0.0
        kinds = [k for k in self.kinds if k.positions]
        layers = sum(k.layers for k in kinds)
        for kind in kinds:
            reach = self._rows_within(kind, lens)
            within = int(self._attended(reach).sum())
            if self._kv_in_place:  # whole pages, from the first within reach
                # what the walk's table counts: positions, or a strided
                # kind's rows from its first
                span = lens if kind.stride == 1 else reach
                ends = np.minimum(span, self.MAXP * self.PS)
                pages = -(-ends // self.PS) - (span - reach) // self.PS
                fetched = int(pages.sum()) * self.PS
            else:
                fetched = K * self.B * kind.table * self.PS
            if len(self.kinds) > 1:
                tags = {"kind": kind.name}
                metrics.llm_decode_kv_tokens_live_total.inc(within, tags)
                metrics.llm_decode_kv_tokens_read_total.inc(fetched, tags)
            live += within * kind.layers / layers
            read += fetched * kind.layers / layers
        metrics.llm_decode_kv_tokens_live_total.inc(live)
        metrics.llm_decode_kv_tokens_read_total.inc(read)
        self._last_kv = {"kv_live": round(live / K, 2),
                         "kv_read": round(read / K, 2)}

    @staticmethod
    def _rows_within(kind: PageKind, lens):
        """Of ``lens`` positions ([slots, steps], the query's own included),
        how many ROWS of ``kind`` a step attends: every position; as many as
        the kind reaches back from the query (a sliding window); or, where
        the reach is ``aligned``, those since its last multiple (a kind of a
        row a position) and one row a ``stride`` of the whole reaches before
        it (a strided kind)."""
        if kind.reach is None:
            return lens
        if not kind.aligned:
            return np.minimum(lens, kind.reach)
        whole = (lens - 1) // kind.reach * kind.reach
        return lens - whole if kind.stride == 1 else whole // kind.stride

    def _attended(self, reach):
        """Of the positions within reach ([slots, steps]), how many a decode
        step's attention attends: all of them, or at most what a family that
        picks its keys says (``ServePrograms.attends_most``)."""
        most = self.programs.attends_most
        return reach if most is None else np.minimum(reach, most(self.cfg))

    def _observe_stats(self, rows) -> None:
        """A synced block's per-step sums of the model's programs
        (``ServePrograms.stats``, [K, n] int32) into the ``rt_llm_*_total``
        counter of each name, and as means per step for the next phases'
        annotations."""
        for name, total in zip(self.programs.stats, rows.sum(axis=0)):
            metrics.LLM_MODEL_STATS[name].inc(int(total))
            self._last_stats[name] = round(float(total) / rows.shape[0], 2)

    def _sweep(self, scheduled: bool = False) -> None:
        """Give back the slot and pages of every finished or cancelled
        request and, where ``scheduled``, of every request whose tokens are
        all dispatched: its end is known, so its slot is handed on behind
        the block that ends it. Blocks in flight are no obstacle: whatever
        they still write to these pages (a finished slot's junk steps
        included) is ordered on the device's stream BEFORE the prefill that
        writes them again, and a block dispatched later reads the tables as
        they are then (see paged_decode_multi; a state row likewise: a
        reused row is overwritten, never read)."""
        with tracing.phase("engine.free") as ph:
            freed = 0
            for i, req in enumerate(self.slot_req):
                over = req is not None and (req.cancelled or (
                    scheduled and req.planned >= req.max_tokens))
                if not over:
                    continue
                req.slot = -1  # emission closes the stream at its last token
                self.slot_req[i] = None
                # the positions it got to: what the host has seen of it, or
                # what is scheduled where that runs ahead
                self._release_pages(i, max(
                    int(self.seq_lens[i]), len(req.prompt) + req.planned - 1))
                freed += 1
                if req.cancelled:
                    # user-cancelled, or finished while it still held the
                    # slot: no later emission closes this stream
                    self._finish_stream(req)
            ph.set(freed=freed)

    def _host_carry(self):
        """The decode carry (token, length) from the host's copy: what a
        drain leaves current (copies: see ``_dispatch_block``)."""
        return (jnp.asarray(self.next_tok.copy()),
                jnp.asarray(self.seq_lens.copy()))

    async def _admit_behind(self, carry, pending: list):
        """Admission without a drain: reserve slots for the waiting requests
        that fit, dispatch their prefills BEHIND whatever is in flight and
        merge their first tokens and lengths into the carry on the device
        (``merge_carry``: one program a wave bucket, which every admission
        takes, the first into an idle engine too — so none is first built
        under load). Nothing is waited for; the first tokens are emitted
        when ``pending`` reaches their entry. Returns the carry."""
        behind = any(kind == "block" for kind, *_ in pending)
        for reqs, first in await self._admit_dispatch(behind):
            # a dummy row's slot lies out of range: the merge drops it
            slots = np.full(first.shape[0], self.B, np.int32)
            lens = np.zeros(first.shape[0], np.int32)
            for j, req in enumerate(reqs):
                slots[j], lens[j], req.planned = req.slot, len(req.prompt), 1
            with tracing.phase("engine.admit", pad=0, wave=len(slots),
                               merged=len(reqs)) as ph:
                carry = await self._call(
                    ph, merge_carry, *(carry or self._host_carry()),
                    jnp.asarray(first), jnp.asarray(slots), jnp.asarray(lens))
            pending.append(("prefill", reqs, first))
        return carry

    def _sync_oldest(self, pending: list) -> None:
        """Wait for the oldest entry in flight and emit it: ``("prefill",
        requests, first tokens)`` a wave admitted behind the blocks,
        ``("block", K, tokens, the slots' requests)`` a decode block."""
        kind, *entry = pending.pop(0)
        if kind == "prefill":
            return self._emit_first(*entry)
        self._emit_block(tuple(entry))

    async def _yield(self) -> None:
        """One turn of the event loop for everything else the replica
        does — stream consumers pushing their deltas, new calls starting,
        stats and health probes. Like ``engine.idle`` a phase that spans
        a suspending await: nothing of this engine opens one meanwhile."""
        with tracing.phase("engine.yield"):
            await asyncio.sleep(0)

    async def _idle(self, timeout: float = 1.0) -> None:
        """No slot is live: sleep until a submit, a cancel or stop()
        wakes the loop."""
        with tracing.phase("engine.idle"):
            self._wake.clear()
            try:
                await asyncio.wait_for(self._wake.wait(), timeout=timeout)
            except asyncio.TimeoutError:
                pass

    async def _loop_inner(self):
        if self.spec_enable:
            # accepted counts are data-dependent: completion steps are
            # unknowable at dispatch, so spec mode always drives the
            # reactive-shaped loop (planned mode needs a schedule)
            await self._loop_spec()
        elif self.eos_id is None:
            await self._loop_planned()
        else:
            await self._loop_reactive()

    async def _loop_planned(self):
        """Fully pipelined driver for length-deterministic generation
        (no EOS): every request's completion step is known at dispatch
        time, so slots are retired and re-admitted ON SCHEDULE without
        ever draining the pipeline — prefills, carry merges and decode
        blocks stream to the device back to back, and the only host syncs
        are the trailing token emissions riding two blocks behind."""
        pending: list = []  # dispatch-ordered: ("prefill",...)|("block",...)
        carry = None

        while self._running:
            # retire slots whose scheduled tokens are all dispatched
            self._sweep(scheduled=True)
            if self._slot_for_the_head():
                carry = await self._admit_behind(carry, pending)
            if all(r is None for r in self.slot_req):
                while pending:
                    self._sync_oldest(pending)
                    # yield between blocks: consumers must observe tokens
                    # in emission order, not one burst after the drain
                    await self._yield()
                carry = None
                await self._idle()
                continue
            # pace dispatch to emission + 2 entries: enough run-ahead to
            # hide the dispatch round trip under device compute, little
            # enough that a newly arriving request interleaves within a
            # couple of blocks instead of queueing behind a whole
            # pre-scheduled generation. Yield right after each sync so
            # consumers see tokens before the next dispatch (whose first
            # use may compile) occupies the loop thread.
            while len(pending) >= 2:
                self._sync_oldest(pending)
                await self._yield()
            K, toks, carry = await self._dispatch_block(carry)
            pending.append(("block", K, toks, list(self.slot_req)))
            await self._yield()

    async def _loop_reactive(self):
        """The driver where a reply may end early (an ``eos_id``): what every
        deployment runs. Entries in flight, dispatch-ordered as in the
        planned loop: block N+1 is enqueued before block N's tokens come
        back, so the host's round trip rides under device compute, and the
        (tok, pos) carry chains ON THE DEVICE from block to block.

        One admission path. What is known at dispatch is acted on at
        dispatch: a request that reaches ``max_tokens`` inside a block
        already dispatched has its end SCHEDULED, so when someone waits its
        slot is swept and refilled behind that block (``_admit_behind``: the
        prefill queued after it, the newcomers merged into the carry on the
        device) and no block is waited for. What only emission can find — an
        EOS, a user's cancel, the last token of a request nobody waited
        behind — drains what is in flight, sweeps and rebuilds the carry
        from the host's copy, which a drain leaves current. With nobody
        waiting, for its slot or for its pages, a scheduled slot keeps its
        place and the loop runs on, a step a block, until its last token is
        on the host.

        The loop holds the event loop's thread nearly all the time: every
        _emit_block sleeps in np.asarray (phase engine.block_sync) until the
        device has finished a block of up to 64 steps (about 0.6 s at 9 ms a
        step), and only the _yield() at the bottom lets the replica's other
        coroutines run."""
        pending: list = []  # dispatch-ordered: ("prefill",...)|("block",...)
        carry = None  # (tok_dev, lens_dev) device-resident between blocks

        def drain():
            while pending:
                self._sync_oldest(pending)

        while self._running:
            # a scheduled end is swept when someone waits for its slot, or
            # a live slot's next step for its pages: the timeline that let
            # that slot in counted them free from the end on
            scheduled = bool(self.waiting) or self._lacking(1)
            if scheduled or not pending:
                self._sweep(scheduled)
            if self._slot_for_the_head():
                carry = await self._admit_behind(carry, pending)
            if all(r is None for r in self.slot_req):
                drain()
                carry = None
                # idle, OR the head-of-queue request can't be admitted yet
                # (pages still held elsewhere): either way we must yield —
                # a bare continue would spin the loop without ever
                # letting consumers/stop() run
                await self._idle()
                continue
            K, toks, carry = await self._dispatch_block(carry)
            pending.append(("block", K, toks, list(self.slot_req)))
            # one block stays in flight while the host emits what is older
            while len(pending) >= 2:
                self._sync_oldest(pending)
            # a request that emission found finished or cancelled in its
            # slot must stop the pipeline rather than over-decode for ever
            if any(r is not None and r.cancelled for r in self.slot_req):
                drain()
                carry = None
            # hand the loop to consumers/admitters every block
            await self._yield()

    # ------------------------------------------------------- speculative loop
    _SPEC_BUCKETS = (1, 2, 4)

    def _spec_inflight_steps(self, pending) -> list[int]:
        """Per-slot spec steps already dispatched but not yet synced."""
        steps = [0] * self.B
        for entry in pending:
            S, snap = entry[0], entry[4]
            for i, rq in enumerate(snap):
                if rq is not None and self.slot_req[i] is rq:
                    steps[i] += S
        return steps

    def _pick_spec_block(self, deficits: list[int]) -> int:
        """Fused spec-steps bucket: sized to the smallest GUARANTEED
        remaining need (each step advances >= 1 token), so a finishing
        request frees its slot without riding out a long block. Buckets
        stop at 4: a spec step can emit up to k+1 tokens, and the
        optimistic dispatch gate stops issuing blocks once in-flight
        steps COULD satisfy every request — a coarser bucket would turn
        that possibility into up to a whole wasted block of verifies."""
        want = max(1, min(deficits))
        for b in self._SPEC_BUCKETS:
            if want <= b:
                return b
        return self._SPEC_BUCKETS[-1]

    def _host_drafts(self, spec_ok):
        """Drafter-hook path: ask ``spec_drafter(context, pos, k)`` for
        up to k draft tokens per live greedy slot. ``context`` is the
        slot's token history through the pending input (a numpy view),
        ``pos`` its length minus one — the small-model-on-TPU hook rides
        here."""
        k = self.spec_k
        drafts = np.zeros((self.B, k), np.int32)
        dlens = np.zeros(self.B, np.int32)
        for i, req in enumerate(self.slot_req):
            if req is None or not spec_ok[i]:
                continue
            n = int(self.seq_lens[i])
            got = list(self.spec_drafter(self.hist[i, :n + 1], n, k))[:k]
            drafts[i, :len(got)] = got
            dlens[i] = len(got)
        return drafts, dlens

    def _emit_spec_block(self, entry) -> None:
        """Host-side emission of one synced speculative block: per step
        and slot, emit the first ``n_emit`` candidate tokens (the
        accepted drafts plus the target's correction/bonus token) and
        discard the rest — the rejected tail's rollback is exactly this
        truncation plus the seq_lens arithmetic (the junk KV those
        positions hold is overwritten when they are legitimately
        decoded)."""
        S, toks, n_emit, n_prop, snapshot, spec_snap = entry
        with tracing.phase("engine.block_sync", steps=S):
            toks = np.asarray(toks)      # [S, B, k+1]; ONE sync per block
            n_emit = np.asarray(n_emit)  # [S, B]
            n_prop = np.asarray(n_prop)
        self.steps += S
        self.spec_steps += S
        emitted = proposed = accepted = 0
        H = self.hist.shape[1]
        with tracing.phase("engine.emit") as ph:
            for s in range(S):
                for i, req in enumerate(snapshot):
                    if req is None:
                        continue
                    ne = int(n_emit[s, i])
                    if ne <= 0:
                        continue
                    live = self.slot_req[i] is req
                    if live:
                        base = int(self.seq_lens[i])
                        self.seq_lens[i] += ne
                    if spec_snap[i] and not req.cancelled:
                        proposed += int(n_prop[s, i])
                        accepted += ne - 1
                    for j in range(ne):
                        if req.cancelled:
                            break  # finished/cancelled mid-block: discard
                        tok = int(toks[s, i, j])
                        if live:
                            self.next_tok[i] = tok
                            if base + j + 1 < H:
                                self.hist[i, base + j + 1] = tok
                        emitted += 1
                        self._emit(req, tok)
            ph.set(tokens=emitted)
        self.spec_proposed += proposed
        self.spec_accepted += accepted
        self._block_log.append((S, emitted, proposed, accepted))

    async def _loop_spec(self):
        """Speculative driver (reactive shape, README § Speculative
        decoding): with the on-device n-gram drafter the whole
        draft→verify→accept cycle lives inside ``paged_decode_spec``'s
        scan, the (token, position, history) carry chains on device, and
        blocks pipeline 2-deep exactly like ``_loop_reactive``. With a
        host ``spec_drafter`` hook each dispatch is one verify step and
        syncs immediately — the drafter needs the accepted tokens before
        it can propose the next window."""
        pending: list = []
        carry = None  # (tok_dev, lens_dev, hist_dev) between blocks
        # device uploads of the per-slot tables (page_tables/aids/temps/
        # active/spec_ok): these only change at admission/free points,
        # exactly where carry resets — hoisting them out of the dispatch
        # keeps the per-block host cost at one RNG split + one append
        # (spec blocks are smaller than plain blocks, so per-dispatch
        # overhead multiplies faster here)
        statics = None
        k = self.spec_k
        host_draft = callable(self.spec_drafter)

        def drain():
            while pending:
                self._emit_spec_block(pending.pop(0))

        while self._running:
            if not pending:  # free only with no block in flight
                self._sweep()
            if self._slot_for_the_head():
                drain()  # admission changes device-visible state
                self._sweep()
                if await self._admit_wave():
                    carry = None
                    # flush the just-emitted prefill tokens (TTFC) before
                    # the next spec dispatch occupies the loop thread
                    await self._yield()
            active = np.array([r is not None for r in self.slot_req])
            if not active.any():
                drain()
                carry = None
                await self._idle()
                continue
            # optimistic dispatch gate: a spec step emits 1..k+1 tokens,
            # so in-flight blocks COULD have satisfied a request long
            # before the 1-token lower bound says so. Once every live
            # request's optimistic bound (emitted + (k+1) x in-flight
            # steps) covers its budget, SYNC the oldest block instead of
            # dispatching — at high accept rates this is what keeps the
            # loop from verifying junk a finished request will discard;
            # when acceptance was actually low the sync corrects the
            # bound from real emissions and dispatch resumes.
            inflight = self._spec_inflight_steps(pending)
            deficits = [r.max_tokens - r.emitted - (k + 1) * inflight[i]
                        for i, r in enumerate(self.slot_req)
                        if r is not None and not r.cancelled]
            if not deficits or max(deficits) <= 0:
                if pending:
                    self._emit_spec_block(pending.pop(0))
                else:
                    await self._idle(timeout=0.05)
                if any(r is not None and r.cancelled
                       for r in self.slot_req):
                    drain()
                    carry = None
                await self._yield()
                continue
            with tracing.phase("engine.decode_dispatch",
                               live=int(active.sum())) as ph:
                self._rng, sub = jax.random.split(self._rng)
                if carry is None:
                    carry = (jnp.asarray(self.next_tok.copy()),
                             jnp.asarray(self.seq_lens.copy()),
                             jnp.asarray(self.hist.copy()))
                    statics = None
                if statics is None:
                    spec_ok = np.array([
                        r is not None and not r.cancelled and r.spec
                        and r.temperature <= 0 for r in self.slot_req])
                    statics = (jnp.asarray(self.aids.copy()),
                               jnp.asarray(self.tables[0].copy()),
                               jnp.asarray(active),
                               jnp.asarray(spec_ok),
                               jnp.asarray(self.temps.copy()),
                               spec_ok)
                aids_d, pt_d, act_d, sok_d, tmp_d, spec_ok = statics
                tok_d, lens_d, hist_d = carry
                if host_draft:
                    drafts, dlens = self._host_drafts(spec_ok)
                    ph.set(steps=1)
                    (toks, n_emit, n_prop, tok_d, lens_d,
                     *cache) = await self._call(
                        ph, self.programs.decode_verify, self.params,
                        self.loras, aids_d, tok_d, lens_d,
                        jnp.asarray(drafts), pt_d, *self.cache,
                        jnp.asarray(dlens), act_d, tmp_d, sub, self.cfg, k)
                else:
                    S = self._pick_spec_block([d for d in deficits if d > 0])
                    ph.set(steps=S)
                    if chaos.ENABLED:
                        # "llm.spec_block": fires once per fused
                        # speculative block — a seeded kill here dies
                        # MID-speculative-window (accepted-but-unsynced
                        # tokens in flight), the recovery window
                        # tests/plans/spec_decode_kill exercises
                        chaos.point("llm.spec_block", steps=S, k=k)
                    (toks, n_emit, n_prop, tok_d, lens_d, hist_d,
                     *cache) = await self._call(
                        ph, self.programs.decode_spec, self.params,
                        self.loras, aids_d, tok_d, lens_d, hist_d, pt_d,
                        *self.cache, act_d, sok_d, tmp_d, sub, self.cfg, S,
                        k, self.spec_ngram)
                self.cache = tuple(cache)
            if host_draft:
                self._emit_spec_block((1, toks[None], n_emit[None],
                                       n_prop[None], list(self.slot_req),
                                       spec_ok))
                carry = None  # host state is authoritative per step
            else:
                carry = (tok_d, lens_d, hist_d)
                pending.append((S, toks, n_emit, n_prop,
                                list(self.slot_req), spec_ok))
                if len(pending) >= 2:
                    self._emit_spec_block(pending.pop(0))
            if any(r is not None and r.cancelled for r in self.slot_req):
                drain()
                carry = None
            await self._yield()


class _FreePages(collections.abc.Sequence):
    """A kind's free pages: a map (``is_free[p]`` — a list cannot say whether
    the page after a slot's last one is free) that reads as the ascending list
    of them, which is what an idle engine's next request draws from the head
    of (the tests and the benchmark's replicas slice it to know where a
    request's K and V will lie). Page 0 is the junk page, never free."""

    def __init__(self, n_pages: int):
        self.is_free = np.ones(n_pages, bool)
        self.is_free[0] = False
        self.count = n_pages - 1

    def __len__(self) -> int:
        return self.count

    def __getitem__(self, i):
        return np.flatnonzero(self.is_free)[i]

    def __iter__(self):
        return iter(np.flatnonzero(self.is_free).tolist())

    def give(self, pages) -> None:
        self.is_free[pages] = True
        self.count += len(pages)

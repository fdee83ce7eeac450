"""Prefill/decode worker pools — the two halves of disaggregated serving.

DistServe's observation (Zhong et al., OSDI'24): prefill is a
throughput-bound batch matmul, decode is a latency-bound memory-bound
loop, and colocating them makes each the other's tail. Here the two
phases run in SEPARATE actor pools connected only by the KV-page plane:

- :class:`PrefillWorker` owns a transient paged pool. Concurrent
  ``prefill`` calls accumulate into padded waves (one
  ``paged_prefill_batch`` dispatch per pad bucket — the engine's own
  admission-wave shape, run standalone); each prompt's pages are then
  sealed into the local shm arena (:func:`ship_pages`) and the pool rows
  are freed immediately — the pool is a staging buffer, the shm arena is
  the KV's home. A ``prefix`` manifest switches the call onto
  ``paged_prefill_suffix``: cached prefix pages are adopted into the
  staging pool verbatim and only the suffix runs through the model.
- :class:`DecodeWorker` wraps the continuous-batching engine. It admits
  requests ONLY with adopted KV (``submit_prefilled``): the engine's
  decode ring never runs a prefill, so admission cost is one page
  scatter and long prompts can no longer stall resident decodes.

Queue-time telemetry: every prefill job records ``prefill_queue`` (enqueue
-> wave dispatch) and every adopted request records ``decode_queue``
(submit -> first slot grant), the two legs a disaggregated request can
starve in; ``kv_ship`` is recorded by the plane itself.
"""

from __future__ import annotations

import asyncio
import functools
import time
from dataclasses import dataclass, field

import numpy as np

from ray_tpu.core.ref import ObjectLostError
from ray_tpu.devtools import chaos
from ray_tpu.llm import engine as _engine
from ray_tpu.llm import llama as _llama
from ray_tpu.llm.disagg import telemetry
from ray_tpu.llm.disagg.kv_plane import (
    KVPageManifest,
    KVShipError,
    adopt_pages,
    ship_pages,
)
from ray_tpu.llm.programs import UnsupportedByModel, serving_programs


def _resolve_params(model_config, params, params_fn):
    # both workers ship and adopt K and V page stacks (kv_plane.py): a
    # family whose cache is something else is refused before anything loads
    programs = serving_programs(model_config)
    if not programs.page_plane:
        raise UnsupportedByModel(
            "disaggregated serving (disagg/kv_plane.py)", programs)
    if params is None:
        params = params_fn() if params_fn is not None else None
    if params is None:
        import jax

        from ray_tpu.models.llama import llama_init

        params = llama_init(jax.random.PRNGKey(0), model_config)
    return params


@dataclass
class _Job:
    tokens: list[int]
    temperature: float
    aid: int
    prefix: KVPageManifest | None
    fut: asyncio.Future
    t_enq: int = field(default_factory=time.perf_counter_ns)
    # owning request's (trace_id, span_id), captured ONCE at enqueue —
    # the wave loop runs outside the request's context, so batch-stamped
    # telemetry (queue span, page-seal span) carries this instead
    tctx: tuple | None = None


class PrefillWorker:
    """Stateless-per-request prefill actor: prompts in, manifests out.

    Run with ``max_concurrency > 1`` so concurrent calls can coalesce
    into one padded wave (the scheduler's pool factory does this)."""

    #: wave padding buckets, shared shape discipline with the engine
    _WAVE_BUCKETS = _engine.ContinuousBatchingEngine._WAVE_BUCKETS

    def __init__(self, model_config, params=None, params_fn=None, *,
                 page_size: int = 16, n_pages: int = 256,
                 max_wave: int = 8, wave_wait_s: float = 0.004,
                 kv_dtype: str | None = None,
                 lora_adapters: dict | None = None, lora_rank: int = 8,
                 seed: int = 0):
        from ray_tpu.utils.device import configure_jax

        configure_jax()
        import jax

        self.cfg = model_config
        self.params = _resolve_params(model_config, params, params_fn)
        self.PS = page_size
        self.n_pages = n_pages
        self.kv_dtype = kv_dtype or "native"
        self.kpool, self.vpool = _llama.make_kv_pools(
            model_config, page_size, n_pages, kv_dtype)
        self.free_pages = list(range(1, n_pages))  # page 0 = junk page
        self.loras = None
        self.lora_index = {"__base__": 0}
        if lora_adapters:
            self.loras, self.lora_index = _llama.make_lora_stack(
                model_config, lora_adapters, lora_rank)
        self.max_wave = max_wave
        self.wave_wait_s = wave_wait_s
        self._rng = jax.random.PRNGKey(seed)
        self._pending: list[_Job] = []
        self._arrived: asyncio.Event | None = None
        self._task = None
        self.waves = 0

    # ------------------------------------------------------------- public
    async def prefill(self, token_ids, *, temperature: float = 0.0,
                      adapter: str | None = None,
                      prefix: KVPageManifest | None = None):
        """Prefill one prompt — or, with ``prefix``, only its suffix over
        the cached prefix pages — and return ``(manifest, first_token)``.
        The manifest covers exactly the pages THIS call produced (the
        suffix pages when ``prefix`` is given); adoption appends them to
        the prefix's. Concurrent calls batch into one padded wave."""
        aid = self.lora_index.get(adapter or "__base__")
        if aid is None:
            raise ValueError(f"unknown LoRA adapter {adapter!r} "
                             f"(loaded: {sorted(self.lora_index)})")
        tokens = [int(t) for t in token_ids]
        if prefix is not None:
            if prefix.n_tokens % self.PS:
                raise ValueError(
                    f"prefix must be page-aligned, got {prefix.n_tokens} "
                    f"tokens at page_size {self.PS}")
            if prefix.kv_dtype != self.kv_dtype:
                raise ValueError(
                    f"prefix kv_dtype {prefix.kv_dtype!r} != pool "
                    f"{self.kv_dtype!r}")
            if not tokens:
                raise ValueError("suffix prefill needs >= 1 suffix token")
        need = self._pages_needed(tokens, prefix)
        if need > self.n_pages - 1:
            raise ValueError(
                f"prompt needs {need} staging pages but the prefill pool "
                f"only has {self.n_pages - 1}")
        loop = asyncio.get_running_loop()
        if self._arrived is None:
            self._arrived = asyncio.Event()
        if self._task is None or self._task.done():
            self._task = loop.create_task(self._wave_loop())
        job = _Job(tokens, float(temperature), aid, prefix,
                   loop.create_future(),
                   tctx=telemetry.capture_trace_ctx())
        self._pending.append(job)
        self._arrived.set()
        return await job.fut

    def headroom(self) -> dict:
        return {"free_pages": len(self.free_pages),
                "pending": len(self._pending),
                "page_size": self.PS, "kv_dtype": self.kv_dtype}

    def disagg_counters(self) -> dict:
        """This process's KV-plane byte/op ledger (the scheduler sums
        these across the pool for the zero-copy proof)."""
        return telemetry.counters()

    # ---------------------------------------------------------- internals
    def _pages_needed(self, tokens: list[int], prefix) -> int:
        if prefix is None:
            return -(-len(tokens) // self.PS)
        return prefix.n_pages + -(-len(tokens) // self.PS)

    async def _wave_loop(self):
        while True:
            while not self._pending:
                self._arrived.clear()
                await self._arrived.wait()
            # let a wave accumulate: concurrent callers land within this
            # window and share one dispatch
            await asyncio.sleep(self.wave_wait_s)
            wave: list[_Job] = []
            free = len(self.free_pages)
            while self._pending and len(wave) < self.max_wave:
                need = self._pages_needed(self._pending[0].tokens,
                                          self._pending[0].prefix)
                if need > free and wave:
                    break  # next wave, once these pages are freed
                job = self._pending.pop(0)
                free -= need
                wave.append(job)
            try:
                await self._dispatch_wave(wave)
            except Exception as e:  # noqa: BLE001 — fail the wave's callers
                for job in wave:
                    if not job.fut.done():
                        job.fut.set_exception(e)

    def _alloc(self, n: int) -> list[int]:
        if n > len(self.free_pages):
            # can only happen if pages leaked — a short allocation would
            # leave page-table slots at 0 and silently write KV into the
            # shared junk page
            raise RuntimeError(
                f"staging pool exhausted: need {n} pages, "
                f"{len(self.free_pages)} free")
        out = self.free_pages[:n]
        del self.free_pages[:n]
        return out

    async def _dispatch_wave(self, wave: list[_Job]):
        t_dispatch = time.perf_counter_ns()
        full: dict[int, list[_Job]] = {}
        sfx: dict[tuple[int, int], list[_Job]] = {}
        for job in wave:
            telemetry.record(telemetry.PREFILL_QUEUE,
                             t_dispatch - job.t_enq, trace_ctx=job.tctx)
            if job.prefix is None:
                Tp_pad = -(-len(job.tokens) // self.PS) * self.PS
                full.setdefault(Tp_pad, []).append(job)
            else:
                Ts_pad = -(-len(job.tokens) // self.PS) * self.PS
                W = job.prefix.n_pages + Ts_pad // self.PS
                sfx.setdefault((Ts_pad, W), []).append(job)
        self.waves += bool(wave)
        for Tp_pad, jobs in full.items():
            self._dispatch_full(Tp_pad, jobs)
        for (Ts_pad, W), jobs in sfx.items():
            await self._dispatch_suffix(Ts_pad, W, jobs)

    def _bucket(self, n: int) -> int:
        return (next(b for b in self._WAVE_BUCKETS if b >= n)
                if n <= self._WAVE_BUCKETS[-1] else n)

    def _finish(self, jobs, first, pages_of):
        """Ship each job's freshly written pages, free the staging rows,
        resolve the futures."""
        first = np.asarray(first)  # ONE sync for the whole group
        for j, job in enumerate(jobs):
            try:
                m = ship_pages(self.kpool, self.vpool, pages_of[j],
                               job.tokens, page_size=self.PS,
                               kv_dtype=self.kv_dtype, trace_ctx=job.tctx)
            except Exception as e:  # noqa: BLE001 — per-job failure
                job.fut.set_exception(e)
                continue
            finally:
                self.free_pages.extend(pages_of[j])
            telemetry.count(
                **{"prefills" if job.prefix is None else "suffix_prefills":
                   1})
            job.fut.set_result((m, int(first[j])))

    def _dispatch_full(self, Tp_pad: int, jobs: list[_Job]):
        import jax
        import jax.numpy as jnp

        npages = Tp_pad // self.PS
        nb = self._bucket(len(jobs))
        toks = np.zeros((nb, Tp_pad), np.int32)
        pages = np.zeros((nb, npages), np.int32)  # dummy rows: junk page
        aids = np.zeros(nb, np.int32)
        true_lens = np.ones(nb, np.int32)
        temps = np.zeros(nb, np.float32)
        pages_of = []
        try:
            for j, job in enumerate(jobs):
                mine = self._alloc(-(-len(job.tokens) // self.PS))
                pages_of.append(mine)
                toks[j, :len(job.tokens)] = job.tokens
                pages[j, :len(mine)] = mine
                aids[j] = job.aid
                true_lens[j] = len(job.tokens)
                temps[j] = job.temperature
            self._rng, sub = jax.random.split(self._rng)
            first, self.kpool, self.vpool = _llama.paged_prefill_batch(
                self.params, self.loras, jnp.asarray(aids),
                jnp.asarray(toks), jnp.asarray(pages), self.kpool,
                self.vpool, jnp.asarray(true_lens), jnp.asarray(temps),
                sub, self.cfg)
        except BaseException:
            # a failed dispatch must not leak staging rows — _finish
            # (which normally frees them per job) never ran
            for rows in pages_of:
                self.free_pages.extend(rows)
            raise
        self._finish(jobs, first, pages_of)

    async def _dispatch_suffix(self, Ts_pad: int, W: int, jobs: list[_Job]):
        """Suffix wave: adopt each job's cached prefix pages into the
        staging pool (zero-copy when the cache lives on this node), then
        run ONLY the suffix through the model.

        Adoption runs off the event loop: with >1 prefill worker a
        suffix prefix may be sealed by a sibling whose loop is likewise
        inside a suffix wave — a blocking fetch here deadlocks both."""
        import jax
        import jax.numpy as jnp

        loop = asyncio.get_running_loop()
        nb = self._bucket(len(jobs))
        toks = np.zeros((nb, Ts_pad), np.int32)
        pages = np.zeros((nb, W), np.int32)
        aids = np.zeros(nb, np.int32)
        prefix_lens = np.zeros(nb, np.int32)
        true_lens = np.ones(nb, np.int32)
        temps = np.zeros(nb, np.float32)
        pages_of = []   # suffix pages: shipped then freed
        adopted_of = []  # prefix staging pages: freed, never shipped
        try:
            # overlap the jobs' independent prefix fetches (each may pull
            # a sibling worker's pages through the object plane) instead
            # of paying one serial round trip per cache hit
            stacks = await asyncio.gather(*(
                loop.run_in_executor(
                    None, functools.partial(adopt_pages, job.prefix,
                                            role="prefill"))
                for job in jobs))
            for j, job in enumerate(jobs):
                k = job.prefix.n_pages
                prows = self._alloc(k)
                adopted_of.append(prows)
                k_stack, v_stack = stacks[j]
                self.kpool = _llama.scatter_pages(self.kpool, prows, k_stack)
                self.vpool = _llama.scatter_pages(self.vpool, prows, v_stack)
                mine = self._alloc(-(-len(job.tokens) // self.PS))
                pages_of.append(mine)
                toks[j, :len(job.tokens)] = job.tokens
                pages[j, :k] = prows
                pages[j, k:k + len(mine)] = mine
                aids[j] = job.aid
                prefix_lens[j] = job.prefix.n_tokens
                true_lens[j] = len(job.tokens)
                temps[j] = job.temperature
            self._rng, sub = jax.random.split(self._rng)
            first, self.kpool, self.vpool = _llama.paged_prefill_suffix(
                self.params, self.loras, jnp.asarray(aids),
                jnp.asarray(toks), jnp.asarray(pages), self.kpool,
                self.vpool, jnp.asarray(prefix_lens),
                jnp.asarray(true_lens), jnp.asarray(temps), sub, self.cfg)
        except BaseException:
            for rows in (*adopted_of, *pages_of):
                self.free_pages.extend(rows)
            raise
        try:
            self._finish(jobs, first, pages_of)
        finally:
            for prows in adopted_of:
                self.free_pages.extend(prows)


class DecodeWorker:
    """Decode actor: the continuous-batching engine, admitting requests
    only with adopted KV. ``EngineFull`` is translated to the serve
    layer's typed :class:`BackPressureError` here, so an overloaded
    decode pool reads as router/scheduler backpressure, never as an
    untyped actor failure."""

    def __init__(self, model_config, params=None, params_fn=None, *,
                 max_batch: int = 8, page_size: int = 16,
                 n_pages: int = 256, max_seq_len: int = 512,
                 eos_id: int | None = None, kv_dtype: str | None = None,
                 lora_adapters: dict | None = None, lora_rank: int = 8,
                 max_waiting: int = 256, spec_enable: bool = False,
                 spec_k: int = 4, spec_ngram: int = 2, spec_drafter=None):
        from ray_tpu.utils.device import configure_jax

        configure_jax()
        params = _resolve_params(model_config, params, params_fn)
        self.engine = _engine.ContinuousBatchingEngine(
            params, model_config, max_batch=max_batch, page_size=page_size,
            n_pages=n_pages, max_seq_len=max_seq_len, eos_id=eos_id,
            lora_adapters=lora_adapters, lora_rank=lora_rank,
            max_waiting=max_waiting, kv_dtype=kv_dtype,
            spec_enable=spec_enable, spec_k=spec_k, spec_ngram=spec_ngram,
            spec_drafter=spec_drafter)
        # live streaming decodes by scheduler-chosen key: the explicit
        # cancel path for streams riding the per-item RPC fallback (the
        # fast lane's abandon reaches the generator's finally directly)
        self._stream_rids: dict[str, int] = {}

    async def decode_adopted(self, token_ids, manifest: KVPageManifest,
                             extra: KVPageManifest | None = None,
                             first_token: int = 0, *, max_tokens: int = 32,
                             temperature: float = 0.0,
                             adapter: str | None = None) -> list[int]:
        """Adopt a prompt's KV pages and decode: returns the full token
        list (``first_token`` first — emission parity with the aggregated
        engine, which emits the prefill token itself). The adoption fetch
        runs on a pool thread so resident decodes never stall behind a
        cross-node page pull."""
        from ray_tpu.serve.exceptions import BackPressureError

        await self.engine.start()
        loop = asyncio.get_running_loop()
        try:
            k_stack, v_stack = await loop.run_in_executor(
                None, adopt_pages, manifest, extra)
        except ObjectLostError as e:
            # normalize onto the plane's typed failure (passthrough-
            # marked): the scheduler re-prefills on it either way
            raise KVShipError(f"adopt: sealed pages lost: {e}") from None
        try:
            rid = self.engine.submit_prefilled(
                [int(t) for t in token_ids], k_stack, v_stack,
                int(first_token), max_tokens=max_tokens,
                temperature=temperature, adapter=adapter)
        except _engine.EngineFull as e:
            raise BackPressureError(
                f"decode engine full: {e}",
                retry_after_s=0.05 * (1 + len(self.engine.waiting)),
            ) from None
        t_submit = time.perf_counter_ns()
        out: list[int] = []
        async for tok in self.engine.stream(rid):
            if not out:
                # first emission == slot grant: the decode-queue leg
                telemetry.record(telemetry.DECODE_QUEUE,
                                 time.perf_counter_ns() - t_submit)
            out.append(tok)
        # refresh the decode-plane signals (tokens-in-flight gauge +
        # spec windows) on the way out — every completed request keeps
        # the scheduler's and the dashboard's numbers fresh
        telemetry.publish_decode_signals(self.engine)
        return out

    async def decode_adopted_stream(self, token_ids,
                                    manifest: KVPageManifest,
                                    extra: KVPageManifest | None = None,
                                    first_token: int = 0, *,
                                    max_tokens: int = 32,
                                    temperature: float = 0.0,
                                    adapter: str | None = None,
                                    cancel_key: str = ""):
        """Streaming twin of :meth:`decode_adopted`: yields token-id
        DELTAS, one list per fused decode block (the engine's
        ``stream_blocks`` coalescing), concatenating to exactly what
        ``decode_adopted`` would have returned. Closing the stream — the
        worker pump's GeneratorExit when the consumer abandons the "G"
        chunk stream, or :meth:`cancel_decode` with ``cancel_key`` on the
        RPC fallback plane — cancels the engine request: the decode slot
        and its KV pages free at the next block boundary, with zero
        duplicate prefill spent."""
        from ray_tpu.serve.exceptions import BackPressureError

        await self.engine.start()
        loop = asyncio.get_running_loop()
        try:
            k_stack, v_stack = await loop.run_in_executor(
                None, adopt_pages, manifest, extra)
        except ObjectLostError as e:
            raise KVShipError(f"adopt: sealed pages lost: {e}") from None
        try:
            rid = self.engine.submit_prefilled(
                [int(t) for t in token_ids], k_stack, v_stack,
                int(first_token), max_tokens=max_tokens,
                temperature=temperature, adapter=adapter)
        except _engine.EngineFull as e:
            raise BackPressureError(
                f"decode engine full: {e}",
                retry_after_s=0.05 * (1 + len(self.engine.waiting)),
            ) from None
        if cancel_key:
            self._stream_rids[cancel_key] = rid
        t_submit = time.perf_counter_ns()
        first = True
        try:
            async for blk in self.engine.stream_blocks(rid):
                if chaos.ENABLED:
                    chaos.point("llm.decode_block", n_tokens=len(blk))
                if first:
                    first = False
                    telemetry.record(telemetry.DECODE_QUEUE,
                                     time.perf_counter_ns() - t_submit)
                yield blk
        finally:
            self.engine.cancel(rid)  # no-op once finished
            if cancel_key:
                self._stream_rids.pop(cancel_key, None)
            telemetry.publish_decode_signals(self.engine)

    def cancel_decode(self, cancel_key: str) -> bool:
        """Cancel a live streaming decode by the scheduler's key —
        the mid-stream disconnect path for streams on the per-item RPC
        fallback, where no ring abandon reaches the generator."""
        rid = self._stream_rids.get(cancel_key)
        if rid is None:
            return False
        self.engine.cancel(rid)
        return True

    def headroom(self) -> dict:
        telemetry.publish_decode_signals(self.engine)
        return self.engine.headroom()

    def engine_stats(self) -> dict:
        return {"steps": self.engine.steps,
                "tokens_out": self.engine.tokens_out,
                "waiting": len(self.engine.waiting),
                "free_pages": len(self.engine.free_pages),
                "tokens_in_flight": self.engine.tokens_in_flight(),
                **{k: v for k, v in self.engine.spec_stats().items()
                   if k != "blocks"}}

    def disagg_counters(self) -> dict:
        return telemetry.counters()

    async def stop(self):
        await self.engine.stop()

"""LLM serving deployment: batched decode behind ray_tpu.serve.

TPU-native counterpart of the reference serve-LLM stack (ref:
python/ray/llm/_internal/serve/ — LLMServer + vLLM engine + OpenAI
router). The deployment batches concurrent requests into ONE generate
call via @serve.batch (the MXU wants batch-N decode, not N batch-1
loops) and exposes an OpenAI-completions-shaped dict protocol that the
HTTP proxy serves at /{app}/LLMServer.
"""
from __future__ import annotations

import time


class LLMServer:
    """Deployment class; bind with a model config + params source."""

    def __init__(self, model_config, params=None, params_fn=None,
                 max_batch_size: int = 8, batch_wait_timeout_s: float = 0.02,
                 default_max_tokens: int = 32):
        from ray_tpu import serve
        from ray_tpu.utils.device import configure_jax

        configure_jax()
        self.cfg = model_config
        if params is None:
            params = params_fn() if params_fn is not None else None
        if params is None:
            import jax

            from ray_tpu.models.llama import llama_init

            params = llama_init(jax.random.PRNGKey(0), model_config)
        self.params = params
        self.default_max_tokens = default_max_tokens
        self._batched = serve.batch(
            max_batch_size=max_batch_size,
            batch_wait_timeout_s=batch_wait_timeout_s,
        )(self._generate_batch)

    async def _generate_batch(self, requests: list[dict]) -> list[dict]:
        from ray_tpu.llm.generation import generate

        t0 = time.monotonic()
        max_new = max(
            int(r.get("max_tokens", self.default_max_tokens)) for r in requests
        )
        # sampling settings are per-request: decode one sub-batch per
        # distinct temperature so no request's settings are overridden
        by_temp: dict[float, list[int]] = {}
        for i, r in enumerate(requests):
            by_temp.setdefault(float(r.get("temperature", 0.0)), []).append(i)
        outs: list = [None] * len(requests)
        for temp, idxs in by_temp.items():
            sub = generate(
                self.params, self.cfg,
                [list(requests[i]["prompt_tokens"]) for i in idxs],
                max_new_tokens=max_new, temperature=temp,
            )
            for i, o in zip(idxs, sub):
                outs[i] = o
        dt = time.monotonic() - t0
        results = []
        for r, out in zip(requests, outs):
            want = int(r.get("max_tokens", self.default_max_tokens))
            results.append({
                "completion_tokens": out[:want],
                "usage": {
                    "prompt_tokens": len(r["prompt_tokens"]),
                    "completion_tokens": want,
                    "batch_size": len(requests),
                    "latency_s": dt,
                },
            })
        return results

    async def __call__(self, request: dict) -> dict:
        """request: {prompt_tokens: [...], max_tokens?, temperature?}"""
        return await self._batched(request)


class LLMEngineServer:
    """Deployment around the continuous-batching engine (ref: the vLLM
    engine the reference delegates to, vllm_engine.py:95 — owned here).
    Requests join the running decode batch at step granularity; responses
    can stream token-by-token; "model" selects a LoRA adapter
    (ref: serve/multiplex.py model multiplexing). The model family is the
    config's type (``LlamaConfig``, ``MlaMoeConfig``): the engine takes its
    programs and its cache from it, and this deployment is the same call
    for both."""

    def __init__(self, model_config, params=None, params_fn=None, *,
                 max_batch: int = 8, page_size: int = 16, n_pages: int = 512,
                 max_seq_len: int = 512, eos_id: int | None = None,
                 lora_adapters: dict | None = None, lora_rank: int = 8,
                 default_max_tokens: int = 32, kv_dtype: str | None = None):
        # a replica runs jax; imported before configure_jax() so that a
        # process pinned to the CPU without it hears of its programs too
        import jax

        from ray_tpu.utils import tracing
        from ray_tpu.utils.device import configure_jax

        configure_jax()
        with tracing.stage("weights"):
            if params is None:
                params = params_fn() if params_fn is not None else None
            if params is None:
                from ray_tpu.llm.programs import serving_programs

                params = serving_programs(model_config).init(
                    jax.random.PRNGKey(0), model_config)
        from ray_tpu.llm.engine import ContinuousBatchingEngine

        self.engine = ContinuousBatchingEngine(
            params, model_config, max_batch=max_batch, page_size=page_size,
            n_pages=n_pages, max_seq_len=max_seq_len, eos_id=eos_id,
            lora_adapters=lora_adapters, lora_rank=lora_rank,
            kv_dtype=kv_dtype)
        self.default_max_tokens = default_max_tokens

    async def _ensure_started(self):
        await self.engine.start()

    def _submit(self, request: dict) -> int:
        from ray_tpu.llm.engine import EngineFull
        from ray_tpu.serve.exceptions import BackPressureError

        try:
            return self.engine.submit(
                list(request["prompt_tokens"]),
                max_tokens=int(request.get("max_tokens",
                                           self.default_max_tokens)),
                temperature=float(request.get("temperature", 0.0)),
                adapter=request.get("model"),
            )
        except EngineFull as e:
            # typed, never-dispatched refusal: the PR 6 router retries /
            # hedges this request on another replica instead of surfacing
            # an untyped ActorError from an overloaded engine
            raise BackPressureError(
                f"LLM engine full: {e}",
                # a waiting slot frees at decode-block granularity; queue
                # depth is the best local estimate of the drain time
                retry_after_s=min(2.0,
                                  0.02 * (1 + len(self.engine.waiting))),
            ) from None

    async def __call__(self, request: dict) -> dict:
        """Full completion: {prompt_tokens, max_tokens?, temperature?,
        model?} -> {completion_tokens, usage}."""
        await self._ensure_started()
        t0 = time.monotonic()
        rid = self._submit(request)
        # block-granular drain: the engine emits whole fused decode
        # blocks host-side, so draining per block costs one loop wake per
        # block instead of one per token
        out: list[int] = []
        async for blk in self.engine.stream_blocks(rid):
            out.extend(blk)
        return {
            "completion_tokens": out,
            "usage": {
                "prompt_tokens": len(request["prompt_tokens"]),
                "completion_tokens": len(out),
                "latency_s": time.monotonic() - t0,
            },
        }

    async def stream(self, request: dict):
        """Async generator of token ids — served to callers through the
        handle's .stream() (one ObjectRef per token). An abandoned
        consumer cancels the request: the decode slot and its KV pages
        free at the next block boundary, not when the generation would
        have finished."""
        await self._ensure_started()
        rid = self._submit(request)
        try:
            async for tok in self.engine.stream(rid):
                yield tok
        finally:
            self.engine.cancel(rid)  # no-op once finished

    async def stream_deltas(self, request: dict):
        """Streaming-serve producer: one ``{"tokens": [...]}`` delta per
        fused decode block (served as one "G" chunk record each through
        the handle's ``.stream_chunks()``), then a terminal delta with
        ``usage``. Token-identical to ``__call__``'s completion_tokens.
        Closing the stream mid-generation cancels the engine request —
        the replica wrapper's GeneratorExit reaches the ``finally`` here
        and the decode slot frees at the next block boundary."""
        await self._ensure_started()
        t0 = time.monotonic()
        rid = self._submit(request)
        n = 0
        try:
            async for blk in self.engine.stream_blocks(rid):
                n += len(blk)
                yield {"tokens": blk}
            yield {
                "tokens": [],
                "done": True,
                "usage": {
                    "prompt_tokens": len(request["prompt_tokens"]),
                    "completion_tokens": n,
                    "latency_s": time.monotonic() - t0,
                },
            }
        finally:
            self.engine.cancel(rid)  # no-op once finished

    def engine_stats(self) -> dict:
        """Counters of this replica's engine. ``free_pages``: the pages a
        newcomer could still be promised, as the disagg scheduler reads it
        (``ContinuousBatchingEngine.headroom``; ``free_pages_now``: those not
        drawn this instant). ``stages``: cumulative sum
        and count of every stage family of ``utils/metrics.py`` in this
        process — the engine loop's phases, a request's queue, prefill
        and decode waits, the prefill counters, the lane's two legs, and
        bring-up by stage (``rt_bringup_seconds``). ``program_builds``: of
        every program the engine got ready, whether it was compiled or read
        from the compile cache and the seconds of each step
        (``ContinuousBatchingEngine.program_builds``).
        ``weights_prepared``: parameter trees the engine has taken and laid
        out for serving (1 unless somebody assigned ``engine.params``). While
        a ``jax.profiler`` trace is on, also ``program_parts``: whoever
        takes the trace needs the table to read it by layer part, and
        nobody else is sent it."""
        from ray_tpu.utils import metrics, tracing

        out = {"steps": self.engine.steps, "tokens_out": self.engine.tokens_out,
               "waiting": len(self.engine.waiting),
               **{k: v for k, v in self.engine.headroom().items()
                  if k in ("free_pages", "free_pages_now")},
               "weights_prepared": self.engine.weights_prepared,
               "program_builds": self.engine.program_builds(),
               "stages": metrics.stage_totals()}
        if tracing.profiling():
            out["program_parts"] = self.engine.program_parts()
        return out

    def program_parts(self) -> dict:
        """The engine's ``program_parts``: an operator joins their own
        profiler trace with it."""
        return self.engine.program_parts()

    def device_report(self) -> dict:
        """The device this replica really serves from and its memory high
        water mark."""
        from ray_tpu.utils.device import device_report

        return device_report()


def build_llm_engine_deployment(model_config, *, params=None, params_fn=None,
                                num_replicas: int = 1, num_tpus: float = 0.0,
                                name: str = "LLMEngineServer", **engine_kw):
    """Bound serve application around the owned engine."""
    from ray_tpu import serve

    opts: dict = {}
    if num_tpus:
        opts["num_tpus"] = num_tpus
    dep = serve.deployment(
        LLMEngineServer,
        name=name,
        num_replicas=num_replicas,
        max_ongoing_requests=64,
        ray_actor_options=opts,
    )
    return dep.bind(model_config, params, params_fn, **engine_kw)


def build_llm_deployment(model_config, *, params=None, params_fn=None,
                         num_replicas: int = 1, max_batch_size: int = 8,
                         num_tpus: float = 0.0, name: str = "LLMServer"):
    """Bound serve application for a Llama config (ref: serve/llm
    build_openai_app shape)."""
    from ray_tpu import serve

    opts: dict = {}
    if num_tpus:
        opts["num_tpus"] = num_tpus
    dep = serve.deployment(
        LLMServer,
        name=name,
        num_replicas=num_replicas,
        max_ongoing_requests=max_batch_size * 2,
        ray_actor_options=opts,
    )
    return dep.bind(model_config, params, params_fn, max_batch_size)

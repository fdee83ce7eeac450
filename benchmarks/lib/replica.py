"""The benchmark's subclass of the program's ``LLMEngineServer``: the same
deployment, engine and loop, plus what the benchmark reads from outside the
program — a replica-side stamp at the first token of the program's own
stream, the engine's counters and its set of compiled programs, a warm-up
that reaches every program the traffic file can reach, the comparison with
the plain reference, and the profiler's switch. Runs in the worker that holds
the chip."""
from __future__ import annotations

import asyncio
import time

from ray_tpu.llm.serving import LLMEngineServer


def make_params_fn(cfg, seed: int, zero_col: int | None):
    def params_fn():
        from ray_tpu.utils.device import configure_jax

        configure_jax()
        from benchmarks.lib import weights

        return weights.make_params(weights.seed_key(seed), cfg, zero_col)

    return params_fn


class BenchEngineServer(LLMEngineServer):
    async def stream_deltas(self, request: dict):
        """The program's own ``stream_deltas``, passed through; the terminal
        delta's usage also carries the seconds from the call to the first
        delta with tokens, as this replica saw them."""
        t0 = time.monotonic()
        first = None
        deltas = super().stream_deltas(request)
        try:
            async for delta in deltas:
                if first is None and delta["tokens"]:
                    first = time.monotonic() - t0
                if delta.get("done"):
                    delta = {**delta, "usage": {**delta["usage"],
                                                "replica_ttft_s": first}}
                yield delta
        finally:
            await deltas.aclose()  # a closed stream cancels the engine request

    # ------------------------------------------------------------- counters
    def _program_keys(self) -> list:
        """(program name, wave or batch, pad or steps) of every program the
        engine has got ready through ``_call``."""
        out = []
        for key in self.engine._compiled:
            name = getattr(key[0], "__name__", str(key[0]))
            if name == "paged_prefill_batch":
                out.append((name, *key[2]))          # tokens [wave, pad]
            elif name == "paged_decode_multi":
                out.append((name, self.engine.B, key[-1]))  # n_steps
            else:
                out.append((name, 0, 0))
        return sorted(out)

    def bench_stats(self) -> dict:
        from ray_tpu.utils.device import device_report

        return {**self.engine_stats(), "compiled": len(self.engine._compiled),
                "programs": self._program_keys(),
                "live": sum(r is not None for r in self.engine.slot_req),
                "block_buckets": list(self.engine.block_buckets),
                "device": device_report(), "t": time.monotonic()}

    # --------------------------------------------------------------- warm-up
    async def warm(self, pads: list[int], waves: list[int],
                   vocab: int) -> dict:
        """Reach every program the traffic can reach, through the engine's
        own ``submit``: for each prompt pad and wave bucket, ``wave`` requests
        of that pad submitted without yielding, which the engine admits as
        one wave; then the decode block buckets, by ``_pick_block``'s own
        rules (a lone request ramps 8, 16, 32; a remainder of 4 takes the
        4-bucket; half the slots full with 64 to go takes the 64-bucket)."""
        await self._ensure_started()
        eng = self.engine
        t0 = time.monotonic()

        async def drain(rids):
            for rid in rids:
                async for _ in eng.stream_blocks(rid):
                    pass

        async def wave_of(n, pad, max_tokens):
            # a wave only forms on an idle engine with n free slots
            prompt = [3 + (i % (vocab - 3)) for i in range(pad)]
            await drain([eng.submit(prompt, max_tokens=max_tokens)
                         for _ in range(n)])

        for pad in pads:
            for wave in waves:
                if wave <= eng.B:
                    await wave_of(wave, pad, 1)
        t_prefill = time.monotonic() - t0
        small = min(pads)
        await wave_of(1, small, 1 + 4)             # block 4
        await wave_of(1, small, 1 + 8 + 16 + 32)   # blocks 8, 16, 32
        half = -(-eng.B // 2)
        await wave_of(half, small, 1 + 64)         # block 64 (high occupancy)
        want = {("paged_prefill_batch", w, p) for p in pads for w in waves
                if w <= eng.B}
        # 1 is ``_pick_block``'s answer when every slot's request has just
        # finished (here: the one-token waves above) and not yet been freed
        want |= {("paged_decode_multi", eng.B, b) for b in (1, *eng.block_buckets)}
        have = {tuple(k) for k in self._program_keys()}
        return {"prefill_s": t_prefill, "total_s": time.monotonic() - t0,
                "missing": sorted(want - have), "programs": len(have)}

    # ------------------------------------------------ the plain reference
    async def reference_check(self, seed: int, cfg, prompt_len: int,
                              max_tokens: int, zero_col: int | None,
                              mode: str = "float32") -> dict:
        """Prefill then decode through the paged cache, against the float32
        reference's full forward pass over the same tokens. The program gives
        out tokens and no logits, so what is compared is (a) the last layer's
        keys and values as the program left them in its pool — they hold all
        13 layers' attention and feed-forward work for every prompt position
        (prefill) and every decoded position (decode through the page table)
        — and (b) each emitted token against the reference's logits: the
        reference's best logit less its logit for the token the program
        chose, in units of that position's logit spread.

        ``mode`` other than float32 is the control: the reference at that
        lower precision stands in the program's place."""
        import jax.numpy as jnp
        import numpy as np

        from benchmarks.lib.configs import load_module
        from benchmarks.lib.traffic import prompt_tokens

        ref = load_module("reference", "dense_gqa")
        await self._ensure_started()
        eng = self.engine
        while any(r is not None for r in eng.slot_req) or eng.waiting:
            await asyncio.sleep(0.05)
        prompt = prompt_tokens(seed, 10**6, prompt_len, cfg.vocab_size)
        n_rows = prompt_len + max_tokens - 1
        pages = list(eng.free_pages[: -(-(prompt_len + max_tokens) // eng.PS)])
        out = await eng.generate(prompt, max_tokens=max_tokens)
        repeats = (await eng.generate(prompt, max_tokens=max_tokens)) == out
        kpool, vpool = eng.kpool, eng.vpool  # read before another request runs

        def compare() -> dict:
            # seconds of compiling and computing: in a thread, so that this
            # replica keeps answering the controller's health probes
            seq = jnp.asarray([prompt + out[:-1]], jnp.int32)
            want = ref.forward(seed, cfg, seq, zero_col=zero_col)
            if mode == "float32":
                idx = jnp.asarray(pages)
                got = {n: pool[cfg.n_layers - 1][idx].reshape(
                           -1, cfg.n_kv_heads, cfg.head_dim)[:n_rows].astype(
                               jnp.float32)
                       for n, pool in (("k", kpool), ("v", vpool))}
                tokens = out
            else:
                low = ref.forward(seed, cfg, seq, mode=mode, zero_col=zero_col)
                got = {"k": low["k"][0], "v": low["v"][0]}
                tokens = [int(t) for t in jnp.argmax(
                    low["logits"][0, prompt_len - 1:], axis=-1)]

            def rel(a, b):
                return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

            split = prompt_len
            logits = np.asarray(want["logits"][0, prompt_len - 1:])
            gap = (logits.max(-1) - logits[np.arange(len(tokens)), tokens]
                   ) / logits.std(-1)
            return {
                "kv_rel_err.prefill": max(
                    rel(got[n][:split], want[n][0, :split]) for n in "kv"),
                "kv_rel_err.decode": max(
                    rel(got[n][split:], want[n][0, split:]) for n in "kv"),
                "token_logit_gap": float(gap.max()),
                # the same greedy request again, on the same idle engine
                "repeats": repeats, "tokens": len(out), "mode": mode,
            }

        return await asyncio.get_running_loop().run_in_executor(None, compare)

    async def reseed(self, seed: int, cfg, zero_col: int | None) -> None:
        """New weights of the same shapes under the same programs (the
        control's tool: a dozen seeds in one set-up). Never used by a run."""
        from benchmarks.lib import weights

        self.engine.params = await asyncio.get_running_loop().run_in_executor(
            None, lambda: weights.make_params(weights.seed_key(seed), cfg,
                                              zero_col))

    # ------------------------------------------------------------- profiler
    def trace_start(self, path: str) -> float:
        import jax

        jax.profiler.start_trace(path)
        return time.monotonic()

    async def trace_stop(self, path: str) -> dict:
        import jax

        from benchmarks.lib.xplane import reduce_trace

        def stop() -> dict:
            stopped = time.monotonic()
            jax.profiler.stop_trace()
            return {**reduce_trace(path), "stopped": stopped}

        return await asyncio.get_running_loop().run_in_executor(None, stop)

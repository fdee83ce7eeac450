"""The benchmark's replica for the looped family: a copy of
``lib/replica_sink_moe.py`` (README_looped.md says what differs) — the same
subclass of the program's ``LLMEngineServer`` as ``lib/replica.py`` (stamps,
counters, profiler), with what names the Llama programs replaced: the program
names and the waves a warm-up has to reach, the seeded weights, and the
comparison with the plain reference, which for this family reads the planes
of EVERY PASS: the rows a layer left at pass 1 stand behind nothing, those it
left at pass 4 behind three whole passes of rounding."""
from __future__ import annotations

import asyncio
import time

from benchmarks.lib.replica import BenchEngineServer

PREFILL, DECODE = "looped_prefill_batch", "looped_decode_multi"


def make_params_fn(cfg, seed: int, eos_id: int | None = None):
    def params_fn():
        from ray_tpu.utils.device import configure_jax

        configure_jax()
        from benchmarks.lib import weights_looped as weights

        return weights.make_params(weights.seed_key(seed), cfg, eos_id)

    return params_fn


def checked_layers(cfg) -> tuple:
    """The layers whose planes are compared: a shallow, a middle, the last."""
    return tuple(sorted({0, cfg.n_layers // 2, cfg.n_layers - 1}))


class LoopedBenchServer(BenchEngineServer):
    def _program_keys(self) -> list:
        out = []
        for key in self.engine._compiled:
            name = getattr(key[0], "__name__", str(key[0]))
            if name == PREFILL:
                out.append((name, *key[2]))                 # tokens [wave, pad]
            elif name == DECODE:
                out.append((name, self.engine.B, key[-1]))  # n_steps
            else:
                out.append((name, 0, 0))
        return sorted(out)

    def _waves(self, pad: int, waves: list[int]) -> list[int]:
        """The wave buckets of ``waves`` that the engine can form at this
        pad: its own split of a group as large as the largest."""
        eng = self.engine
        most = max(len(w) for w in eng._split_wave(pad, [None] * max(waves)))
        return sorted({min(w, most) for w in waves if w <= eng.B})

    async def warm(self, pads: list[int], waves: list[int], vocab: int,
                   check_pads: list[int] = ()) -> dict:
        """``lib/replica_sink_moe.py``'s warm-up through the engine's own
        ``submit`` under this family's program names: each pad's waves cut
        to what the family's wave limit lets the engine form; ``check_pads``
        (the reference checks' own, which the traffic never reaches) one
        prompt each."""
        await self._ensure_started()
        eng = self.engine
        t0 = time.monotonic()

        async def wave_of(n, pad, max_tokens):
            # a wave only forms on an idle engine with n free slots
            prompt = [3 + (i % (vocab - 3)) for i in range(pad)]
            for rid in [eng.submit(prompt, max_tokens=max_tokens)
                        for _ in range(n)]:
                async for _ in eng.stream_blocks(rid):
                    pass

        want = {(PREFILL, w, p) for p in pads for w in self._waves(p, waves)}
        want |= {(PREFILL, 1, p) for p in check_pads}
        for _, wave, pad in sorted(want):
            await wave_of(wave, pad, 1)
        t_prefill = time.monotonic() - t0
        small = min(pads)   # a traffic pad: its waves are warm already
        await wave_of(1, small, 1 + 4)             # block 4
        await wave_of(1, small, 1 + 8 + 16 + 32)   # blocks 8, 16, 32
        half = -(-eng.B // 2)
        await wave_of(half, small, 1 + 64)         # block 64 (high occupancy)
        want |= {(DECODE, eng.B, b) for b in (1, *eng.block_buckets)}
        have = {tuple(k) for k in self._program_keys()}
        return {"prefill_s": t_prefill, "total_s": time.monotonic() - t0,
                "missing": sorted(want - have), "programs": len(have),
                "unwanted": sorted(have - want)}

    async def reference_check(self, seed: int, cfg, prompt_len: int,
                              max_tokens: int, mode: str = "float32",
                              variant: dict | None = None,
                              which: int = 0) -> dict:
        """Prefill of a prompt and then decode through the pages, against the
        float32 reference's full forward pass over the same tokens. The
        program gives out tokens and no logits, so what is compared is what
        it left in its pools — the keys (as they are attended) and values of
        a shallow, a middle and the last layer IN THE PLANES OF ALL PASSES
        (``l``, ``L + l``, ``2 L + l``, ``3 L + l``), prompt positions
        (prefill) and decoded positions (decode: the walk through the table
        at a plane that is a value of the program's loop) apart — and each
        emitted token against the reference's logits:

        * ``kv_rel_err.*``: plane 0's rows (pass 1 of layer 0: before any
          attention — embedding, N1, the projections, the rotation, paging),
          norm-relative over the span's positions, worse of K and V: the
          precision guard;
        * ``pass<u>_kv_rel_err.*``, u = 1 .. 4: the rows of the three layers'
          planes of pass u, norm-relative, the worst of the three layers and
          of K and V. A pass's rows stand on every pass before it: the
          fourth's on three whole passes of rounding, so each pass has a
          limit of its own;
        * ``token_logit_gap*``: the reference's best logit less its logit
          for the emitted token, in logit spreads, the median and the
          largest of a request, both judged: the head's state is chosen by
          the exit rule, which no plane shows, and the median is blind to a
          fault in fewer than half of a request's tokens;
        * ``h43``, ``lam_*``, ``exit_depth_mean``: the reference's own
          ``||h_4 - h_3|| / ||h_4||``, the gate's spread and the mean pass the
          rule chose, over the positions from the prompt's last on
          (reported, never judged).

        ``mode`` other than float32 (the reference at that lower precision)
        or a ``variant`` (the reference with other mathematics, at the
        program's own precision) is the control, the float32 reference as
        published standing in the program's place. ``which`` numbers the
        checked request (its own prompt). The weights are dropped while the
        reference computes and made again from the seed."""
        import jax.numpy as jnp
        import numpy as np

        from benchmarks.lib import weights_looped as weights
        from benchmarks.lib.configs import load_module
        from benchmarks.lib.traffic import prompt_tokens

        ref = load_module("reference", "looped")
        await self._ensure_started()
        eng = self.engine
        eos = eng.eos_id
        while any(r is not None for r in eng.slot_req) or eng.waiting:
            await asyncio.sleep(0.05)
        prompt = prompt_tokens(seed, 10**6 + which, prompt_len, cfg.vocab_size)
        n_rows = prompt_len + max_tokens - 1
        L, U = cfg.n_layers, cfg.n_passes
        planes = [u * L + l for u in range(U) for l in checked_layers(cfg)]
        drawn = list(eng.free[0][:eng._pages_of(prompt_len + max_tokens)[0]])
        out = await eng.generate(prompt, max_tokens=max_tokens)
        repeats = (await eng.generate(prompt, max_tokens=max_tokens)) == out
        got = {}
        if mode == "float32" and not variant:
            kp, vp = eng.cache  # read before another request runs
            at = jnp.asarray(drawn)
            # a plane at a time: a gather out of a whole pool made XLA
            # reserve a copy of the pool (PERF.md section 6, PR 33)
            got = {n: {p: np.asarray(pool[p][at].astype(jnp.float32)).reshape(
                           -1, pool.shape[3] * pool.shape[4])[:n_rows]
                       for p in planes}
                   for n, pool in (("k", kp), ("v", vp))}
        if variant and variant.get("read_from") == "prompt":
            variant = {**variant, "read_from": prompt_len}

        def compare() -> dict:
            seq = prompt + out[:-1]
            kw = dict(logits_from=prompt_len - 1, planes=planes, zero_col=eos)
            want = ref.forward(seed, cfg, seq, **kw)
            if got:
                tokens, mine = out, got
            else:
                # a variant stands in at the program's own precision
                low = ref.forward(seed, cfg, seq, variant=variant,
                                  mode="bfloat16" if mode == "float32" else mode,
                                  **kw)
                tokens = [int(t) for t in jnp.argmax(low["logits"], axis=-1)]
                mine = {n: {p: np.asarray(low[n][p])[:n_rows] for p in planes}
                        for n in "kv"}
            theirs = {n: {p: np.asarray(want[n][p])[:n_rows] for p in planes}
                      for n in "kv"}

            def rel(a, b):
                return float(np.linalg.norm(a - b) / np.linalg.norm(b))

            def worst(some, span):
                return max(rel(mine[n][p][span], theirs[n][p][span])
                           for n in "kv" for p in some)

            pre, dec = slice(0, prompt_len), slice(prompt_len, n_rows)
            res = {}
            for name, span in (("prefill", pre), ("decode", dec)):
                res[f"kv_rel_err.{name}"] = worst(planes[:1], span)
                for u in range(U):
                    res[f"pass{u + 1}_kv_rel_err.{name}"] = worst(
                        [p for p in planes if p // L == u], span)
            logits = np.asarray(want["logits"])
            gap = (logits.max(-1) - logits[np.arange(len(tokens)), tokens]
                   ) / logits.std(-1)
            h, lam = np.asarray(want["h"]), want["lam"]
            return {
                **res,
                "token_logit_gap": float(gap.max()),
                "token_logit_gap_p50": float(np.percentile(gap, 50)),
                "h43": rel(h[-2], h[-1]),
                "lam_min": float(lam.min()), "lam_p50": float(np.median(lam)),
                "lam_max": float(lam.max()),
                "exit_depth_mean": float(np.mean(want["depth"])),
                "repeats": repeats, "tokens": len(out), "mode": mode,
                "rows_compared": n_rows, "planes_compared": planes}

        eng.params = None  # the reference's float32 layers beside the pools
        loop = asyncio.get_running_loop()
        try:
            return await loop.run_in_executor(None, compare)
        finally:
            eng.params = await loop.run_in_executor(
                None, lambda: weights.make_params(weights.seed_key(seed), cfg,
                                                  eos))

    async def reseed(self, seed: int, cfg) -> None:
        """New weights of the same shapes under the same programs (the
        control's tool: a dozen seeds in one set-up). Never used by a run."""
        from benchmarks.lib import weights_looped as weights

        self.engine.params = None  # two sets of 5.3 GB do not fit side by side
        self.engine.params = await asyncio.get_running_loop().run_in_executor(
            None, lambda: weights.make_params(weights.seed_key(seed), cfg,
                                              self.engine.eos_id))

"""The benchmark's replica for the windowed exact + pooled-pair attention
family: ``lib/replica_cohere2_moe.py``'s subclass of the program's
``LLMEngineServer`` (stamps, counters, profiler, the waves its wave limit lets
the engine form) with what names that family replaced — the program names and
so the warm-up that lists them, the seeded weights, and the comparison with
the plain reference, which for this family reads two kinds of pages: the ring
of the slot's own window's exact rows, and the pooled pairs, a row a chunk."""
from __future__ import annotations

import asyncio
import time

from benchmarks.lib import replica_cohere2_moe as base

PREFILL, DECODE = "eva_prefill_batch", "eva_decode_multi"


def make_params_fn(cfg, seed: int, zero_col: int | None):
    def params_fn():
        from ray_tpu.utils.device import configure_jax

        configure_jax()
        from benchmarks.lib import weights_eva as weights

        return weights.make_params(weights.seed_key(seed), cfg, zero_col)

    return params_fn


class EvaBenchServer(base.Cohere2MoeBenchServer):
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

    async def warm(self, pads: list[int], waves: list[int], vocab: int) -> dict:
        """``lib/replica_cohere2_moe.py``'s warm-up (each pad's waves cut to
        what the family's wave limit lets the engine form) under this
        family's program names."""
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

        for pad in pads:
            for wave in self._waves(pad, waves):
                await wave_of(wave, pad, 1)
        t_prefill = time.monotonic() - t0
        small = min(pads)
        await wave_of(1, small, 1 + 4)             # block 4
        await wave_of(1, small, 1 + 8 + 16 + 32)   # blocks 8, 16, 32
        half = -(-eng.B // 2)
        await wave_of(half, small, 1 + 64)         # block 64 (high occupancy)
        want = {(PREFILL, w, p) for p in pads for w in self._waves(p, waves)}
        want |= {(DECODE, eng.B, b) for b in (1, *eng.block_buckets)}
        have = {tuple(k) for k in self._program_keys()}
        return {"prefill_s": t_prefill, "total_s": time.monotonic() - t0,
                "missing": sorted(want - have), "programs": len(have),
                "unwanted": sorted(have - want)}

    async def reference_check(self, seed: int, cfg, prompt_len: int,
                              max_tokens: int, mode: str = "float32",
                              variant: dict | None = None,
                              which: int = 0) -> dict:
        """Prefill of a prompt and then decode through both kinds of pages,
        against the float32 reference's full forward pass over the same
        tokens, each query's rows built from the definition. The program
        gives out tokens and no logits, so what is compared is what it left
        in its cache — the exact K and V rows its ring still holds (the
        prompt's, written by prefill at the prompt's end, and the decoded
        positions' apart; less the ring's oldest pages, which the steps a
        fused block decodes past the last token write over) and the pooled
        PAIRS of every chunk complete at the request's end (those prefill
        made at the prompt's true length, and those decode made as a chunk
        filled, apart) — and each emitted token against the reference's
        head-0 logits. No routing here, so nothing flips: layer 0 reads tight
        and the last layer reads what seven layers of attention over rows and
        pairs left:

        * ``kv_rel_err.*``: layer 0's exact rows (embedding, norm,
          projections, rotation, the ring), norm-relative, worse of K, V;
        * ``pair_rel_err.*``: layer 0's pairs (the pooling and the write of
          the pair), norm-relative, worse of K^, V^;
        * ``deep_row_err_p50.*``: the last layer's exact rows, the MEDIAN
          over positions of each position's relative error;
        * ``deep_pair_err_p50.*``: the last layer's pairs, likewise;
        * ``token_logit_gap*``: the reference's best head-0 logit less its
          logit for the emitted token, in logit spreads (reported).

        ``mode`` other than float32 (the reference at that lower precision)
        or a ``variant`` (the reference with other mathematics, at the
        program's own precision) is the control, the reference standing in
        the program's place. ``which`` numbers the checked request (its own
        prompt). The weights are dropped while the reference computes (its
        float32 layer and 12k-position activations do not fit beside weights
        and pools) and made again from the seed."""
        import jax.numpy as jnp
        import numpy as np

        from benchmarks.lib import weights_eva as weights
        from benchmarks.lib.configs import load_module
        from benchmarks.lib.traffic import prompt_tokens

        ref = load_module("reference", "eva")
        await self._ensure_started()
        eng = self.engine
        while any(r is not None for r in eng.slot_req) or eng.waiting:
            await asyncio.sleep(0.05)
        PS, W, C, eos = eng.PS, cfg.window_size, cfg.chunk_size, eng.eos_id
        prompt = prompt_tokens(seed, 10**6 + which, prompt_len, cfg.vocab_size)
        n_rows = prompt_len + max_tokens - 1
        drawn = [list(f[:n]) for f, n in
                 zip(eng.free, eng._pages_of(prompt_len + max_tokens))]
        out = await eng.generate(prompt, max_tokens=max_tokens)
        # the ring's pages, oldest first: entry e holds the latest page p <=
        # last with p % entries == e. Left out: the oldest ones, which up to
        # 64 steps past the last token write over, and what prefill never
        # wrote (the windows before the one the prompt's end lies in)
        entries, last = len(drawn[0]), (n_rows - 1) // PS
        margin = min(-(-64 // PS) + 1, entries // 2)
        first = max(last - entries + 1 + margin, prompt_len // W * W // PS, 0)
        ring_pages = list(range(first, last + 1))
        where = np.concatenate([np.arange(p * PS, (p + 1) * PS)
                                for p in ring_pages])
        where = where[where < n_rows]
        n_chunks = n_rows // C
        layers = (0, cfg.n_layers - 1)
        got = {}
        sound = mode == "float32" and not variant
        if sound:  # read before another request runs, with no await between
            kw, vw, ks, vs = eng.cache
            ring = jnp.asarray([drawn[0][p % entries] for p in ring_pages])
            pages = jnp.asarray(drawn[1][:-(-n_chunks // PS)])
            for name, pool, at, n in (("k", kw, ring, len(where)),
                                      ("v", vw, ring, len(where)),
                                      ("kh", ks, pages, n_chunks),
                                      ("vh", vs, pages, n_chunks)):
                got[name] = {i: np.asarray(pool[i][at].astype(jnp.float32)
                                           ).reshape(-1, pool.shape[-2]
                                                     * pool.shape[-1])[:n]
                             for i in layers}
        repeats = (await eng.generate(prompt, max_tokens=max_tokens)) == out
        variant = dict(variant or {})
        if variant.pop("pad", None):  # where the engine's pad would end
            pad = -(-prompt_len // PS) * PS
            # only a prompt that does not fill its last page differs, and only
            # a request that stays in the window the padded chunk lies in is
            # computed (the reference swaps the pairs in afterwards)
            if prompt_len < pad and n_rows <= (prompt_len // W + 1) * W:
                variant |= {"pad": pad, "pad_from": prompt_len}
        if variant.pop("no_decode_pairs", None):
            variant["no_pairs_from"] = prompt_len

        def compare() -> dict:
            seq = prompt + out[:-1]
            kw_ = dict(logits_from=prompt_len - 1, layers=layers, zero_col=eos)
            want = ref.forward(seed, cfg, seq, **kw_)
            if sound:
                tokens, mine = out, got
            else:
                low = ref.forward(
                    seed, cfg, seq, variant=variant,
                    mode="bfloat16" if mode == "float32" else mode, **kw_)
                tokens = [int(t) for t in
                          jnp.argmax(low["logits"][:, 0], axis=-1)]
                mine = {n: {i: low[n][i][where if n in ("k", "v") else slice(n_chunks)]
                            for i in layers} for n in ("k", "v", "kh", "vh")}
            theirs = {n: {i: want[n][i][where if n in ("k", "v") else slice(n_chunks)]
                          for i in layers} for n in ("k", "v", "kh", "vh")}

            def whole(names, layer, span):
                return max(float(
                    np.linalg.norm(mine[n][layer][span] - theirs[n][layer][span])
                    / np.linalg.norm(theirs[n][layer][span])) for n in names)

            def by_row(names, layer, span):
                worst = 0.0
                for n in names:
                    g, w = mine[n][layer][span], theirs[n][layer][span]
                    e = np.linalg.norm(g - w, axis=-1) / np.linalg.norm(w, axis=-1)
                    worst = max(worst, float(np.median(e)))
                return worst

            spans = {("kv", "prefill"): where < prompt_len,
                     ("kv", "decode"): where >= prompt_len,
                     ("pair", "prefill"): np.arange(n_chunks) < prompt_len // C,
                     ("pair", "decode"): np.arange(n_chunks) >= prompt_len // C}
            res = {}
            for (what, phase), span in spans.items():
                if not span.any():
                    continue
                names = ("k", "v") if what == "kv" else ("kh", "vh")
                deep = "deep_row" if what == "kv" else "deep_pair"
                res[f"{what}_rel_err.{phase}"] = whole(names, layers[0], span)
                res[f"{deep}_err_p50.{phase}"] = by_row(names, layers[1], span)
            logits = np.asarray(want["logits"])[:, 0]
            gap = (logits.max(-1) - logits[np.arange(len(tokens)), tokens]
                   ) / logits.std(-1)
            return {**res, "token_logit_gap": float(gap.max()),
                    "token_logit_gap_p50": float(np.percentile(gap, 50)),
                    "repeats": repeats, "tokens": len(out), "mode": mode,
                    "rows_compared": [len(where), n_chunks]}

        eng.params = None  # the reference's float32 layer does not fit beside
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
        from benchmarks.lib import weights_eva as weights

        self.engine.params = None
        self.engine.params = await asyncio.get_running_loop().run_in_executor(
            None, lambda: weights.make_params(weights.seed_key(seed), cfg,
                                              self.engine.eos_id))

"""The benchmark's replica for the window-with-a-sink sparse-expert family: a
copy of ``lib/replica_cohere2_moe.py`` (README_sink_moe.md says what differs)
— the same subclass of the program's ``LLMEngineServer`` as ``lib/replica.py``
(stamps, counters, profiler), with what names the Llama programs replaced: the
program names and the waves a warm-up has to reach, the seeded weights, and
the comparison with the plain reference, which for this family reads two
kinds of pools of two geometries (the full layers' pages at 4 KV heads, the
window layers' ring at 8; a key in the first 192 of its row's 256 lanes, a
value in its 128)."""
from __future__ import annotations

import asyncio
import time

from benchmarks.lib.replica import BenchEngineServer

PREFILL, DECODE = "sink_moe_prefill_batch", "sink_moe_decode_multi"


def make_params_fn(cfg, seed: int):
    def params_fn():
        from ray_tpu.utils.device import configure_jax

        configure_jax()
        from benchmarks.lib import weights_sink_moe as weights

        return weights.make_params(weights.seed_key(seed), cfg)

    return params_fn


class SinkMoeBenchServer(BenchEngineServer):
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
        """``lib/replica.py``'s warm-up through the engine's own ``submit``,
        with each pad's waves cut to what the family's wave limit lets the
        engine form; ``check_pads`` (the reference checks' own, which the
        traffic never reaches) one prompt each; what it must have reached is
        named here."""
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
                              variant: dict | None = None) -> dict:
        """Prefill of a prompt and then decode through both kinds of pages,
        against the float32 reference's full forward pass over the same
        tokens, computed a block of queries at a time. The program gives out
        tokens and no logits, so what is compared is what it left in its
        pools — every layer's keys and values as its attention reads them,
        prompt positions (prefill: blocked attention from the sink, the ring
        written at the prompt's end) and decoded positions (decode: both
        walks through both tables, the window's merged with its sink) apart
        — and each emitted token against the reference's logits. Of a window
        layer the rows compared are the positions its ring still holds, less
        a margin for the steps a fused block may decode past the last token
        (they write over the ring's oldest pages).

        **Routing flips**, as ``lib/replica_mla_moe.py``: a top-8 choice
        flips between bf16 and float32 where the 8th and 9th sums nearly
        tie, and a flipped position carries another expert's output. The
        reference is never handed the program's choices; each depth is read
        where flips cannot blur it:

        * ``kv_rel_err.*``: layer 0's rows (a full layer's pages at 4 KV
          heads; before any attention or routing: embedding, RMSNorm,
          projections, the rotation of 64 lanes at base 5e6, the value's
          scale, paging), norm-relative over all positions, worse of K, V;
        * ``ring_rel_err.*``: layer 1's rows (a window layer's ring at 8 KV
          heads; behind layer 0's full attention and DENSE half, so still
          before any routing: base 1e4, the ring), norm-relative;
        * ``row_err_p50.*``: layer 2's rows (behind layer 1: the window walk
          with its sink, the router, the 16 held experts), the MEDIAN over
          positions of each position's relative error — the guard of the
          sink, of the window and of the window layers' grouping;
        * ``full_row_err_p50.*``: layer 5's rows (the second full layer,
          four expert layers on), the median over ALL positions;
        * ``deep_row_err_p50.*``: layer 6's rows (the last window layer),
          the median over the positions the ring holds;
        * ``token_logit_gap*``: the reference's best logit less its logit
          for the emitted token, in logit spreads;
        * ``sink_share_p50``: the sink's share of a window query's mass in
          layer 1, median over positions and heads (the reference's own;
          reported, never judged);
        * ``route_flip_share`` (control modes only: the stand-in's choices
          against the float32 reference's): reported, never judged.

        ``mode`` other than float32 (the reference at that lower precision)
        or a ``variant`` (the reference with other mathematics) is the
        control, the float32 reference as published standing in the
        program's place. The weights are dropped while the reference
        computes (its float32 layer does not fit beside them) and made again
        from the seed."""
        import jax.numpy as jnp
        import numpy as np

        from benchmarks.lib import weights_sink_moe as weights
        from benchmarks.lib.configs import load_module
        from benchmarks.lib.traffic import prompt_tokens

        ref = load_module("reference", "sink_moe")
        await self._ensure_started()
        eng = self.engine
        while any(r is not None for r in eng.slot_req) or eng.waiting:
            await asyncio.sleep(0.05)
        prompt = prompt_tokens(seed, 10**6, prompt_len, cfg.vocab_size)
        n_rows = prompt_len + max_tokens - 1
        PS = eng.PS
        drawn = [list(f[:n]) for f, n in
                 zip(eng.free, eng._pages_of(prompt_len + max_tokens))]
        out = await eng.generate(prompt, max_tokens=max_tokens)
        repeats = (await eng.generate(prompt, max_tokens=max_tokens)) == out
        # which positions the window kind's ring still holds, oldest first:
        # entry e holds the latest page p <= last with p % entries == e
        entries, last = len(drawn[1]), (n_rows - 1) // PS
        ring_pages = sorted(last - (last - e) % entries for e in range(entries)
                            if last - (last - e) % entries >= 0)
        # a block covers the tokens left to the next bucket: under 32 past
        margin = min(32, cfg.sliding_window // 4)
        lo = max(ring_pages[0] * PS, n_rows - cfg.sliding_window + margin, 0)
        layers_f, layers_w = cfg.layers_of(False), cfg.layers_of(True)
        hd, hv = cfg.head_dim, cfg.v_head_dim
        got = {}
        if mode == "float32" and not variant:
            kf, vf, kw, vw = eng.cache  # read before another request runs
            full = jnp.asarray(drawn[0])
            ring = jnp.asarray([drawn[1][p % entries] for p in ring_pages])
            base = ring_pages[0] * PS
            for name, pf, pw, width in (("k", kf, kw, hd), ("v", vf, vw, hv)):
                # a key lies in the first hd of its row's lanes
                rows_f = np.asarray(pf[:, full][..., :width].astype(jnp.float32)
                                    ).reshape(pf.shape[0], -1,
                                              pf.shape[3] * width)[:, :n_rows]
                rows_w = np.asarray(pw[:, ring][..., :width].astype(jnp.float32)
                                    ).reshape(pw.shape[0], -1, pw.shape[3] * width
                                              )[:, lo - base:n_rows - base]
                got[name] = (rows_f, rows_w)

        def kinds(fwd, name):
            """A forward pass's rows of one name as the pools hold them:
            (the full layers' [n, n_rows, .], the window layers' from lo)."""
            return (np.stack([np.asarray(fwd[name][i])[:n_rows]
                              for i in layers_f]),
                    np.stack([np.asarray(fwd[name][i])[lo:n_rows]
                              for i in layers_w]))

        def compare() -> dict:
            seq = prompt + out[:-1]
            want = ref.forward(seed, cfg, seq, logits_from=prompt_len - 1,
                               probe=(layers_w[0],))
            flips = {}
            if got:
                tokens, mine = out, got
            else:
                # a variant stands in at the program's own precision
                low = ref.forward(seed, cfg, seq, logits_from=prompt_len - 1,
                                  mode="bfloat16" if mode == "float32" else mode,
                                  variant=variant)
                both = sorted(set(low["chosen"]) & set(want["chosen"]))
                flips = {"route_flip_share": float(np.mean([jnp.mean(jnp.any(
                    jnp.sort(low["chosen"][i], -1)
                    != jnp.sort(want["chosen"][i], -1), axis=-1))
                    for i in both]))}
                tokens = [int(t) for t in jnp.argmax(low["logits"], axis=-1)]
                mine = {n: kinds(low, n) for n in "kv"}
            theirs = {n: kinds(want, n) for n in "kv"}

            def rel(a, b):
                return float(np.linalg.norm(a - b) / np.linalg.norm(b))

            def whole(kind, layer, span):
                return max(rel(mine[n][kind][layer][span],
                               theirs[n][kind][layer][span]) for n in "kv")

            def by_position(kind, layer, span, q):
                worst = 0.0
                for n in "kv":
                    g, w = mine[n][kind][layer][span], theirs[n][kind][layer][span]
                    e = np.linalg.norm(g - w, axis=-1) / np.linalg.norm(w, axis=-1)
                    worst = max(worst, float(np.percentile(e, q)))
                return worst

            # spans of the window kind's rows count from position lo
            w_pre, w_dec = slice(0, prompt_len - lo), slice(prompt_len - lo, None)
            f_pre, f_dec = slice(0, prompt_len), slice(prompt_len, n_rows)
            logits = np.asarray(want["logits"])
            gap = (logits.max(-1) - logits[np.arange(len(tokens)), tokens]
                   ) / logits.std(-1)
            share = np.asarray(want["sink_share"][layers_w[0]])[lo:n_rows]
            return {
                "kv_rel_err.prefill": whole(0, 0, f_pre),
                "kv_rel_err.decode": whole(0, 0, f_dec),
                "ring_rel_err.prefill": whole(1, 0, w_pre),
                "ring_rel_err.decode": whole(1, 0, w_dec),
                "row_err_p50.prefill": by_position(1, 1, w_pre, 50),
                "row_err_p50.decode": by_position(1, 1, w_dec, 50),
                "full_row_err_p50.prefill": by_position(0, -1, f_pre, 50),
                "full_row_err_p50.decode": by_position(0, -1, f_dec, 50),
                "deep_row_err_p50.prefill": by_position(1, -1, w_pre, 50),
                "deep_row_err_p50.decode": by_position(1, -1, w_dec, 50),
                "kv_rel_err_last.prefill": whole(0, -1, f_pre),
                "kv_rel_err_last.decode": whole(0, -1, f_dec),
                "token_logit_gap": float(gap.max()),
                "token_logit_gap_p50": float(np.percentile(gap, 50)),
                "sink_share_p50": float(np.median(share)),
                "sink_share_p10": float(np.percentile(share, 10)),
                "sink_share_p90": float(np.percentile(share, 90)),
                **flips, "repeats": repeats, "tokens": len(out), "mode": mode,
                "rows_compared": [n_rows, n_rows - lo],
            }

        eng.params = None  # the reference's float32 layer does not fit beside
        loop = asyncio.get_running_loop()
        try:
            return await loop.run_in_executor(None, compare)
        finally:
            eng.params = await loop.run_in_executor(
                None, lambda: weights.make_params(weights.seed_key(seed), cfg))

    async def reseed(self, seed: int, cfg) -> None:
        """New weights of the same shapes under the same programs (the
        control's tool: a dozen seeds in one set-up). Never used by a run."""
        from benchmarks.lib import weights_sink_moe as weights

        self.engine.params = None  # two sets of 6.9 GB do not fit side by side
        self.engine.params = await asyncio.get_running_loop().run_in_executor(
            None, lambda: weights.make_params(weights.seed_key(seed), cfg))

"""The benchmark's replica for the compressed-latent convolved attention +
top-1 expert family: ``lib/replica_cohere2_moe.py``'s subclass of the
program's ``LLMEngineServer`` (stamps, counters, profiler, the waves its wave
limit lets the engine form) with what names that family replaced — the
program names and so the warm-up that lists them, the seeded weights, and
the comparison with the plain reference, which for this family reads two
kinds of cache OF EVERY LAYER: its K and V pages, and its row, one a slot
whatever the length. A copy of ``lib/replica_kda_moe.py`` (README_cca_moe.md
says what differs)."""
from __future__ import annotations

import asyncio
import time

from benchmarks.lib import replica_cohere2_moe as base

PREFILL, DECODE = "cca_moe_prefill_batch", "cca_moe_decode_multi"


def make_params_fn(cfg, seed: int, eos_id: int | None = None):
    def params_fn():
        from ray_tpu.utils.device import configure_jax

        configure_jax()
        from benchmarks.lib import weights_cca_moe as weights

        return weights.make_params(weights.seed_key(seed), cfg, eos_id)

    return params_fn


class CcaMoeBenchServer(base.Cohere2MoeBenchServer):
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
        """Prefill of a prompt and then decode through K/V pages and rows,
        against the float32 reference's full forward pass over the same
        tokens. The program gives out tokens and no logits, so what is
        compared is what it left in its cache — every layer's keys (as they
        are attended) and values, prompt positions and decoded positions
        apart, and every layer's ROW, which holds ONE position's worth: read
        once after a request of one token (the row the prefill wrote **at the
        prompt's true length**, advanced by the step or two the loop runs on
        before it sees the request done) and once after the whole request
        (advanced by every decode step the engine ran: its loops dispatch a
        block before the last one's tokens are back, so a lone request's
        slot decodes on past its last token, feeding what it emitted;
        ``_emit_block`` is tapped for those tokens, so the reference is fed
        every token the row was) — and each emitted token against the
        reference's logits.

        **Routing flips.** With ONE expert a token a choice that flips
        between bf16 and float32 (the two largest ``p + bias`` nearly tied)
        replaces the WHOLE sublayer's output at that position, not an eighth
        of it. The reference is never handed the program's choices; each
        depth is read where flips cannot blur it:

        * ``kv_rel_err.*`` and ``row_rel_err.*``: layer 0's keys and values
          (worse of the two) and its row (before any routing: embedding,
          norm, projection, mean, both convolutions, norm, temperature,
          rotation, shift), norm-relative over all positions: the precision
          guard of the mixing itself;
        * ``kv_row_err_p50.*``: layer 1's rows of K and V (behind ONE expert
          sublayer), the MEDIAN over positions of each position's relative
          error, worse of K and V;
        * ``deep_kv_row_err_p50.*``: the last layer's rows, likewise;
        * ``deep_row_err.*``: the last layer's row (one position: reported);
        * ``token_logit_gap*``: the reference's best logit less its logit
          for the emitted token, in logit spreads (reported).

        ``mode`` other than float32 (the reference at that lower precision)
        or a ``variant`` (the reference with other mathematics) is the
        control, the float32 reference as published standing in the
        program's place. ``which`` numbers the checked request (its own
        prompt)."""
        import jax.numpy as jnp
        import numpy as np

        from benchmarks.lib.configs import load_module
        from benchmarks.lib.traffic import prompt_tokens

        ref = load_module("reference", "cca_moe")
        await self._ensure_started()
        eng = self.engine
        eos = eng.eos_id

        async def idle():
            while any(r is not None for r in eng.slot_req) or eng.waiting:
                await asyncio.sleep(0.05)

        async def served(n_tokens: int):
            """One request alone on the engine: (tokens given out, every
            token its decode steps emitted — those of the steps run past its
            last token too — and what it left in its pages and its row)."""
            await idle()
            need = eng._pages_of(prompt_len + n_tokens)
            pages, row = list(eng.free[0][:need[0]]), eng.free[1][0]
            emitted, emit = [], eng._emit_block

            def tap(entry):  # a block's tokens of the one live slot
                _, toks, snapshot = entry
                live = [i for i, r in enumerate(snapshot) if r is not None]
                if live:
                    emitted.extend(int(t) for t in np.asarray(toks)[:, live[0]])
                emit(entry)

            eng._emit_block = tap
            try:
                out = await eng.generate(prompt, max_tokens=n_tokens)
                seen = -1
                while seen != len(emitted):  # the blocks still in flight
                    seen = len(emitted)
                    await idle()
                    await asyncio.sleep(0.2)
            finally:
                del eng._emit_block
            kp, vp, rows = eng.cache  # before another request runs
            at = jnp.asarray(pages)

            def f32(a):
                return np.asarray(a.astype(jnp.float32))

            def flat(pool, j):
                return f32(pool[j][at]).reshape(-1, pool.shape[-2] * pool.shape[-1])

            # a layer at a time: a gather out of a whole pool made XLA
            # reserve a copy of the pool (PERF.md section 6, PR 33)
            layers = range(kp.shape[0])
            left = {"row": np.stack([f32(rows[j, row]) for j in layers]),
                    "k": np.stack([flat(kp, j) for j in layers]),
                    "v": np.stack([flat(vp, j) for j in layers])}
            if out[1:] != emitted[:n_tokens - 1]:
                raise RuntimeError("the tapped blocks are not the request's")
            return out, emitted, left, need[0] * eng.PS

        prompt = prompt_tokens(seed, 10**6 + which, prompt_len, cfg.vocab_size)
        sound = mode == "float32" and not variant
        # the row after the prefill (and the step or two the loop runs on
        # before it sees the request done), and after the whole request
        first, ran_1, after_prefill, room_1 = await served(1)
        out, ran, after_all, room = await served(max_tokens)
        # as ``lib/replica_ssm_moe.py``: what the row after the prefill
        # consumed is what has to repeat; the last token the first serving
        # emitted was fed to nothing
        if first != out[:1] or ran_1[:-1] != ran[:len(ran_1) - 1]:
            raise RuntimeError("greedy repeat differs")
        if prompt_len + len(ran_1) > room_1:
            raise RuntimeError(
                f"{len(ran_1)} steps past a prompt of {prompt_len}: past its pages")
        repeats = (await eng.generate(prompt, max_tokens=max_tokens)) == out
        n_rows = prompt_len + max_tokens - 1
        at_1, at_all = prompt_len + len(ran_1), prompt_len + len(ran)
        # steps past a slot's last page attend the junk page: a deep layer's
        # row is then nobody's
        deep_decode = at_all <= room
        last = cfg.n_layers - 1
        if variant and "pad" in variant:  # where the engine's pad would end
            pad = -(-prompt_len // eng.PS) * eng.PS
            variant = {k: v for k, v in variant.items() if k != "pad"}
            if pad > prompt_len:
                variant |= {"pad": pad, "pad_from": prompt_len}

        def compare() -> dict:
            seq = prompt + out[:1] + ran[:len(ran) - 1]  # every token fed
            kw = dict(logits_from=prompt_len - 1, state_at=(at_1, at_all),
                      zero_row=eos)
            want = ref.forward(seed, cfg, seq, **kw)
            if sound:
                tokens = out
                mine = {"k": after_all["k"], "v": after_all["v"],
                        "row": np.stack([after_prefill["row"], after_all["row"]], 1)}
            else:
                low = ref.forward(seed, cfg, seq, mode=mode, variant=variant, **kw)
                tokens = [int(t) for t in jnp.argmax(low["logits"], axis=-1)]
                mine = {n: np.asarray(low[n]) for n in ("k", "v", "row")}
            theirs = {n: np.asarray(want[n]) for n in ("k", "v", "row")}
            for side in (mine, theirs):
                side["k"], side["v"] = side["k"][:, :n_rows], side["v"][:, :n_rows]

            def rel(g, w):
                return float(np.linalg.norm(g - w) / np.linalg.norm(w))

            def kv_whole(layer, span):
                return max(rel(mine[n][layer][span], theirs[n][layer][span])
                           for n in ("k", "v"))

            def kv_rows(layer, span):
                worst = 0.0
                for n in ("k", "v"):
                    g, w = mine[n][layer][span], theirs[n][layer][span]
                    e = np.linalg.norm(g - w, axis=-1) / np.linalg.norm(w, axis=-1)
                    worst = max(worst, float(np.median(e)))
                return worst

            def row(layer, when):
                return rel(mine["row"][layer, when], theirs["row"][layer, when])

            pre, dec = slice(0, prompt_len), slice(prompt_len, n_rows)
            logits = np.asarray(want["logits"])[:len(tokens)]
            tokens = tokens[:len(logits)]
            gap = (logits.max(-1) - logits[np.arange(len(tokens)), tokens]
                   ) / logits.std(-1)
            res = {} if not deep_decode else {
                "deep_row_err.decode": row(last, 1)}
            return {
                **res,
                "kv_rel_err.prefill": kv_whole(0, pre),
                "kv_rel_err.decode": kv_whole(0, dec),
                "row_rel_err.prefill": row(0, 0),
                "row_rel_err.decode": row(0, 1),
                "kv_row_err_p50.prefill": kv_rows(1, pre),
                "kv_row_err_p50.decode": kv_rows(1, dec),
                "deep_kv_row_err_p50.prefill": kv_rows(last, pre),
                "deep_kv_row_err_p50.decode": kv_rows(last, dec),
                "deep_row_err.prefill": row(last, 0),
                "token_logit_gap": float(gap.max()),
                "token_logit_gap_p50": float(np.percentile(gap, 50)),
                "repeats": repeats, "tokens": len(out), "mode": mode,
                "rows_compared": n_rows, "state_positions": [at_1, at_all]}

        return await asyncio.get_running_loop().run_in_executor(None, compare)

    async def reseed(self, seed: int, cfg) -> None:
        """New weights of the same shapes under the same programs (the
        control's tool: a dozen seeds in one set-up). Never used by a run."""
        from benchmarks.lib import weights_cca_moe as weights

        self.engine.params = None
        self.engine.params = await asyncio.get_running_loop().run_in_executor(
            None, lambda: weights.make_params(weights.seed_key(seed), cfg,
                                              self.engine.eos_id))

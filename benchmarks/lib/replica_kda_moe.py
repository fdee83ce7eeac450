"""The benchmark's replica for the delta-rule + latent-attention + group-routed
expert family: ``lib/replica_cohere2_moe.py``'s subclass of the program's
``LLMEngineServer`` (stamps, counters, profiler, the waves its wave limit lets
the engine form) with what names that family replaced — the program names and
so the warm-up that lists them, the seeded weights, and the comparison with
the plain reference, which for this family reads two kinds of cache: the MLA
layers' latent pages, and the KDA layers' state and conv rows, one row a slot
whatever the length. A copy of ``lib/replica_ssm_moe.py`` (README_kda_moe.md
says what differs)."""
from __future__ import annotations

import asyncio
import time

from benchmarks.lib import replica_cohere2_moe as base

PREFILL, DECODE = "kda_moe_prefill_batch", "kda_moe_decode_multi"


def make_params_fn(cfg, seed: int):
    def params_fn():
        from ray_tpu.utils.device import configure_jax

        configure_jax()
        from benchmarks.lib import weights_kda_moe as weights

        return weights.make_params(weights.seed_key(seed), cfg)

    return params_fn


class KdaMoeBenchServer(base.Cohere2MoeBenchServer):
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
        """Prefill of a prompt and then decode through latent pages and state
        rows, against the float32 reference's full forward pass over the same
        tokens, whose delta rule runs one position at a time. The program
        gives out tokens and no logits, so what is compared is what it left
        in its cache — the MLA layers' latent rows as they read them, prompt
        positions (prefill: the chunked scan feeds them) and decoded
        positions apart, and every KDA layer's state and conv row, which hold
        ONE position's worth: read once after a request of one token (the
        state the prefill wrote **at the prompt's true length**, advanced by
        the step or two the loop runs on before it sees the request done) and
        once after the whole request (advanced by every decode step the
        engine ran: its loops dispatch a block before the last one's tokens
        are back, so a lone request's slot decodes on past its last token,
        feeding what it emitted; ``_emit_block`` is tapped for those tokens,
        so the reference is fed every token the state was) — and each emitted
        token against the reference's logits.

        **Routing flips**, as the other expert families: a top-8 choice
        flips between bf16 and float32 where the 8th and 9th scores (or two
        groups' sums) nearly tie, and a flipped position carries another
        expert's output. The reference is never handed the program's
        choices; each depth is read where flips cannot blur it:

        * ``state_rel_err.*``: layer 0's state and conv row (a KDA layer
          before any routing: embedding, norm, in-projection, convolution,
          gate, the delta rule in its chunked form and then its one-step
          form), norm-relative, worse of the two: the precision guard of the
          recurrence itself;
        * ``latent_row_err_p50.*``: the first MLA layer's rows (behind the
          dense layers and the first expert layers), the MEDIAN over
          positions of each position's relative error;
        * ``deep_state_err_p50.*``: the last KDA layer's state, the median
          over its heads of each head's relative error;
        * ``deep_latent_row_err_p50.*``: the last MLA layer's rows, likewise;
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

        ref = load_module("reference", "kda_moe")
        await self._ensure_started()
        eng = self.engine

        async def idle():
            while any(r is not None for r in eng.slot_req) or eng.waiting:
                await asyncio.sleep(0.05)

        async def served(n_tokens: int):
            """One request alone on the engine: (tokens given out, every
            token its decode steps emitted — those of the steps run past its
            last token too — and what it left in its state row and pages)."""
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
            pool, states, convs = eng.cache  # before another request runs
            at = jnp.asarray(pages)

            def f32(a):
                return np.asarray(a.astype(jnp.float32))

            # a layer at a time: a gather out of a whole pool made XLA
            # reserve a copy of the pool (PERF.md section 6, PR 33)
            left = {
                "state": np.stack([f32(states[j, row])
                                   for j in range(states.shape[0])]),
                "conv": np.stack([f32(convs[j, row]).reshape(
                    cfg.conv_kernel - 1, -1) for j in range(convs.shape[0])]),
                "rows": np.stack([f32(pool[j][at]).reshape(-1, pool.shape[-1])
                                  for j in range(pool.shape[0])])}
            if out[1:] != emitted[:n_tokens - 1]:
                raise RuntimeError("the tapped blocks are not the request's")
            return out, emitted, left, need[0] * eng.PS

        prompt = prompt_tokens(seed, 10**6 + which, prompt_len, cfg.vocab_size)
        sound = mode == "float32" and not variant
        # the state after the prefill (and the step or two the loop runs on
        # before it sees the request done), and after the whole request
        first, ran_1, after_prefill, room_1 = await served(1)
        out, ran, after_all, room = await served(max_tokens)
        # as ``lib/replica_ssm_moe.py``: what the state after the prefill
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
        # steps past a slot's last page read the junk page in the MLA
        # layers: the state of a layer behind one is then nobody's
        deep_decode = at_all <= room
        n_kda, n_mla = after_all["state"].shape[0], after_all["rows"].shape[0]
        if variant and "pad" in variant:  # where the engine's pad would end
            pad = -(-prompt_len // eng.PS) * eng.PS
            variant = {k: v for k, v in variant.items() if k != "pad"}
            if pad > prompt_len:
                variant |= {"pad": pad, "pad_from": prompt_len}

        def compare() -> dict:
            seq = prompt + out[:1] + ran[:len(ran) - 1]  # every token fed
            kw = dict(logits_from=prompt_len - 1, state_at=(at_1, at_all))
            want = ref.forward(seed, cfg, seq, **kw)
            if sound:
                tokens = out
                mine = {"rows": after_all["rows"][:, :n_rows],
                        "state": np.stack([after_prefill["state"], after_all["state"]], 1),
                        "conv": np.stack([after_prefill["conv"], after_all["conv"]], 1)}
            else:
                low = ref.forward(seed, cfg, seq, mode=mode, variant=variant, **kw)
                tokens = [int(t) for t in jnp.argmax(low["logits"], axis=-1)]
                mine = {n: np.asarray(low[n]) for n in ("rows", "state", "conv")}
                mine["rows"] = mine["rows"][:, :n_rows]
            theirs = {n: np.asarray(want[n]) for n in ("rows", "state", "conv")}
            theirs["rows"] = theirs["rows"][:, :n_rows]

            def rel(g, w):
                return float(np.linalg.norm(g - w) / np.linalg.norm(w))

            def state_whole(layer, when):
                return max(rel(mine[n][layer, when], theirs[n][layer, when])
                           for n in ("state", "conv"))

            def state_heads(layer, when):
                g, w = mine["state"][layer, when], theirs["state"][layer, when]
                e = (np.linalg.norm((g - w).reshape(len(g), -1), axis=-1)
                     / np.linalg.norm(w.reshape(len(w), -1), axis=-1))
                return float(np.median(e))

            def latent_rows(layer, span):
                g, w = mine["rows"][layer][span], theirs["rows"][layer][span]
                e = np.linalg.norm(g - w, axis=-1) / np.linalg.norm(w, axis=-1)
                return float(np.median(e))

            pre, dec = slice(0, prompt_len), slice(prompt_len, n_rows)
            logits = np.asarray(want["logits"])[:len(tokens)]
            tokens = tokens[:len(logits)]
            gap = (logits.max(-1) - logits[np.arange(len(tokens)), tokens]
                   ) / logits.std(-1)
            res = {} if not deep_decode else {
                "deep_state_err_p50.decode": state_heads(n_kda - 1, 1)}
            return {
                **res,
                "state_rel_err.prefill": state_whole(0, 0),
                "state_rel_err.decode": state_whole(0, 1),
                "latent_row_err_p50.prefill": latent_rows(0, pre),
                "latent_row_err_p50.decode": latent_rows(0, dec),
                "deep_state_err_p50.prefill": state_heads(n_kda - 1, 0),
                "deep_latent_row_err_p50.prefill": latent_rows(n_mla - 1, pre),
                "deep_latent_row_err_p50.decode": latent_rows(n_mla - 1, dec),
                "token_logit_gap": float(gap.max()),
                "token_logit_gap_p50": float(np.percentile(gap, 50)),
                "repeats": repeats, "tokens": len(out), "mode": mode,
                "rows_compared": n_rows, "state_positions": [at_1, at_all]}

        return await asyncio.get_running_loop().run_in_executor(None, compare)

    async def reseed(self, seed: int, cfg) -> None:
        """New weights of the same shapes under the same programs (the
        control's tool: a dozen seeds in one set-up). Never used by a run."""
        from benchmarks.lib import weights_kda_moe as weights

        self.engine.params = None
        self.engine.params = await asyncio.get_running_loop().run_in_executor(
            None, lambda: weights.make_params(weights.seed_key(seed), cfg))

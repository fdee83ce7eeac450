"""The benchmark's replica for the learned-sparse-attention expert family:
``lib/replica_cohere2_moe.py``'s subclass of the program's ``LLMEngineServer``
(stamps, counters, profiler, the waves its wave limit lets the engine form)
with what names that family replaced — the program names and so the warm-up
that lists them, the seeded weights, and the comparison with the plain
reference, which for this family reads three pools on one kind of page (K, V
and the indexer's keys, unpacked)."""
from __future__ import annotations

import asyncio
import time

from benchmarks.lib import replica_cohere2_moe as base

PREFILL, DECODE = "sparse_moe_prefill_batch", "sparse_moe_decode_multi"


def make_params_fn(cfg, seed: int):
    def params_fn():
        from ray_tpu.utils.device import configure_jax

        configure_jax()
        from benchmarks.lib import weights_sparse_moe as weights

        return weights.make_params(weights.seed_key(seed), cfg)

    return params_fn


class SparseMoeBenchServer(base.Cohere2MoeBenchServer):
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
        """Prefill of a prompt and then decode through the three pools,
        against the float32 reference's full forward pass over the same
        tokens. The program gives out tokens and no logits, so what is
        compared is what it left in its pools — every layer's keys, values
        and indexer keys as its attention reads them, prompt positions
        (prefill: blocked scoring, selection, the picked kernel) and decoded
        positions (decode: the paged scoring kernel, selection, the masked
        walk) apart — and each emitted token against the reference's logits.

        **Selection flips** are this family's routing flips, and more of
        them: where a query's ``topk``-th and next score nearly tie, bf16
        inputs and float32 pick different keys — at nearly every query past
        position ``topk`` — and a top-8 expert choice flips as in the other
        expert families. The reference is never handed the program's picks;
        each depth is read where flips cannot blur it:

        * ``kv_rel_err.*``: layer 0's rows of all three pools (before any
          selection or routing: embedding, norm, projections, per-head
          norms, rotation, paging and packing), norm-relative over the
          positions compared, worst of K, V, kI;
        * ``near_row_err_p50.prefill``: the prompt's first ``topk``
          positions — their queries select everything and attend nothing
          later, so no selection flip reaches them at any depth — the MEDIAN
          over positions of each position's relative error, worst of the
          three pools, worst of the layers past 0: the precision guard for
          the whole block;
        * ``far_row_err_p50.*``: the last layer's rows past ``topk`` (prompt)
          and the decoded positions: medians;
        * ``far_row_err_p10.prefill``: the 10th percentile there (the
          positions flips touched least);
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
        from ray_tpu.ops.paged_indexer import unpack_keys

        ref = load_module("reference", "sparse_moe")
        await self._ensure_started()
        eng = self.engine
        while any(r is not None for r in eng.slot_req) or eng.waiting:
            await asyncio.sleep(0.05)
        prompt = prompt_tokens(seed, 10**6 + which, prompt_len, cfg.vocab_size)
        n_rows = prompt_len + max_tokens - 1
        drawn = list(eng.free[0][:eng._pages_of(prompt_len + max_tokens)[0]])
        out = await eng.generate(prompt, max_tokens=max_tokens)
        got = {}
        if mode == "float32" and not variant:
            kpool, vpool, ipool = eng.cache  # read before another request runs
            pages = jnp.asarray(drawn)

            def rows(pool, unpack=None):
                # a layer at a time: gathering pages out of the whole
                # 4 GB pool made XLA reserve a copy of it (my chip run, PR 33)
                out = []
                for i in range(pool.shape[0]):
                    got_i = pool[i][pages]
                    if unpack:
                        got_i = unpack(got_i)
                    out.append(np.asarray(got_i.astype(jnp.float32)).reshape(
                        -1, got_i.shape[-1] if unpack else
                        got_i.shape[-2] * got_i.shape[-1])[:n_rows])
                return np.stack(out)

            got = {"k": rows(kpool), "v": rows(vpool),
                   "ki": rows(ipool, lambda p: unpack_keys(
                       p, cfg.indexer_head_dim))}
        repeats = (await eng.generate(prompt, max_tokens=max_tokens)) == out
        names = ("k", "v", "ki")

        def compare() -> dict:
            seq = prompt + out[:-1]
            want = ref.forward(seed, cfg, seq, logits_from=prompt_len - 1)
            if got:
                tokens, mine = out, got
            else:
                low = ref.forward(seed, cfg, seq, logits_from=prompt_len - 1,
                                  mode=mode, variant=variant)
                tokens = [int(t) for t in jnp.argmax(low["logits"], axis=-1)]
                mine = {n: np.asarray(low[n]) for n in names}
            theirs = {n: np.asarray(want[n]) for n in names}

            def whole(layer, span):
                return max(float(np.linalg.norm(mine[n][layer][span]
                                                - theirs[n][layer][span])
                                 / np.linalg.norm(theirs[n][layer][span]))
                           for n in names)

            def by_position(layer, span, q):
                worst = 0.0
                for n in names:
                    g, w = mine[n][layer][span], theirs[n][layer][span]
                    e = np.linalg.norm(g - w, axis=-1) / np.linalg.norm(w, axis=-1)
                    worst = max(worst, float(np.percentile(e, q)))
                return worst

            near = slice(0, min(cfg.topk, prompt_len))
            far = slice(min(cfg.topk, prompt_len - 1), prompt_len)
            dec = slice(prompt_len, n_rows)
            logits = np.asarray(want["logits"])
            gap = (logits.max(-1) - logits[np.arange(len(tokens)), tokens]
                   ) / logits.std(-1)
            res = {
                "kv_rel_err.prefill": whole(0, slice(0, prompt_len)),
                "kv_rel_err.decode": whole(0, dec),
                "near_row_err_p50.prefill": max(
                    by_position(i, near, 50) for i in range(1, cfg.n_layers)),
                "row_err_p50.decode": by_position(-1, dec, 50),
                "token_logit_gap": float(gap.max()),
                "token_logit_gap_p50": float(np.percentile(gap, 50)),
            }
            if prompt_len > cfg.topk:
                res["far_row_err_p50.prefill"] = by_position(-1, far, 50)
                res["far_row_err_p10.prefill"] = by_position(-1, far, 10)
            return {**res, "repeats": repeats, "tokens": len(out),
                    "mode": mode, "rows_compared": n_rows}

        return await asyncio.get_running_loop().run_in_executor(None, compare)

    async def reseed(self, seed: int, cfg) -> None:
        """New weights of the same shapes under the same programs (the
        control's tool: a dozen seeds in one set-up). Never used by a run."""
        from benchmarks.lib import weights_sparse_moe as weights

        self.engine.params = None
        self.engine.params = await asyncio.get_running_loop().run_in_executor(
            None, lambda: weights.make_params(weights.seed_key(seed), cfg))

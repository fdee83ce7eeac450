"""The benchmark's replica for the MLA + sparse-expert family: the same
subclass of the program's ``LLMEngineServer`` as ``lib/replica.py`` (stamps,
counters, warm-up, profiler), with what names the Llama programs replaced —
the program names a warm-up has to reach, the seeded weights, and the
comparison with the plain reference, which for this family reads the one
latent pool (``c`` and ``k_rope`` rows of the last layer)."""
from __future__ import annotations

import asyncio

from benchmarks.lib.replica import BenchEngineServer

PREFILL, DECODE = "mla_moe_prefill_batch", "mla_moe_decode_multi"


def make_params_fn(cfg, seed: int, zero_col: int | None):
    def params_fn():
        from ray_tpu.utils.device import configure_jax

        configure_jax()
        from benchmarks.lib import weights_mla_moe as weights

        return weights.make_params(weights.seed_key(seed), cfg, zero_col)

    return params_fn


class MlaMoeBenchServer(BenchEngineServer):
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
        """``lib/replica.py``'s warm-up through the engine's own ``submit``;
        what it must have reached is named here."""
        out = await super().warm(pads, waves, vocab)
        eng = self.engine
        want = {(PREFILL, w, p) for p in pads for w in waves if w <= eng.B}
        want |= {(DECODE, eng.B, b) for b in (1, *eng.block_buckets)}
        have = {tuple(k) for k in self._program_keys()}
        return {**out, "missing": sorted(want - have), "programs": len(have)}

    async def reference_check(self, seed: int, cfg, prompt_len: int,
                              max_tokens: int, zero_col: int | None,
                              mode: str = "float32") -> dict:
        """Prefill then decode through the paged latent pool, against the
        float32 reference's full forward pass over the same tokens. The
        program gives out tokens and no logits, so what is compared is what
        it left in its pool — every layer's rows ``[c, k_rope]``, prompt
        positions (prefill, expanded attention) and decoded positions
        (decode, absorbed attention through the page table) apart — and each
        emitted token against the reference's logits.

        **Routing flips.** A top-k choice flips between bf16 and float32
        wherever the k-th and (k+1)-th scores lie closer than the bf16 error
        of everything upstream — about one (position, layer) pair in six
        under seeded weights — and a flipped position then carries another
        expert's output: an error of tens of percent that is no fault. The
        reference is never handed the program's choices. Instead the
        comparison reads each depth where flips cannot blur it:

        * ``kv_rel_err.*``: the rows of the first expert layer (everything
          before any routing: embedding, MLA both ways, paging, the dense
          layer), all positions, norm-relative, worse of ``c`` and ``k_rope``;
        * ``moe_row_err_p50.*``: the rows one expert layer on (router,
          routed and shared experts), the MEDIAN over positions of each
          position's relative error — most positions did not flip there;
        * ``deep_row_err_p10.prefill``: the last layer's rows, the 10th
          percentile over the prompt's positions — the positions no flip
          touched on the way through every expert layer but the last;
        * ``token_logit_gap*``: the reference's best logit less its logit
          for the emitted token, in logit spreads: the worst of the tokens
          and quantiles (the last expert layer and the head);
        * reported, not judged: ``kv_rel_err_last.*`` (the last layer's rows,
          all positions, flips included) and ``route_flip_share`` (the share
          of (position, expert layer) pairs whose chosen set differs between
          the float32 reference and the reference with every matmul input
          rounded to bf16 — in a control run, to the control's precision).

        ``mode`` other than float32 is the control: the reference at that
        lower precision stands in the program's place."""
        import jax.numpy as jnp
        import numpy as np

        from benchmarks.lib.configs import load_module
        from benchmarks.lib.traffic import prompt_tokens

        ref = load_module("reference", "mla_moe")
        await self._ensure_started()
        eng = self.engine
        while any(r is not None for r in eng.slot_req) or eng.waiting:
            await asyncio.sleep(0.05)
        prompt = prompt_tokens(seed, 10**6, prompt_len, cfg.vocab_size)
        n_rows = prompt_len + max_tokens - 1
        pages = list(eng.free_pages[: -(-(prompt_len + max_tokens) // eng.PS)])
        out = await eng.generate(prompt, max_tokens=max_tokens)
        repeats = (await eng.generate(prompt, max_tokens=max_tokens)) == out
        (pool,) = eng.cache  # read before another request runs

        def compare() -> dict:
            r, split = cfg.kv_lora_rank, prompt_len
            seq = jnp.asarray([prompt + out[:-1]], jnp.int32)
            want = ref.forward(seed, cfg, seq, zero_col=zero_col)
            low = ref.forward(seed, cfg, seq, zero_col=zero_col,
                              mode="bfloat16" if mode == "float32" else mode)
            if mode == "float32":
                got = pool[:, jnp.asarray(pages)].reshape(
                    cfg.n_layers, -1, cfg.latent_width)[:, :n_rows].astype(
                        jnp.float32)
                tokens = out
            else:
                got = low["rows"][:, 0]
                tokens = [int(t) for t in jnp.argmax(
                    low["logits"][0, prompt_len - 1:], axis=-1)]
            ref_rows = want["rows"][:, 0]                 # [L, T, r + rope]

            def rel(a, b):
                return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

            def whole(layer, span):  # worse of c and k_rope, all positions
                g, w = got[layer, span], ref_rows[layer, span]
                return max(rel(g[:, :r], w[:, :r]), rel(g[:, r:], w[:, r:]))

            def by_position(layer, span, q):
                g, w = got[layer, span], ref_rows[layer, span]
                e = jnp.linalg.norm(g - w, axis=-1) / jnp.linalg.norm(w, axis=-1)
                return float(jnp.percentile(e, q))

            pre, dec = slice(0, split), slice(split, n_rows)
            first, last = cfg.first_dense_layers, cfg.n_layers - 1
            nxt = min(first + 1, last)
            logits = np.asarray(want["logits"][0, prompt_len - 1:])
            gap = (logits.max(-1) - logits[np.arange(len(tokens)), tokens]
                   ) / logits.std(-1)
            flips = jnp.any(jnp.sort(low["chosen"], -1)
                            != jnp.sort(want["chosen"], -1), axis=-1)
            return {
                "kv_rel_err.prefill": whole(first, pre),
                "kv_rel_err.decode": whole(first, dec),
                "moe_row_err_p50.prefill": by_position(nxt, pre, 50),
                "moe_row_err_p50.decode": by_position(nxt, dec, 50),
                "deep_row_err_p10.prefill": by_position(last, pre, 10),
                "kv_rel_err_last.prefill": whole(last, pre),
                "kv_rel_err_last.decode": whole(last, dec),
                "token_logit_gap": float(gap.max()),
                "token_logit_gap_p50": float(np.percentile(gap, 50)),
                "token_logit_gap_p25": float(np.percentile(gap, 25)),
                "route_flip_share": float(jnp.mean(flips)),
                "repeats": repeats, "tokens": len(out), "mode": mode,
            }

        return await asyncio.get_running_loop().run_in_executor(None, compare)

    async def reseed(self, seed: int, cfg, zero_col: int | None) -> None:
        from benchmarks.lib import weights_mla_moe as weights

        self.engine.params = None  # two sets of 10 GB do not fit side by side
        self.engine.params = await asyncio.get_running_loop().run_in_executor(
            None, lambda: weights.make_params(weights.seed_key(seed), cfg,
                                              zero_col))

"""What the ``tests/test_looped*.py`` files share: the family's tiny
configuration (the published shape's ratios kept: 4 query heads on 4 KV heads
of 16 lanes, a SwiGLU 2.75 times a model of 64, 3 layers run 4 times = 12
planes, pages of 8), an engine over seeded weights, the served cases and the
comparison with the benchmark's plain float32 reference
(``benchmarks/reference/looped.py``). The cases are spread over several files
because tier-1 runs ``--dist loadfile``: a file is one worker's."""
import asyncio

import jax.numpy as jnp
import numpy as np

from benchmarks.lib import weights_looped as W
from benchmarks.reference import looped as R
from ray_tpu.llm.engine import ContinuousBatchingEngine
from ray_tpu.models.looped import LoopedConfig

CFG = LoopedConfig.tiny()
PS, SEED = 8, 5
L, U = CFG.n_layers, CFG.n_passes
KVW = CFG.n_kv_heads * CFG.head_dim


def rel(a, b):
    return float(jnp.linalg.norm(jnp.asarray(a) - jnp.asarray(b))
                 / jnp.linalg.norm(jnp.asarray(b)))


def _engine(seed=SEED, cfg=CFG, **kw):
    params = W.make_params(W.seed_key(seed), cfg)
    kw = {"max_batch": 4, "page_size": PS, "max_seq_len": 96, "n_pages": 49,
          "eos_id": None, "block_buckets": (4, 8), **kw}
    return ContinuousBatchingEngine(params, cfg, **kw)


# prompt lengths 1, a page -1 / +0 / +1, two pages (a block of the walk where
# the tests cut it to two pages) -1 / +0 / +1, three pages; 12 decode steps
# from each cross a page's edge, and from 7-17 a block's
CASES = [(1, 12), (7, 12), (8, 12), (9, 12), (15, 12), (16, 12), (17, 12),
         (24, 12)]


def _serve(eng, cases, seed=0, only=None):
    """Serve ``cases`` (prompt length, tokens) at once -> (prompts, outputs).
    ``only``: the indices served of them, the prompts drawn as if all were."""
    async def run():
        await eng.start()
        rng = np.random.default_rng(seed)
        prompts = [rng.integers(3, CFG.vocab_size, n).tolist() for n, _ in cases]
        picked = range(len(cases)) if only is None else only
        prompts = [prompts[i] for i in picked]
        outs = await asyncio.wait_for(asyncio.gather(*(
            eng.generate(p, max_tokens=cases[i][1])
            for p, i in zip(prompts, picked))), timeout=280)
        await eng.stop()
        return prompts, outs

    return asyncio.run(run())


def _serve_one(eng, prompt, max_tokens):
    async def run():
        await eng.start()
        out = await asyncio.wait_for(eng.generate(prompt, max_tokens=max_tokens), 280)
        await eng.stop()
        return out

    return asyncio.run(run())


def _logit_gaps(cfg, prompt, out, seed=SEED, **ref_kw):
    """The reference's best logit less its logit for the token the program
    emitted, at every position, in logit spreads: zeros where the program's
    tokens are the reference's own."""
    logits = np.asarray(R.forward(seed, cfg, prompt + out[:-1],
                                  logits_from=len(prompt) - 1, planes=(),
                                  **ref_kw)["logits"])
    return (logits.max(-1) - logits[np.arange(len(out)), out]) / logits.std(-1)


def _slot_rows(eng, drawn, n_rows):
    """What the engine left in the pages ``drawn``: {"k" | "v": [planes,
    n_rows, KV * hd]} float32."""
    at = jnp.asarray(drawn)
    return {n: np.stack([np.asarray(pool[p][at]).reshape(-1, KVW)[:n_rows]
                         for p in range(pool.shape[0])])
            for n, pool in zip("kv", eng.cache)}

"""What the four ``tests/test_sink_moe*.py`` files share: the family's tiny
configuration (the published shape's ratios kept: a dense layer, then five
window layers around a full one, 8 query heads on 2 (full) and 4 (window) KV
heads, keys of 24 lanes with 8 rotated against values of 16, a window (16)
much shorter than the context on pages of 8, so a ring of 3), an engine over
seeded weights, the served cases and the comparison with the benchmark's plain
float32 reference (``benchmarks/reference/sink_moe.py``). The cases are spread
over four files because tier-1 runs ``--dist loadfile``: a file is one
worker's, and one file of them all held a worker for ten minutes (PR 53's tree)."""
import asyncio

import jax.numpy as jnp
import numpy as np

from benchmarks.lib import weights_sink_moe as W
from benchmarks.reference import sink_moe as R
from ray_tpu.llm.engine import ContinuousBatchingEngine
from ray_tpu.models.sink_moe import SinkMoeConfig

CFG = SinkMoeConfig.tiny(experts_held=(4, 12), vocab_held=(256, 512))
PS, RING, WINDOW = 8, 3, 16   # pages of 8: a window of 16 touches at most 3
SEED = 5


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


# ------------------------------------------------- the engine and the reference
def _engine(seed=SEED, cfg=CFG, **kw):
    params = W.make_params(W.seed_key(seed), cfg)
    kw = {"max_batch": 4, "page_size": PS, "max_seq_len": 160,
          "n_pages": {"full": 81, "window": 16}, "eos_id": None,
          "block_buckets": (4, 8), **kw}
    return ContinuousBatchingEngine(params, cfg, **kw)


# prompt lengths 1, a page -1 / +0 / +1, the window -1 / +0 / +1, the ring's
# rows -1 / +0 / +1, three rings; 12 decode steps from the short ones cross
# position 16 (``starts`` leaves 0) and 24 (the ring's first overwrite)
CASES = [(1, 12), (7, 12), (8, 12), (9, 12), (15, 12), (16, 12), (17, 12),
         (23, 12), (24, 12), (25, 12), (72, 30)]
FEW = [10]   # the case a control is judged on: (72, 30), three rings long


def _serve(eng, cases, seed=0, only=None):
    """Serve ``cases`` (prompt length, tokens) at once -> (prompts, outputs).
    ``only``: the indices served of them, the prompts drawn as if all were
    (a case's prompt is the same whichever file serves it)."""
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


def _logit_gaps(cfg, prompt, out, **ref_kw):
    """The reference's best logit less its logit for the token the program
    emitted, at every position, in logit spreads: zeros where the program's
    tokens are the reference's own."""
    logits = np.asarray(R.forward(SEED, cfg, prompt + out[:-1],
                                  logits_from=len(prompt) - 1, q_block=32,
                                  **ref_kw)["logits"])
    return (logits.max(-1) - logits[np.arange(len(out)), out]) / logits.std(-1)


def _serve_one(eng, prompt, max_tokens):
    async def run():
        await eng.start()
        out = await asyncio.wait_for(eng.generate(prompt, max_tokens=max_tokens), 280)
        await eng.stop()
        return out

    return asyncio.run(run())

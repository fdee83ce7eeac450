"""LLM tests: KV-cache decode parity vs full recompute, ragged batching,
serve deployment, dataset batch inference (ref test strategy:
python/ray/llm tests — engine correctness + serving integration)."""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.models.llama import LlamaConfig, llama_forward, llama_init


@pytest.fixture(scope="module")
def tiny():
    import jax

    cfg = LlamaConfig.tiny()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _greedy_full_recompute(params, cfg, prompt, max_new):
    """Reference decoder: re-run the full forward per step (no cache)."""
    import jax.numpy as jnp

    toks = list(prompt)
    for _ in range(max_new):
        logits, _ = llama_forward(params, jnp.asarray([toks], dtype=jnp.int32), cfg)
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def test_kv_cache_decode_matches_full_recompute(tiny):
    """The defining correctness property: cached incremental decode must
    produce exactly the greedy tokens of full recomputation."""
    from ray_tpu.llm import generate

    cfg, params = tiny
    prompt = [5, 17, 42, 7]
    expected = _greedy_full_recompute(params, cfg, prompt, 8)
    got = generate(params, cfg, [prompt], max_new_tokens=8, temperature=0.0)[0]
    assert got == expected, (got, expected)


def test_ragged_batch_matches_single(tiny):
    """Left-padded ragged batching must not change any sequence's output."""
    from ray_tpu.llm import generate

    cfg, params = tiny
    prompts = [[5, 17, 42, 7], [3, 9], [11, 2, 8]]
    singles = [
        generate(params, cfg, [p], max_new_tokens=6, temperature=0.0)[0]
        for p in prompts
    ]
    batched = generate(params, cfg, prompts, max_new_tokens=6, temperature=0.0)
    assert batched == singles


def test_sampled_generation_seeds(tiny):
    from ray_tpu.llm import generate

    cfg, params = tiny
    a = generate(params, cfg, [[1, 2, 3]], max_new_tokens=8, temperature=1.0, seed=1)
    b = generate(params, cfg, [[1, 2, 3]], max_new_tokens=8, temperature=1.0, seed=1)
    c = generate(params, cfg, [[1, 2, 3]], max_new_tokens=8, temperature=1.0, seed=2)
    assert a == b  # deterministic under a seed
    assert all(0 <= t < cfg.vocab_size for t in a[0])
    assert a != c or True  # different seeds usually differ; never invalid


@pytest.fixture(scope="module")
def rt():
    ray_tpu.init(num_cpus=16)
    yield ray_tpu
    from ray_tpu import serve

    serve.shutdown()
    ray_tpu.shutdown()


def test_llm_serve_deployment_batches(rt, tiny):
    """Concurrent requests coalesce into one batched decode
    (ref: serve/llm LLMServer batching)."""
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_deployment

    cfg, params = tiny
    app = build_llm_deployment(cfg, params=params, max_batch_size=4)
    handle = serve.run(app, name="llm", timeout_s=240)
    refs = [
        handle.remote({"prompt_tokens": [1, 2, 3, i], "max_tokens": 4})
        for i in range(8)
    ]
    results = ray_tpu.get(refs, timeout=300)
    assert all(len(r["completion_tokens"]) == 4 for r in results)
    assert all(0 <= t < cfg.vocab_size for r in results for t in r["completion_tokens"])
    # at least one request observed a coalesced batch
    assert max(r["usage"]["batch_size"] for r in results) > 1
    serve.delete("llm")


def test_batch_inference_over_dataset(rt, tiny):
    """Data-LLM processor: dataset of prompts -> dataset of completions
    (ref: llm/_internal/batch processors on Ray Data)."""
    from ray_tpu import data
    from ray_tpu.llm import build_llm_processor

    cfg, params = tiny
    ds = data.from_items([
        {"prompt_tokens": [1, 2, 3], "id": i} for i in range(12)
    ])
    processor = build_llm_processor(cfg, params=params, batch_size=4,
                                    max_new_tokens=3)
    out = processor(ds).take_all()
    assert len(out) == 12
    assert all(len(row["completion_tokens"]) == 3 for row in out)
    # same prompt -> same greedy completion everywhere
    assert len({tuple(row["completion_tokens"]) for row in out}) == 1


# ------------------------------------------------ continuous-batching engine
def _run(coro):
    import asyncio

    return asyncio.run(coro)


def test_engine_parity_with_batched_generate(tiny):
    """Paged-KV continuous batching must produce exactly the greedy tokens
    of the static-batch generate path."""
    from ray_tpu.llm import ContinuousBatchingEngine, generate

    cfg, params = tiny

    async def go():
        eng = ContinuousBatchingEngine(params, cfg, max_batch=4, page_size=8,
                                       n_pages=64, max_seq_len=128)
        await eng.start()
        prompts = [[1, 2, 3, 4, 5], [7, 8, 9], [11, 12, 13, 14, 15, 16, 17]]
        import asyncio

        outs = await asyncio.gather(
            *[eng.generate(p, max_tokens=8) for p in prompts])
        await eng.stop()
        return outs

    outs = _run(go())
    ref = generate(params, tiny[0], [[1, 2, 3, 4, 5], [7, 8, 9],
                                     [11, 12, 13, 14, 15, 16, 17]],
                   max_new_tokens=8, temperature=0.0)
    assert outs == ref


def test_engine_mid_decode_admission(tiny):
    """VERDICT r2 done-criterion: a request admitted while another is
    mid-decode finishes WITHOUT waiting for the running batch."""
    from ray_tpu.llm import ContinuousBatchingEngine

    cfg, params = tiny

    async def go():
        import asyncio

        eng = ContinuousBatchingEngine(params, cfg, max_batch=4, page_size=8,
                                       n_pages=64, max_seq_len=128)
        await eng.start()
        long_task = asyncio.get_event_loop().create_task(
            eng.generate([1, 2, 3], max_tokens=110))
        while eng.steps < 5:  # the long request is decoding now
            await asyncio.sleep(0.01)
        short = await eng.generate([5, 6], max_tokens=4)
        long_done_when_short_finished = long_task.done()
        long_out = await long_task
        await eng.stop()
        return short, long_out, long_done_when_short_finished

    short, long_out, long_done = _run(go())
    assert len(short) == 4
    assert len(long_out) == 110
    assert not long_done, "short request waited for the long batch to drain"


def test_engine_streaming_and_page_reclaim(tiny):
    from ray_tpu.llm import ContinuousBatchingEngine

    cfg, params = tiny

    async def go():
        eng = ContinuousBatchingEngine(params, cfg, max_batch=2, page_size=8,
                                       n_pages=32, max_seq_len=64)
        await eng.start()
        free0 = len(eng.free_pages)
        rid = eng.submit([9, 10], max_tokens=12)
        toks = [t async for t in eng.stream(rid)]
        # run several rounds: page leak would exhaust the pool
        for _ in range(6):
            await eng.generate([3, 1, 4, 1, 5], max_tokens=10)
        free1 = len(eng.free_pages)
        await eng.stop()
        return toks, free0, free1

    toks, free0, free1 = _run(go())
    assert len(toks) == 12
    assert free0 == free1, f"page leak: {free0} -> {free1}"


@pytest.mark.parametrize("eos_id,n_pages", [(None, 41), (255, 43)])
def test_engine_compiles_off_the_event_loop(tiny, eos_id, n_pages):
    """A program's first use at new shapes compiles — tens of seconds at
    real widths — and the loop that drives the engine is the one a replica
    answers health probes on. So neither driver (planned: no EOS; reactive)
    may compile an engine program on the loop thread, and the tokens are
    those of the static-batch path all the same. The pool shape is this
    test's own, so the programs are new to the process."""
    import asyncio
    import threading

    import jax.monitoring

    from ray_tpu.llm import ContinuousBatchingEngine, generate

    cfg, params = tiny
    compiled_on: dict[str, set] = {}

    def on_duration(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled_on.setdefault(kw.get("fun_name"), set()).add(
                threading.get_ident())

    prompts = [[1, 2, 3, 4, 5], [7, 8, 9]]

    async def go():
        eng = ContinuousBatchingEngine(params, cfg, max_batch=3, page_size=8,
                                       n_pages=n_pages, max_seq_len=72,
                                       eos_id=eos_id)
        await eng.start()
        outs = await asyncio.gather(
            *[eng.generate(p, max_tokens=20) for p in prompts])
        await eng.stop()
        return outs, threading.get_ident()

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        outs, loop_thread = _run(go())
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    for program in ("jit(paged_prefill_batch)", "jit(paged_decode_multi)"):
        assert compiled_on.get(program), f"{program} was not new: {compiled_on}"
        assert loop_thread not in compiled_on[program], (
            f"{program} compiled on the event loop")
    ref = generate(params, cfg, prompts, max_new_tokens=20, temperature=0.0)
    if eos_id is None:
        assert outs == ref
    else:  # stops at EOS, if the model happens to emit it
        assert all(o == r[:len(o)] and len(o) >= 1 for o, r in zip(outs, ref))


def test_engine_lora_multiplex(tiny):
    """Two adapters in ONE decode batch must produce their own outputs
    (and differ from base when the adapter is non-trivial)."""
    import numpy as np

    from ray_tpu.llm import ContinuousBatchingEngine

    cfg, params = tiny
    rng = np.random.default_rng(0)
    r = 4
    D, Oq = cfg.d_model, cfg.n_heads * cfg.head_dim
    adapters = {
        "alpha": {"wq_a": rng.normal(0, 0.3, (D, r)),
                  "wq_b": rng.normal(0, 0.3, (r, Oq))},
        "beta": {},  # zero adapter == base model
    }

    async def go():
        import asyncio

        eng = ContinuousBatchingEngine(params, cfg, max_batch=4, page_size=8,
                                       n_pages=64, max_seq_len=64,
                                       lora_adapters=adapters, lora_rank=r)
        await eng.start()
        prompt = [5, 6, 7, 8]
        base, alpha, beta = await asyncio.gather(
            eng.generate(prompt, max_tokens=8),
            eng.generate(prompt, max_tokens=8, adapter="alpha"),
            eng.generate(prompt, max_tokens=8, adapter="beta"),
        )
        await eng.stop()
        return base, alpha, beta

    base, alpha, beta = _run(go())
    assert beta == base, "zero adapter must match the base model"
    assert alpha != base, "non-trivial adapter produced base outputs"


def test_engine_serve_streaming(rt, tiny):
    """Tokens stream through the serve handle: the first token arrives
    well before the request completes."""
    import time

    from ray_tpu import serve
    from ray_tpu.llm import build_llm_engine_deployment

    cfg, params = tiny
    app = build_llm_engine_deployment(
        cfg, params=params, max_batch=4, page_size=8, n_pages=64,
        max_seq_len=128)
    serve.run(app, name="llm_engine")
    try:
        handle = serve.get_deployment_handle("LLMEngineServer", "llm_engine")
        # full completion path
        out = ray_tpu.get(handle.remote(
            {"prompt_tokens": [1, 2, 3], "max_tokens": 5}), timeout=120)
        assert len(out["completion_tokens"]) == 5
        # streaming path: iterate the ObjectRefGenerator
        gen = handle.stream.stream({"prompt_tokens": [1, 2, 3],
                                    "max_tokens": 30})
        t0 = time.monotonic()
        toks = []
        first_at = None
        for ref in gen:
            toks.append(ray_tpu.get(ref, timeout=60))
            if first_at is None:
                first_at = time.monotonic() - t0
        total = time.monotonic() - t0
        assert len(toks) == 30
        assert first_at < total * 0.7, (
            f"first token at {first_at:.2f}s of {total:.2f}s — not streaming")
    finally:
        serve.delete("llm_engine")


def test_int8_kv_quantize_roundtrip():
    """The per-(token, kv-head) symmetric int8 quantizer loses < 1% on
    typical KV magnitudes (llm/llama.py _kv_write/_kv_read contract)."""
    import jax.numpy as jnp

    from ray_tpu.llm.llama import _kv_read, _kv_write

    rng = np.random.default_rng(0)
    L, P, PS, KV, hd = 1, 4, 8, 2, 16
    pool = {"q": jnp.zeros((L, P, PS, KV, hd), jnp.int8),
            "s": jnp.zeros((L, P, PS, KV), jnp.float32)}
    val = jnp.asarray(rng.normal(0, 0.7, size=(PS, KV, hd)),
                      dtype=jnp.float32)
    row = jnp.full((PS,), 2, jnp.int32)
    off = jnp.arange(PS, dtype=jnp.int32)
    pool = _kv_write(pool, 0, row, off, val)
    # read the page back through the gather path (1 "slot" seeing page 2)
    page_tables = jnp.asarray([[2]], jnp.int32)
    got = _kv_read(pool, 0, page_tables, jnp.float32)
    err = jnp.abs(got[0] - val) / (jnp.max(jnp.abs(val)) + 1e-9)
    assert float(jnp.max(err)) < 0.01, float(jnp.max(err))


def test_engine_int8_kv_matches_bf16_engine(tiny):
    """kv_dtype="int8" is a drop-in: same API, greedy outputs agree with
    the full-precision engine on nearly every token (int8 rounding can
    legitimately flip near-ties, so this asserts agreement, not
    equality)."""
    import asyncio

    from ray_tpu.llm import ContinuousBatchingEngine

    cfg, params = tiny
    prompts = [[1, 2, 3, 4, 5], [7, 8, 9], [11, 12, 13, 14, 15, 16, 17],
               [21, 22], [30, 31, 32, 33]]

    def run(kv_dtype):
        async def go():
            eng = ContinuousBatchingEngine(
                params, cfg, max_batch=4, page_size=8, n_pages=64,
                max_seq_len=128, kv_dtype=kv_dtype)
            await eng.start()
            outs = await asyncio.gather(
                *[eng.generate(p, max_tokens=10) for p in prompts])
            await eng.stop()
            return outs

        return _run(go())

    base = run(None)
    q8 = run("int8")
    total = sum(len(o) for o in base)
    agree = sum(int(x == y) for b, q in zip(base, q8)
                for x, y in zip(b, q))
    assert agree / total >= 0.85, f"agreement {agree}/{total}"
    with pytest.raises(ValueError, match="kv_dtype"):
        ContinuousBatchingEngine(params, cfg, kv_dtype="fp4")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("tq", [1, 5])
@pytest.mark.parametrize("group", [1, 4, 8])
def test_gqa_attn_matches_repeat_then_attend(group, tq, dtype):
    """``_gqa_attn`` groups the query heads over the KV heads; the plain
    float32 reference here repeats K and V to H heads and attends. Query
    head h must read KV head h // G: every KV head's values sit around
    their own level, so a wrong head order lands on a wrong level."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm.llama import _gqa_attn

    B, KV, d, tk = 3, 2, 16, 11
    H = KV * group
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(group * 10 + tq), 3)
    q = jax.random.normal(kq, (B, tq, H, d), jnp.float32)
    k = jax.random.normal(kk, (B, tk, KV, d), jnp.float32)
    level = jnp.arange(KV, dtype=jnp.float32)[None, None, :, None]
    v = level + 0.1 * jax.random.normal(kv_, (B, tk, KV, d), jnp.float32)
    # decode-like: row j of slot b attends keys 0..pos[b]+j; the last three
    # keys are masked for every row, and slot 0's first row attends one key
    pos = jnp.asarray([0, 3, tk - 4 - (tq - 1)])
    mask = (jnp.arange(tk)[None, None, :]
            <= pos[:, None, None] + jnp.arange(tq)[None, :, None])
    assert not bool(mask[:, :, -3:].any()) and int(mask[0, 0].sum()) == 1
    q, k, v = (x.astype(dtype) for x in (q, k, v))

    got = _gqa_attn(q, k, v, mask)
    assert got.shape == (B, tq, H, d) and got.dtype == q.dtype

    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    kf, vf = (jnp.repeat(x, group, axis=2) for x in (kf, vf))
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf, precision="highest") / np.sqrt(d)
    w = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), axis=-1)
    want = jnp.einsum("bhqk,bkhd->bqhd", w, vf, precision="highest")

    got = np.asarray(got.astype(jnp.float32))
    np.testing.assert_allclose(got, np.asarray(want),
                               atol=1e-5 if dtype == "float32" else 3e-2)
    levels = np.rint(got.mean(axis=-1))  # [B, tq, H]
    assert (levels == np.arange(H) // group).all()


def _kv_counters():
    from ray_tpu.utils import metrics

    totals = metrics.stage_totals()
    return tuple(
        totals[f"rt_llm_decode_kv_tokens_{n}_total"].get("", {}).get("sum", 0)
        for n in ("live", "read"))


def _lone_then_admission(params, cfg, eos_id):
    """One lone request whose counters can be reckoned by hand, then a long
    request (14 tokens: it ends inside a 4-step block), and a short one
    admitted while it decodes. Returns (counters' growth over the lone
    request, the lone request's tokens, the long one's, the short one's)."""
    import asyncio

    from ray_tpu.llm import ContinuousBatchingEngine

    async def go():
        eng = ContinuousBatchingEngine(
            params, cfg, max_batch=3, page_size=8, n_pages=64, max_seq_len=64,
            eos_id=eos_id, block_buckets=(4,))
        await eng.start()
        before = _kv_counters()
        lone = await eng.generate([1, 2, 3, 4, 5], max_tokens=9)
        grown = tuple(a - b for a, b in zip(_kv_counters(), before))
        steps = eng.steps
        long_task = asyncio.get_event_loop().create_task(
            eng.generate([1, 2, 3], max_tokens=14))
        while eng.steps < steps + 4:  # the long request is decoding now
            await asyncio.sleep(0.01)
        short = await eng.generate([5, 6, 7, 8, 9], max_tokens=6)
        long_out = await long_task
        await eng.stop()
        return grown, lone, long_out, short

    return _run(go())


@pytest.mark.parametrize("eos_id", [None, 255], ids=["planned", "reactive"])
def test_engine_decode_in_place_matches_gathered(tiny, eos_id, monkeypatch):
    """The decode step that reads K and V in place (the Pallas kernel, here
    interpreted) gives the greedy tokens of the one that gathers the window,
    in both loops, across a mid-decode admission and a request that ends
    inside a block; and the two read counters say which path ran. On this
    backend the engine gathers unless the seam's rule, which every family
    binds as ``_reads_in_place``, is answered for it, as here — on a TPU a
    plain pool takes the kernel by itself. The Llama family asks its pool too
    (``_pool_walkable``: the tiny head is not whole lane tiles, which the
    interpreted kernel does not mind)."""
    from ray_tpu.llm import llama as programs

    cfg, params = tiny
    pool = programs.make_kv_pools(cfg, 8, 4, None)[0]
    assert not programs._reads_in_place() and not programs._walks(pool)
    assert not programs._pool_walkable(programs.make_kv_pools(cfg, 8, 4, "int8")[0])
    grown_g, *gathered = _lone_then_admission(params, cfg, eos_id)
    assert [len(o) for o in gathered] == [9, 14, 6]

    monkeypatch.setattr(programs, "_reads_in_place", lambda: True)
    monkeypatch.setattr(programs, "_pool_walkable",
                        lambda pool: not isinstance(pool, dict))
    programs.paged_decode_multi.clear_cache()  # traced with the other answer
    try:
        grown_k, *in_place = _lone_then_admission(params, cfg, eos_id)
    finally:
        programs.paged_decode_multi.clear_cache()
    assert in_place == gathered

    if eos_id is None:
        # the planned loop dispatches exactly the lone request's 8 decode
        # steps: two blocks of 4 from lengths 5 and 9, attending 6..13
        # positions. Gathered, a step fetches 3 slots x 8 pages x 8 tokens;
        # in place, whole pages: 8 for 6..8, 16 for 9..13.
        assert grown_g == (sum(range(6, 14)), 8 * 3 * 8 * 8)
        assert grown_k == (sum(range(6, 14)), 3 * 8 + 5 * 16)
    else:  # the reactive loop runs on past a lone request's last token
        # while that is on its way to the host: whole steps, one a block
        assert min(grown_g[0], grown_k[0]) >= sum(range(6, 14))
        # its 8 steps and one or two run-on blocks of one step, no more
        assert grown_g[1] % (3 * 8 * 8) == 0
        assert 8 <= grown_g[1] // (3 * 8 * 8) <= 8 + 2
        assert grown_k[0] <= grown_k[1] < 2 * grown_k[0]  # whole pages of 8


def _prefill_wave(programs, cfg, params, N, Tp, kv_dtype, told: bool):
    """``paged_prefill_batch`` once over a wave of ``N`` prompts padded to
    ``Tp`` (the last row of several right-padded), with the seam's rule
    answered ``told``. Returns the logits of the last real rows (the sampling
    tail set aside), both pools as float arrays and whether the traced
    program holds a Pallas call."""
    import jax
    import jax.numpy as jnp

    PS = 16
    rng = np.random.default_rng(N * 1000 + Tp)
    true_lens = np.full(N, Tp, np.int32)
    if N > 1:
        true_lens[-1] = Tp - 37
    tokens = rng.integers(3, cfg.vocab_size, (N, Tp)).astype(np.int32)
    tokens[np.arange(Tp)[None, :] >= true_lens[:, None]] = 0
    n_pages = -(-Tp // PS)
    pages = 1 + np.arange(N * n_pages, dtype=np.int32).reshape(N, n_pages)
    args = (params, None, jnp.zeros(N, jnp.int32), jnp.asarray(tokens),
            jnp.asarray(pages))
    tail = (jnp.asarray(true_lens), jnp.zeros(N, jnp.float32),
            jax.random.PRNGKey(0))
    was = programs._reads_in_place, programs._sample_tail
    programs._reads_in_place = lambda: told
    programs._sample_tail = lambda logits, temps, key: logits
    programs.paged_prefill_batch.clear_cache()  # traced with other answers
    try:
        def run(*pools):  # fresh pools a call: the program donates them
            return programs.paged_prefill_batch(*args, *pools, *tail, cfg=cfg)

        def pools():
            return programs.make_kv_pools(cfg, PS, 1 + N * n_pages, kv_dtype)

        kernel = "pallas_call" in str(jax.make_jaxpr(run)(*pools()))
        logits, kpool, vpool = run(*pools())
    finally:
        programs._reads_in_place, programs._sample_tail = was
        programs.paged_prefill_batch.clear_cache()

    def rows(pool):
        if isinstance(pool, dict):
            return (np.asarray(pool["q"], np.float32)
                    * np.asarray(pool["s"], np.float32)[..., None])
        return np.asarray(pool.astype(jnp.float32))

    return np.asarray(logits.astype(jnp.float32)), rows(kpool), rows(vpool), kernel


@pytest.mark.parametrize("G,hd,N,Tp,dtype,kv_dtype,blocked", [
    (1, 128, 1, 128, "bfloat16", None, True),
    (4, 128, 1, 128, "bfloat16", None, True),
    (8, 128, 1, 128, "bfloat16", None, True),
    (1, 128, 3, 384, "bfloat16", None, True),
    (4, 128, 3, 384, "bfloat16", None, True),
    (8, 128, 3, 384, "bfloat16", None, True),
    (4, 128, 1, 384, "float32", None, True),
    (8, 128, 3, 128, "float32", None, True),
    (4, 128, 3, 128, "bfloat16", "int8", True),
    (4, 128, 3, 144, "bfloat16", None, False),
    (4, 64, 3, 128, "bfloat16", None, False),
], ids=str)
def test_prefill_batch_blocked_matches_plain(G, hd, N, Tp, dtype, kv_dtype,
                                             blocked):
    """The dense family's whole-prompt prefill attends through the blocked
    kernel (``ops/prefill_attention.py``, here interpreted) where the seam's
    rule says so and the shapes are the kernel's — a pad of whole blocks of
    128, a head of whole lane tiles — and gives what ``_gqa_attn`` over the
    masked square gives: the same first tokens, the logits and every layer's
    K and V rows to bf16's rounding (two bf16 programs that round in another
    order read 0.008-0.015 apart as the benchmark's ``kv_rel_err`` counts,
    0.009-0.018 in the worst element; a float32 model's agree to 1e-5; layer
    0's rows exactly: nothing attends before them), at one, four and eight
    query heads a KV head, for a lone prompt and a wave with a right-padded
    row, into int8 pools too (the kernel sees the fresh K and V either way).
    A pad of 144 and a 64-wide head take the plain form told or not: the
    same program, bit for bit."""
    import jax

    from ray_tpu.llm import llama as programs

    cfg = LlamaConfig(vocab_size=256, d_model=2 * G * hd, n_layers=2,
                      n_heads=2 * G, n_kv_heads=2, d_ff=256, max_seq_len=512,
                      dtype=dtype)
    assert cfg.head_dim == hd
    params = llama_init(jax.random.PRNGKey(G), cfg)
    plain = _prefill_wave(programs, cfg, params, N, Tp, kv_dtype, told=False)
    told = _prefill_wave(programs, cfg, params, N, Tp, kv_dtype, told=True)
    assert not plain[3] and told[3] == blocked
    if not blocked:
        for a, b in zip(plain[:3], told[:3]):
            np.testing.assert_array_equal(a, b)
        return
    assert (plain[0].argmax(-1) == told[0].argmax(-1)).all()
    limits = (1e-4, 1e-4) if dtype == "float32" else (0.03, 0.06)

    def apart(a, b):  # as the benchmark's kv_rel_err, and the worst element
        return (np.linalg.norm(a - b) / np.linalg.norm(a),
                np.abs(a - b).max() / np.abs(a).max())

    assert np.less(apart(plain[0], told[0]), limits).all()
    for a, b in zip(plain[1:3], told[1:3]):
        np.testing.assert_array_equal(a[0], b[0])
        assert np.abs(a[1]).max() > 0.5  # the rows are there to compare
        assert np.less(apart(a[1], b[1]), limits).all(), apart(a[1], b[1])


# ------------------------------------------------------------------ the seam
_KERNEL_READ = (r'(?<!\["moe"\])\[\s*"(wq|wk|wv|wo|w_gate|w_up|w_down)"\s*\]')


def _weights_read_in_one_place():
    """A dense Llama layer's seven kernels are subscripted by the shared
    halves of ``models/llama.py`` and by nothing else: no other function of
    that file (``llama_init`` builds them; ``llama_serving_layout`` takes
    five of them OUT of a layer to join them and reads none; the ``moe``
    sub-tree, the LoRA stack's ``wq_a`` names and the partition rules' name
    sets are not reads), no module under ``ray_tpu/llm/`` (``llm/mla_moe.py``
    applies its OWN family's ``wo``). The joined kernels have two readers
    too: ``llama_project`` (``wqkv``) and ``llama_ffn`` (``w_gate_up``)."""
    import importlib
    import inspect
    import pkgutil
    import re

    import ray_tpu.llm
    from ray_tpu.models import llama

    halves = {"llama_project", "llama_attn_out", "llama_ffn"}
    reads = {}
    for name, fn in inspect.getmembers(llama, inspect.isfunction):
        if fn.__module__ == llama.__name__ and name != "llama_init":
            reads[name] = set(re.findall(_KERNEL_READ, inspect.getsource(fn)))
    assert set().union(*(reads[h] for h in halves)) == {
        "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}
    assert {n: r for n, r in reads.items() if r and n not in halves} == {}
    joined = {n: set(re.findall(r'\[\s*"(wqkv|w_gate_up)"\s*\]',
                                inspect.getsource(fn)))
              for n, fn in inspect.getmembers(llama, inspect.isfunction)}
    assert {n: r for n, r in joined.items() if r} == {
        "llama_project": {"wqkv"}, "llama_ffn": {"w_gate_up"}}
    for info in pkgutil.walk_packages(ray_tpu.llm.__path__, "ray_tpu.llm."):
        if info.name != "ray_tpu.llm.mla_moe":
            src = inspect.getsource(importlib.import_module(info.name))
            assert not re.findall(_KERNEL_READ, src), info.name


def _imports_of(source: str) -> set:
    """Every module a source names in an import, a ``from`` import counted
    as the module and as each name under it."""
    import ast

    names = set()
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.ImportFrom):
            names |= {f"{node.module}.{a.name}" for a in node.names}
            names.add(node.module)
        elif isinstance(node, ast.Import):
            names |= {a.name for a in node.names}
    return names


def _families() -> dict:
    """name -> module, of every module under ``ray_tpu/llm/`` that defines
    ``PROGRAMS``: found, not listed."""
    import importlib
    import pkgutil

    import ray_tpu.llm

    out = {}
    for info in pkgutil.iter_modules(ray_tpu.llm.__path__):
        mod = importlib.import_module(f"ray_tpu.llm.{info.name}")
        if hasattr(mod, "PROGRAMS"):
            out[info.name] = mod
    return out


def _arrows_point_one_way():
    """engine -> seam -> family programs -> models -> ops: the engine
    defines no device program and does not pull in a family it does not
    serve (but the Llama family's, which ``llm/__init__.py`` exports and the
    engine re-imports for ``benchmarks/sizing.py``: ROADMAP D11); no program
    module (nor the seam) imports the engine."""
    import inspect
    import subprocess
    import sys

    from ray_tpu.llm import engine, programs

    families = _families()
    assert {"llama", "mla_moe", "cohere2_moe", "sparse_moe", "ssm_moe",
            "eva", "kda_moe", "cca_moe", "sink_moe", "looped"} <= set(families)
    subprocess.run(
        [sys.executable, "-c",
         "import sys, ray_tpu.llm.engine; "
         f"assert not [f for f in {sorted(set(families) - {'llama'})} "
         "if 'ray_tpu.llm.' + f in sys.modules]"],
        check=True, timeout=120)
    assert "jax.jit" not in inspect.getsource(engine)
    for mod in (*families.values(), programs):
        assert "ray_tpu.llm.engine" not in _imports_of(
            inspect.getsource(mod)), mod.__name__


def _a_family_is_declared_once():
    """No family's file names another family's, in ``llm/`` or in
    ``models/``; the seam names no family and imports nothing of ``models/``;
    the platform rule is read in one place; and serving a config loads its
    own family's programs and no other's."""
    import inspect
    import pathlib
    import re
    import subprocess
    import sys

    import ray_tpu.llm
    import ray_tpu.models
    from ray_tpu.llm import programs

    families = _families()
    for pkg in (ray_tpu.llm, ray_tpu.models):
        for name in families:
            source = pathlib.Path(pkg.__path__[0], f"{name}.py").read_text()
            theirs = {f"{p}.{other}" for other in families if other != name
                      for p in ("ray_tpu.llm", "ray_tpu.models")}
            assert not theirs & _imports_of(source), (pkg.__name__, name)
    seam = inspect.getsource(programs)
    assert not [m for m in _imports_of(seam) if m.startswith("ray_tpu.models")]
    assert "isinstance(cfg" not in seam
    assert "isinstance(cfg" not in inspect.getsource(ray_tpu.models)
    asks = [f"{path.name}:{n}" for path in
            sorted(pathlib.Path(ray_tpu.llm.__path__[0]).rglob("*.py"))
            for n, line in enumerate(path.read_text().splitlines(), 1)
            if "default_backend" in line]
    assert len(asks) == 1 and asks[0].startswith("programs.py:"), asks
    # the one definition, bound by name in every family and answered there
    for name, mod in families.items():
        assert mod._reads_in_place is programs.reads_in_place, name
        assert not re.search(r"def _reads_in_place", inspect.getsource(mod))
    script = (
        "import sys, importlib\n"
        "from ray_tpu.llm.programs import serving_programs\n"
        f"families = {sorted(families)}\n"
        "name, cls = sys.argv[1:]\n"
        "cfg = getattr(importlib.import_module('ray_tpu.models.' + name), cls).tiny()\n"
        "P = serving_programs(cfg)\n"
        "assert P.family == name and P.init is not None\n"
        "loaded = {f for f in families if 'ray_tpu.llm.' + f in sys.modules}\n"
        "assert loaded - {'llama'} == {name} - {'llama'}, loaded\n")
    configs = {"llama": "LlamaConfig", "mla_moe": "MlaMoeConfig",
               "cohere2_moe": "Cohere2MoeConfig", "sparse_moe": "SparseMoeConfig",
               "ssm_moe": "SsmMoeConfig", "eva": "EvaConfig",
               "kda_moe": "KdaMoeConfig", "cca_moe": "CcaMoeConfig",
               "sink_moe": "SinkMoeConfig", "looped": "LoopedConfig"}
    assert set(configs) == set(families)
    procs = [subprocess.Popen([sys.executable, "-c", script, name, cls])
             for name, cls in configs.items()]
    assert [p.wait(timeout=240) for p in procs] == [0] * len(procs)


@pytest.mark.parametrize("check", [_weights_read_in_one_place,
                                   _arrows_point_one_way,
                                   _a_family_is_declared_once],
                         ids=["weights", "imports", "families"])
def test_llm_seam(check):
    check()


# ------------------------------------------------ admission without a drain
# What deployments run: an ``eos_id`` (the reactive loop) and more callers
# than slots, so that a slot whose end is scheduled is swept and refilled
# behind the block that ends it. eos 1000 lies outside every tiny vocabulary.
def _family_engine(family: str, **kw):
    """A tiny engine of ``family`` with two slots, and its vocabulary."""
    import importlib

    import jax

    from ray_tpu.llm import ContinuousBatchingEngine

    kw = {"max_batch": 2, "page_size": 8, "eos_id": 1000,
          "block_buckets": (4, 8), **kw}
    if family == "llama":
        cfg = LlamaConfig.tiny()
        params = llama_init(jax.random.PRNGKey(0), cfg)
        kw = {"n_pages": 64, "max_seq_len": 128, **kw}
    else:
        W = importlib.import_module(f"benchmarks.lib.weights_{family}")
        name = "".join(p.title() for p in family.split("_")) + "Config"
        cfg = getattr(importlib.import_module(f"ray_tpu.models.{family}"),
                      name).tiny(experts_held=(4, 12), vocab_held=(256, 512))
        params = W.make_params(W.seed_key(5), cfg)
        kw = {"max_seq_len": 96, "n_pages": {
            "ssm_moe": {"kv": 41, "state": 4},
            "cohere2_moe": {"full": 61, "window": 16}}[family], **kw}
    return ContinuousBatchingEngine(params, cfg, **kw), cfg.vocab_size


def _prompts(vocab: int, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, vocab, n).tolist() for n in lens]


def _admit_counters():
    from ray_tpu.utils import metrics

    return tuple(metrics.stage_totals()[f"rt_llm_{n}_total"]
                 .get("", {}).get("sum", 0)
                 for n in ("prefill_waves", "admit_waves_undrained"))


def _all_given_back(eng) -> bool:
    """Every slot, page and row is free again and no request is left."""
    return (all(r is None for r in eng.slot_req) and not eng._reqs
            and [len(f) for f in eng.free] == eng.capacity
            and not any(t.any() for t in eng.tables))


# (prompt length, max_tokens): ends inside blocks and at their edges, a
# one-token request, a prompt past cohere2's window of 32, pages of 8 crossed
_HANDED_ON = [(10, 13), (40, 9), (2, 5), (5, 1), (20, 17), (7, 8), (33, 12)]
# a pool that BINDS: four slots and 20 pages of 8 positions, five eighths of
# four whole reservations of 16 + 48 — the sum of the ends does not fit, the
# peak of what staggered requests hold does. Reserving whole, as the engine
# did, two of these live at once: a third asks for 4 to 8 of the 4 pages left
_BINDING = [(16, 48), (16, 20), (16, 36), (16, 12), (16, 48), (16, 28),
            (16, 40), (16, 16), (16, 44), (16, 24)]
_BINDING_POOLS = {"llama": 21, "cohere2_moe": {"full": 21, "window": 25}}


def _watch_growth(eng) -> dict:
    """Taps ``eng._grow``: the most slots that stepped in one block, and that
    after it no table entry a stepping slot's block reaches is the junk
    page's, of any kind."""
    seen, grow = {"live": 0}, eng._grow

    def tap(K):
        grow(K)
        stepping = [(i, r) for i, r in enumerate(eng.slot_req) if r is not None
                    and not r.cancelled and r.planned < r.max_tokens]
        seen["live"] = max(seen["live"], len(stepping))
        for i, r in stepping:
            reach = len(r.prompt) + min(r.planned + K, r.max_tokens)
            for table, n in zip(eng.tables, eng._pages_of(reach)):
                assert table[i, :n].all(), (i, K, reach, table[i])

    eng._grow = tap
    return seen


@pytest.mark.parametrize("family,binds,eos_id", [
    ("llama", False, 1000), ("ssm_moe", False, 1000),
    ("cohere2_moe", False, 1000), ("llama", True, 1000), ("llama", True, None),
    ("cohere2_moe", True, 1000), ("cohere2_moe", True, None)])
def test_replies_equal_those_served_alone_when_slots_are_handed_on(
        family, binds, eos_id):
    """The page- and row-reuse guard: seven greedy requests on two slots, so
    five of them take a slot (its pages, its ring, its state row) that was
    swept while blocks of its last holder were in flight. Each reply equals
    the same request served alone, and everything is given back. Where the
    pool ``binds`` (ten requests on four slots over ``_BINDING_POOLS``, both
    loops): a slot holds the pages its positions have reached, so more than
    two live at once, each block steps into pages drawn before it, and a page
    given back mid-flight is drawn again by a slot that grows."""
    import asyncio

    cases = _BINDING if binds else _HANDED_ON
    eng, vocab = _family_engine(family, eos_id=eos_id, **(
        {"max_batch": 4, "n_pages": _BINDING_POOLS[family]} if binds else {}))
    prompts = _prompts(vocab, [n for n, _ in cases])
    seen = _watch_growth(eng)

    async def go():
        await eng.start()
        before = _admit_counters()
        together = await asyncio.wait_for(asyncio.gather(*(
            eng.generate(p, max_tokens=m)
            for p, (_, m) in zip(prompts, cases))), timeout=600)
        waves, undrained = (a - b for a, b in zip(_admit_counters(), before))
        for _ in range(200):  # the planned loop's sweep behind the last block
            if _all_given_back(eng):
                break
            await asyncio.sleep(0.01)
        given_back = _all_given_back(eng)
        alone = [await eng.generate(p, max_tokens=m)
                 for p, (_, m) in zip(prompts, cases)]
        await eng.stop()
        return together, alone, waves, undrained, given_back

    together, alone, waves, undrained, given_back = _run(go())
    assert [len(o) for o in together] == [m for _, m in cases]
    assert together == alone
    assert given_back
    if binds:
        assert 2 < seen["live"] <= 4, seen
        return
    # every wave finds a block in flight and waits for none, but: the first
    # admission's (two pads, two waves into an idle engine) and the one
    # behind the one-token request, whose only token is on the host before a
    # sweep meets it — the drained path, like any end that emission finds
    assert waves >= 5 and undrained >= waves - 3, (waves, undrained)


# --------------------------------------------------- the timeline's bound
def _bookkeeper(kinds, counts, B, **kw):
    """An engine that is never started, for its host bookkeeping alone: ``B``
    slots over pages of 8 positions of the given ``kinds``, ``counts`` pages
    a kind (the junk page included)."""
    import jax

    from ray_tpu.llm import ContinuousBatchingEngine
    from ray_tpu.llm.engine import _FreePages

    cfg = LlamaConfig.tiny()
    eng = ContinuousBatchingEngine(
        llama_init(jax.random.PRNGKey(0), cfg), cfg, max_batch=B, page_size=8,
        n_pages=8, max_seq_len=128, **kw)
    eng.kinds = kinds
    eng.free = [_FreePages(n) for n in counts]
    eng.capacity = [n - 1 for n in counts]
    eng.tables = [np.zeros((B, k.table), np.int32) for k in kinds]
    return eng


def _admit(eng):
    """The top of a turn of the loops, bookkeeping alone: scheduled ends
    swept and the head of the queue admitted while it fits. Returns the head
    that was refused though a slot stood empty — refused for pages."""
    eng._sweep(scheduled=True)
    while eng.waiting and any(r is None for r in eng.slot_req):
        if eng._reserve_slot(eng.waiting[0]) is None:
            return eng.waiting[0]
        eng.waiting.pop(0).planned = 1


def _block(eng, K=None) -> None:
    """The rest of the turn: one block's pages drawn (``_grow`` asserts that
    every page the block steps into was there) and its steps planned."""
    K = K or eng._pick_block()
    eng._grow(K)
    for r in eng.slot_req:
        if r is not None:
            r.planned = min(r.max_tokens, r.planned + K)


@pytest.mark.parametrize("seed", range(6))
def test_the_timeline_admits_what_fits_and_refuses_what_would_not(seed):
    """Seeded random requests over four kinds of pages — a row a position, a
    ring, a strided kind, a state row — in pools that bind: stepping what
    admission let in to its ends, in the blocks ``_pick_block`` sizes, never
    asks the free pages for a page they lack (``_grow`` asserts it), a cancel
    only lowers what is held and promised, and a request the check refused
    WOULD have: let in all the same and stepped a step a block, some block
    lacks a page. Everything is given back at the end."""
    import copy

    from ray_tpu.llm.engine import _Request
    from ray_tpu.llm.programs import PageKind

    rng = np.random.default_rng(seed)
    kinds = (PageKind("full", 1, 16), PageKind("window", 1, 4, reach=24),
             PageKind("pairs", 1, 4, stride=4), PageKind("state", 1, 1,
                                                         positions=False))
    counts = [int(rng.integers(25, 45)), int(rng.integers(9, 17)),
              int(rng.integers(7, 12)), 7]
    eng = _bookkeeper(kinds, counts, B=6)
    for i in range(40):  # two tokens at the least: see ``_timeline`` on one
        n = int(rng.integers(1, 64))
        eng.waiting.append(_Request(i, [1] * n, int(rng.integers(2, 128 - n)),
                                    0.0, 0))
    refused = lacked = 0
    while eng.waiting or any(r is not None for r in eng.slot_req):
        head = _admit(eng)
        if head is not None:  # what if it had been let in all the same
            refused += 1
            twin = _bookkeeper(kinds, counts, B=6)
            twin.slot_req, twin.tables, twin.free = copy.deepcopy(
                (eng.slot_req, eng.tables, eng.free))
            late, slot = copy.deepcopy(head), twin.slot_req.index(None)
            twin.slot_req[slot], late.slot, late.planned = late, slot, 1
            short = not twin._draw(slot, len(late.prompt), 128, grown=False)
            while not short and any(r is not None for r in twin.slot_req):
                short = twin._lacking(1)
                if not short:
                    _block(twin, K=1)
                    _admit(twin)
            lacked += short
        _block(eng)
        live = [r for r in eng.slot_req if r is not None]
        if rng.random() < 0.1 and live:  # an EOS or a cancel, mid-block
            before = eng._timeline()
            live[int(rng.integers(len(live)))].cancelled = True
            assert (eng._timeline() <= before).all()
    assert refused and lacked == refused, (refused, lacked)
    assert _all_given_back(eng)


def test_the_speculative_engine_still_draws_whole():
    """A speculative step advances a slot by what its drafts are worth, so no
    timeline knows its positions: there admission counts every resident at its
    end — the sum of the whole reservations, as it always did — and a slot
    draws whole at once, through the same drawing path."""
    from ray_tpu.llm.engine import _Request
    from ray_tpu.llm.programs import PageKind

    kinds = (PageKind("kv", 1, 16),)
    spec, plain = (_bookkeeper(kinds, [21], B=4, spec_enable=on)
                   for on in (True, False))
    for eng in (spec, plain):
        eng.waiting = [_Request(i, [1] * 16, 48, 0.0, 0) for i in range(4)]
        while eng.waiting and eng._reserve_slot(eng.waiting[0]) is not None:
            eng.waiting.pop(0)
    # 20 pages: two whole requests of 8, or the prompts of four that the
    # timeline refuses all the same (their ends coincide: 4 x 8 at the peak)
    assert [len(e.waiting) for e in (spec, plain)] == [2, 2]
    assert [int(np.count_nonzero(e.tables[0])) for e in (spec, plain)] == [16, 4]
    assert list(spec._timeline()) == list(plain._timeline()) == [16]
    assert len(spec.free[0]) == 4 and not spec._growth(4)[0].any()
    # what the scheduler is told: pages not yet drawn are not pages to spare
    room = plain.headroom()
    assert (room["free_pages"], room["free_pages_now"]) == (4, 16)
    assert room["free_pages_by_kind"] == {"kv": 4}


def test_a_request_of_one_token_draws_the_page_a_step_run_on_writes():
    """A request of ONE token is all dispatched at admission, and the loop
    runs on a step a block until that token is on the host: such a step
    writes the token's position, which a prompt of whole pages leaves on a
    page the prompt did not draw. The benchmark's replicas read the state
    that those steps leave (``served(1)``): the page must be the slot's own,
    not the junk page every dead slot writes."""
    from ray_tpu.llm.engine import _Request
    from ray_tpu.llm.programs import PageKind

    eng = _bookkeeper((PageKind("kv", 1, 16),), [9], B=2)
    eng.waiting = [_Request(1, [1] * 8, 1, 0.0, 0)]
    assert _admit(eng) is None and np.count_nonzero(eng.tables[0]) == 1
    assert list(eng._timeline()) == [2] == eng._pages_of(8 + 1)
    _block(eng, K=1)  # the step run on: position 8, the second page
    assert np.count_nonzero(eng.tables[0][0]) == 2
    _block(eng, K=1)  # and no page past the request's end
    assert np.count_nonzero(eng.tables[0][0]) == 2


def _eos_case(first, rest):
    """A token of ``first`` to stand for the EOS: its first occurrence lies
    in the middle of a block (the blocks of a full batch of two are 4 or 8
    steps), and it is not the first token of any other reply."""
    for k in range(2, len(first) - 2):
        tok = first[k]
        if tok not in first[:k] and all(tok != o[0] for o in rest):
            return k, tok
    raise AssertionError("no token of the reply can stand for the EOS")


def test_eos_mid_block_ends_the_reply_while_others_wait(tiny):
    """What dispatch cannot know keeps the drained path: a reply that meets
    its EOS in the middle of a block ends AT the EOS (the rest of the block
    is dropped), its slot is swept and the waiting requests get it; their
    replies are those of the same requests served alone, cut at the same
    token."""
    import asyncio

    lens = [(6, 24), (9, 20), (4, 18), (12, 16), (5, 22)]
    eng, vocab = _family_engine("llama")
    prompts = _prompts(vocab, [n for n, _ in lens], seed=1)

    async def serve(eng, together: bool):
        await eng.start()
        if together:
            outs = await asyncio.wait_for(asyncio.gather(*(
                eng.generate(p, max_tokens=m)
                for p, (_, m) in zip(prompts, lens))), timeout=600)
        else:
            outs = [await eng.generate(p, max_tokens=m)
                    for p, (_, m) in zip(prompts, lens)]
        given_back = _all_given_back(eng)
        await eng.stop()
        return outs, given_back

    alone, _ = _run(serve(eng, False))
    k, eos = _eos_case(alone[0], alone[1:])

    def cut(out):
        return out[:out.index(eos) + 1] if eos in out else out

    eng, _ = _family_engine("llama", eos_id=eos)
    outs, given_back = _run(serve(eng, True))
    assert outs[0] == alone[0][:k + 1] and outs[0][-1] == eos
    assert outs == [cut(o) for o in alone]
    assert given_back


@pytest.mark.parametrize("when", ["in_its_slot", "after_its_slot_was_handed_on"])
def test_cancel_mid_block_ends_the_stream_while_others_wait(tiny, when):
    """A user's cancel, like an EOS, is found by the host: the request's
    stream ends, its slot and pages come back, and the others' replies are
    whole — whether the cancel finds the request still in its slot (the
    drained path) or after its end was scheduled and the slot handed on to a
    waiting request (no sweep meets it then: emission closes the stream)."""
    import asyncio

    lens = [(6, 40 if when == "in_its_slot" else 9), (9, 20), (4, 18), (12, 16)]
    eng, vocab = _family_engine("llama")
    prompts = _prompts(vocab, [n for n, _ in lens], seed=2)

    async def go():
        await eng.start()
        rids = [eng.submit(p, max_tokens=m) for p, (_, m) in zip(prompts, lens)]
        victim = eng._reqs[rids[0]]
        if when != "in_its_slot":
            sweep = eng._sweep

            def sweep_then_cancel(scheduled=False):
                sweep(scheduled)
                if victim.planned and victim.slot < 0 and not victim.finished:
                    eng.cancel(rids[0])  # handed on, tokens still in flight

            eng._sweep = sweep_then_cancel

        async def collect(rid):
            out = []
            async for tok in eng.stream(rid):
                out.append(tok)
                if when == "in_its_slot" and rid == rids[0] and len(out) == 3:
                    eng.cancel(rid)
            return out

        outs = await asyncio.wait_for(
            asyncio.gather(*(collect(r) for r in rids)), timeout=600)
        for _ in range(200):  # the sweep behind the last emission
            if _all_given_back(eng):
                break
            await asyncio.sleep(0.01)
        given_back = _all_given_back(eng)
        alone = [await eng.generate(p, max_tokens=m)
                 for p, (_, m) in zip(prompts, lens)]
        await eng.stop()
        return outs, alone, given_back, victim

    outs, alone, given_back, victim = _run(go())
    assert victim.cancelled and not victim.finished
    assert len(outs[0]) < lens[0][1] and outs[0] == alone[0][:len(outs[0])]
    assert outs[1:] == alone[1:]
    assert given_back


@pytest.mark.parametrize("n,max_tokens,blocks", [
    (1, 1, []), (1, 1 + 4, [4]), (1, 1 + 8 + 16 + 32, [8, 16, 32]),
    (4, 1 + 64, [64])], ids=["one_token", "four", "ramp", "half_full"])
def test_a_lone_request_decodes_as_before_and_runs_on(tiny, n, max_tokens,
                                                      blocks):
    """What the benchmark's warm-up and reference check need of the loop
    (``benchmarks/lib/replica*.py``, not this PR's to edit), with nobody
    waiting: a lone ``1 + 4`` takes the 4-bucket, a lone ``1 + 8 + 16 + 32``
    ramps 8, 16, 32, half the slots at ``1 + 64`` take the 64-bucket, and
    after its last scheduled token — a request of ONE token included — the
    loop runs on, one step a block (the program ``(DECODE, B, 1)`` that every
    ``warm()`` wants), at least once and never past the request's pages, until
    that token is on the host: ``served(1)`` reads the tokens of those steps
    through ``_emit_block``, assigned on the instance, and a reply with no
    such step fails its ``ran[:len(ran_1) - 1]``."""
    import asyncio

    eng, vocab = _family_engine("llama", max_batch=8,
                                block_buckets=(4, 8, 16, 32, 64))
    prompt = _prompts(vocab, [5], seed=3)[0]
    taken, emit = [], eng._emit_block

    def tap(entry):
        K, toks, snapshot = entry
        taken.append((K, np.asarray(toks).shape[0],
                      sum(r is not None for r in snapshot)))
        emit(entry)

    eng._emit_block = tap

    async def go():
        await eng.start()
        outs = await asyncio.gather(*(
            eng.generate(prompt, max_tokens=max_tokens) for _ in range(n)))
        while any(r is not None for r in eng.slot_req):
            await asyncio.sleep(0.01)
        await eng.stop()
        return outs

    outs = _run(go())
    assert all(len(o) == max_tokens for o in outs) and len(set(map(tuple, outs))) == 1
    assert all(K == rows and live == n for K, rows, live in taken)
    ks = [K for K, _, _ in taken]
    assert ks[:len(blocks)] == blocks
    run_on = ks[len(blocks):]
    assert run_on and set(run_on) == {1}, ks
    assert len(prompt) + max_tokens - 1 + len(run_on) <= eng.PS * -(
        -(len(prompt) + max_tokens) // eng.PS), ks

"""Disaggregated LLM serving tests: KV-page plane round trips, prefix
cache radix/pinning/eviction semantics, disagg-vs-aggregated decode
parity, EngineFull -> backpressure mapping, prefix-affinity routing, and
the seeded decode-kill chaos plan (every in-flight request completes
with bounded duplicate prefill work)."""

import asyncio
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.llm.disagg.kv_plane import (
    KVPageEntry,
    KVPageManifest,
    adopt_pages,
    manifest_nbytes,
    ship_pages,
)
from ray_tpu.llm.disagg.prefix_cache import PrefixCache, prefix_hint
from ray_tpu.models.llama import LlamaConfig, llama_init

HERE = os.path.dirname(os.path.abspath(__file__))
KILL_PLAN = os.path.join(HERE, "plans", "llm_decode_kill.json")

PS = 8  # page size used throughout


def _tiny_cfg():
    return LlamaConfig(vocab_size=512, d_model=128, n_layers=2, n_heads=4,
                       n_kv_heads=4, d_ff=256, max_seq_len=512,
                       dtype="float32")


@pytest.fixture(scope="module")
def rt():
    ray_tpu.init(num_cpus=8)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def tiny():
    import jax

    cfg = _tiny_cfg()
    return cfg, llama_init(jax.random.PRNGKey(0), cfg)


# --------------------------------------------------------------- prefix hint
def test_prefix_hint_stability():
    toks = list(range(1, 40))
    h = prefix_hint(toks, page_size=16, n_pages=1)
    assert h and h == prefix_hint(toks, page_size=16, n_pages=1)
    # only the first full page matters: a divergent suffix shares the hint
    assert h == prefix_hint(toks[:16] + [999], page_size=16, n_pages=1)
    # a divergent first page does not
    assert h != prefix_hint([7] + toks[1:], page_size=16, n_pages=1)
    # prompts too short for one full page are uncacheable: no hint
    assert prefix_hint(toks[:15], page_size=16) == ""


def test_routing_hint_rendezvous_choice():
    """Same hint -> same replica across callers; exclusion falls back
    deterministically to the next-highest-weight replica."""
    from ray_tpu.serve.handle import _Router

    r = _Router.__new__(_Router)
    import threading

    r.lock = threading.Lock()
    r.replicas = [{"replica_id": f"rep-{i}", "actor_name": f"a{i}"}
                  for i in range(4)]
    r.inflight = {}
    r.remote_ongoing = {}
    r.inflight_at_probe = {}
    r.models = {}
    picks = {r._choose(hint="abc")["replica_id"] for _ in range(8)}
    assert len(picks) == 1  # rendezvous: deterministic, caller-independent
    (primary,) = picks
    # different hints spread over the replica set
    spread = {r._choose(hint=f"h{i}")["replica_id"] for i in range(32)}
    assert len(spread) > 1
    # excluding the primary falls to ONE deterministic runner-up
    ex = {primary}
    second = {r._choose(hint="abc", exclude=ex)["replica_id"]
              for _ in range(8)}
    assert len(second) == 1 and second != picks


def test_handle_options_carry_routing_hint():
    from ray_tpu.serve.handle import DeploymentHandle

    h = DeploymentHandle("d", "app", multiplexed_model_id="m1")
    h2 = h.options(routing_hint="abc")
    assert h2.routing_hint == "abc"
    assert h2.multiplexed_model_id == "m1"  # options() merges, not resets
    import pickle

    h3 = pickle.loads(pickle.dumps(h2))
    assert h3.routing_hint == "abc" and h3.multiplexed_model_id == "m1"


# -------------------------------------------------------------- prefix cache
def _fake_manifest(tokens, nbytes_per_page=100):
    pages = [KVPageEntry(refs={}, nbytes=nbytes_per_page)
             for _ in range(len(tokens) // PS)]
    return KVPageManifest(token_ids=tuple(tokens), page_size=PS,
                          kv_dtype="native", pages=pages)


def test_cache_hit_partial_miss():
    c = PrefixCache(PS, capacity_bytes=1 << 20)
    base = list(range(100, 100 + 3 * PS))
    c.insert(_fake_manifest(base))
    # full hit: every full page of the lookup is cached
    m = c.lookup(base)
    assert m is not None and m.n_pages == 3 and m.token_ids == tuple(base)
    c.release(m)
    # partial hit: shared first 2 pages, divergent third
    div = base[:2 * PS] + [7] * PS
    m2 = c.lookup(div)
    assert m2 is not None and m2.n_pages == 2
    assert m2.token_ids == tuple(base[:2 * PS])
    c.release(m2)
    # miss: divergent first page
    assert c.lookup([9] * (3 * PS)) is None
    s = c.stats()
    assert s["hits"] == 2 and s["full_hits"] == 1 and s["misses"] == 1
    assert 0 < s["hit_rate"] < 1
    # max_tokens caps the walk below the prompt length
    m3 = c.lookup(base, max_tokens=len(base) - 1)
    assert m3.n_pages == 2
    c.release(m3)


def test_cache_lru_eviction_prefers_leaves():
    c = PrefixCache(PS, capacity_bytes=350)  # 3 pages of 100 fit, 4 don't
    a = list(range(0, 2 * PS))          # shared interior path
    c.insert(_fake_manifest(a + list(range(500, 500 + PS))))   # leaf 1
    time.sleep(0)
    c.insert(_fake_manifest(a + list(range(600, 600 + PS))))   # leaf 2
    # 4 cached pages exceed capacity: the insert's pressure sweep dropped
    # the LRU leaf (leaf 1), never an interior page
    s = c.stats()
    assert s["evictions"] == 1 and s["pages"] == 3
    assert c.lookup(a + list(range(600, 600 + PS))).n_pages == 3
    assert c.lookup(a + list(range(500, 500 + PS))).n_pages == 2  # interior


def test_cache_pinned_never_evicted():
    c = PrefixCache(PS, capacity_bytes=1 << 20)
    toks = list(range(0, 2 * PS))
    c.insert(_fake_manifest(toks))
    pinned = c.lookup(toks)  # pins both nodes
    c.capacity_bytes = 0     # brutal arena pressure
    c.insert(_fake_manifest([9] * PS))  # triggers eviction sweep
    # the unpinned insert is evictable; the pinned path is not
    assert c.lookup(toks, max_tokens=len(toks)) is not None
    c.release(pinned)
    c.release(c.lookup(toks))
    # after release the pressure sweep may finally reclaim everything
    c.insert(_fake_manifest([11] * PS))
    assert c.stats()["bytes"] <= 300


def test_cache_invalidate_respects_pins():
    c = PrefixCache(PS, capacity_bytes=1 << 20)
    toks = list(range(0, 2 * PS))
    c.insert(_fake_manifest(toks))
    pinned = c.lookup(toks)
    assert c.invalidate(toks) == 0  # pinned: survives
    c.release(pinned)
    assert c.invalidate(toks) == 2
    assert c.lookup(toks) is None


def test_cache_eviction_frees_shm_bytes(rt):
    """Evicting a cached page drops its refs and the owner frees the
    sealed shm copy — eviction IS arena memory coming back."""
    from ray_tpu.core import api

    core = api.get_core()
    page = np.arange(4096, dtype=np.float32)

    def shm_bytes():
        st = core.store.stats()
        return st.get("bytes_in_use", st.get("peak", 0))

    c = PrefixCache(PS, capacity_bytes=1 << 30)
    toks = list(range(0, 2 * PS))
    refs = {i: core.put_value(page.copy(), prefer_shm=True)
            for i in range(2)}
    m = KVPageManifest(
        token_ids=tuple(toks), page_size=PS, kv_dtype="native",
        pages=[KVPageEntry(refs={"k": refs[i]}, nbytes=page.nbytes)
               for i in range(2)])
    c.insert(m)
    del m, refs  # the cache's entries hold the only remaining handles
    before = shm_bytes()
    c.capacity_bytes = 0
    c.insert(_fake_manifest([99] * PS, nbytes_per_page=0))  # pressure sweep
    assert c.stats()["evicted_bytes"] >= 2 * page.nbytes
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if shm_bytes() <= before - 2 * page.nbytes:
            break
        time.sleep(0.1)
    assert shm_bytes() <= before - 2 * page.nbytes, (
        f"shm not reclaimed: before={before} now={shm_bytes()}")


# ------------------------------------------------------------- KV-page plane
def test_ship_adopt_roundtrip(rt):
    """Pages sliced from a pool, sealed to shm, and adopted back are
    byte-identical, and the ledger counts payload bytes off-driver."""
    import jax.numpy as jnp

    from ray_tpu.llm import llama as _programs
    from ray_tpu.llm.disagg import telemetry

    cfg = _tiny_cfg()
    kpool, vpool = _programs.make_kv_pools(cfg, PS, 16, None)
    rng = np.random.default_rng(0)
    kpool = jnp.asarray(rng.normal(size=kpool.shape), kpool.dtype)
    vpool = jnp.asarray(rng.normal(size=vpool.shape), vpool.dtype)
    toks = list(range(1, 2 * PS + 1))
    before = telemetry.counters()
    m = ship_pages(kpool, vpool, [3, 5], toks, page_size=PS)
    assert m.n_pages == 2 and m.n_tokens == 2 * PS and m.full_pages() == 2
    assert m.nbytes > 0
    k_stack, v_stack = adopt_pages(m)
    np.testing.assert_array_equal(k_stack,
                                  np.asarray(kpool[:, jnp.asarray([3, 5])]))
    np.testing.assert_array_equal(v_stack,
                                  np.asarray(vpool[:, jnp.asarray([3, 5])]))
    after = telemetry.counters()
    moved = after["kv_array_bytes"] - before["kv_array_bytes"]
    driver = after["kv_driver_bytes"] - before["kv_driver_bytes"]
    assert moved >= 2 * m.nbytes  # ship + adopt both counted
    assert 0 < driver < moved / 10  # manifests are metadata, not payload
    assert driver >= manifest_nbytes(m)
    # prefix() shares entries with the parent (the cache-insert view)
    p = m.prefix(1)
    assert p.n_pages == 1 and p.pages[0] is m.pages[0]
    assert p.token_ids == tuple(toks[:PS])


def test_manifest_pickle_rides_borrower_protocol(rt):
    import pickle

    from ray_tpu.core import api

    core = api.get_core()
    ref = core.put_value(np.arange(64, dtype=np.float32), prefer_shm=True)
    m = KVPageManifest(token_ids=tuple(range(PS)), page_size=PS,
                       kv_dtype="native",
                       pages=[KVPageEntry(refs={"k": ref}, nbytes=256)])
    m2 = pickle.loads(pickle.dumps(m))
    assert m2.token_ids == m.token_ids and m2.pages[0].nbytes == 256
    np.testing.assert_array_equal(ray_tpu.get(m2.pages[0].refs["k"]),
                                  np.arange(64, dtype=np.float32))


# ---------------------------------------------------- disagg decode parity
def _aggregated_tokens(cfg, params, prompt, max_tokens):
    """Reference: the aggregated continuous-batching engine."""
    from ray_tpu.llm.engine import ContinuousBatchingEngine

    async def run():
        eng = ContinuousBatchingEngine(params, cfg, max_batch=2,
                                       page_size=PS, n_pages=64,
                                       max_seq_len=128)
        await eng.start()
        rid = eng.submit(prompt, max_tokens=max_tokens, temperature=0.0)
        out = [t async for t in eng.stream(rid)]
        await eng.stop()
        return out

    return asyncio.run(run())


def _disagg_tokens(cfg, params, prompt, max_tokens, *, via_cache=False):
    """The disaggregated path, in-process: PrefillWorker -> KV-page
    plane -> DecodeWorker. With via_cache, the prompt's first full pages
    travel as a cached prefix manifest + suffix prefill instead."""
    from ray_tpu.llm.disagg.pools import DecodeWorker, PrefillWorker

    async def run():
        pf = PrefillWorker(cfg, params, page_size=PS, n_pages=64,
                           wave_wait_s=0.001)
        if via_cache:
            full_m, _ = await pf.prefill(prompt)
            cache = PrefixCache(PS, capacity_bytes=1 << 30)
            cache.insert(full_m)
            prefix_m = cache.lookup(prompt, max_tokens=len(prompt) - 1)
            assert prefix_m is not None and prefix_m.n_pages >= 1
            sm, first = await pf.prefill(prompt[prefix_m.n_tokens:],
                                         prefix=prefix_m)
            manifest, extra = prefix_m, sm
        else:
            manifest, extra = None, None
            manifest, first = await pf.prefill(prompt)
        dw = DecodeWorker(cfg, params, max_batch=2, page_size=PS,
                          n_pages=64, max_seq_len=128)
        out = await dw.decode_adopted(prompt, manifest, extra, first,
                                      max_tokens=max_tokens,
                                      temperature=0.0)
        await dw.stop()
        return out

    return asyncio.run(run())


def test_disagg_matches_aggregated(rt, tiny):
    """Acceptance: prefill-elsewhere + adopt + decode produces the SAME
    tokens as the aggregated engine (greedy), full-prefill and
    cached-prefix legs both."""
    cfg, params = tiny
    prompt = list(range(1, 20))  # 19 tokens: 2 full pages + ragged tail
    want = _aggregated_tokens(cfg, params, prompt, 8)
    assert len(want) == 8
    got = _disagg_tokens(cfg, params, prompt, 8)
    assert got == want
    cached = _disagg_tokens(cfg, params, prompt, 8, via_cache=True)
    assert cached == want  # cache on == cache off, byte-identical


def test_prefill_wave_coalesces(rt, tiny):
    """Concurrent prefill calls share one padded wave dispatch."""
    from ray_tpu.llm.disagg.pools import PrefillWorker

    cfg, params = tiny

    async def run():
        pf = PrefillWorker(cfg, params, page_size=PS, n_pages=64,
                           wave_wait_s=0.05)
        outs = await asyncio.gather(*(
            pf.prefill(list(range(1, 1 + PS * 2))) for _ in range(4)))
        return pf.waves, outs

    waves, outs = asyncio.run(run())
    assert waves == 1
    firsts = {first for _, first in outs}
    assert len(firsts) == 1  # identical prompts, identical first token


# --------------------------------------------------------- backpressure map
def test_engine_full_becomes_backpressure(tiny):
    from ray_tpu.llm.engine import EngineFull
    from ray_tpu.llm.serving import LLMEngineServer
    from ray_tpu.serve.exceptions import BackPressureError

    srv = LLMEngineServer.__new__(LLMEngineServer)
    srv.default_max_tokens = 4

    class FullEngine:
        waiting = [None] * 3

        def submit(self, *a, **kw):
            raise EngineFull("queue at capacity")

    srv.engine = FullEngine()
    with pytest.raises(BackPressureError) as ei:
        srv._submit({"prompt_tokens": [1, 2, 3]})
    assert ei.value.retry_after_s > 0
    # typed passthrough: the PR 6 router sees the class, not a TaskError
    assert getattr(BackPressureError, "_rt_error_passthrough", False)


def test_scheduler_backpressure_before_prefill(tiny):
    """Admission control refuses BEFORE spending prefill work when the
    decode pools lack page headroom."""
    from ray_tpu.llm.disagg.scheduler import DisaggLLMServer
    from ray_tpu.serve.exceptions import BackPressureError

    s = DisaggLLMServer.__new__(DisaggLLMServer)
    s.PS = PS
    s.default_max_tokens = 4
    s.max_attempts = 2
    s.decode_pool = [object(), object()]
    s._capacity = 7
    s._est_pages = [6, 7]  # nearly full
    s._est_tokens = [0, 0]
    s._signals = [None, None]
    s._foreign = {}
    s._share_group = None
    s._sig_task = None
    s._last_req_ts = 0.0
    s.signal_refresh_s = 0.2
    s._pool_tmpls = {}
    import itertools

    s._dw_rr = itertools.count()
    s.backpressured = 0
    s.requests = 0
    from ray_tpu.llm.disagg.prefix_cache import PrefixCache as PC

    s.cache = PC(PS)
    with pytest.raises(BackPressureError) as ei:
        asyncio.run(s({"prompt_tokens": list(range(40)), "max_tokens": 16}))
    assert ei.value.retry_after_s > 0
    assert s.backpressured == 1


# -------------------------------------------------- foreign-loop ref await
def test_await_ref_from_driver_loop(rt):
    """Regression: awaiting an actor-call ObjectRef from an asyncio loop
    that is NOT the core loop (driver code, scheduler pools) must bridge
    to the core loop instead of waiting on a loop nothing wakes."""

    @ray_tpu.remote
    class Echo:
        async def hi(self, x):
            return x + 1

    a = Echo.options(max_concurrency=4).remote()

    async def main():
        one = await a.hi.remote(1)
        many = await asyncio.gather(*(a.hi.remote(i) for i in range(4)))
        return one, many

    one, many = asyncio.run(main())
    assert one == 2 and many == [1, 2, 3, 4]


def test_store_reads_survive_default_executor_saturation(rt):
    """Regression: the core's blocking shm-store reads must run on a
    PRIVATE pool. Actor code parks blocking api.get calls on the loop's
    default executor (run_in_executor(None, ...) — the decode workers'
    adoption fetch does exactly this), and when those occupied every
    default thread the store read that would unblock them queued behind
    them forever: ≥6 concurrent adoptions per worker deadlocked."""
    from ray_tpu.core import api

    core = api.get_core()
    want = np.arange(1 << 14, dtype=np.float32)
    ref = core.put_value(want.copy(), prefer_shm=True)

    async def saturate():
        for _ in range(16):
            core.loop.run_in_executor(None, time.sleep, 4.0)

    asyncio.run_coroutine_threadsafe(saturate(), core.loop).result(5)
    t0 = time.monotonic()
    got = ray_tpu.get(ref)
    elapsed = time.monotonic() - t0
    np.testing.assert_array_equal(got, want)
    assert elapsed < 2.0, (
        f"shm get took {elapsed:.1f}s behind a saturated default "
        "executor — store reads are sharing the user pool again")


# ------------------------------------------------------------ telemetry
def test_disagg_stage_telemetry(rt):
    from ray_tpu.llm.disagg import telemetry
    from ray_tpu.utils import recorder

    for sid, name in ((recorder.PREFILL_QUEUE, "prefill_queue"),
                      (recorder.KV_SHIP, "kv_ship"),
                      (recorder.DECODE_QUEUE, "decode_queue")):
        assert recorder.STAGE_NAMES[sid] == name
    telemetry.record(telemetry.TTFT, 1_000_000)
    assert telemetry.stage_window(telemetry.TTFT)
    # the core's 1Hz latency flush may race us for the snapshot; what
    # must hold is that a snapshot (ours or a fresh record's) carries the
    # stage window and that a CONFIRMED publish parks the source
    snap = telemetry.snapshot_if_fresh()
    if snap is not None:
        assert "ttft" in snap["stages"]
        telemetry.mark_published()
        assert telemetry.snapshot_if_fresh() is None  # nothing new since


# ------------------------------------------------------- seeded chaos plan
_CHAOS_CHILD = r"""
import asyncio, json
import ray_tpu
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.llm.disagg.scheduler import DisaggLLMServer

cfg = LlamaConfig(vocab_size=512, d_model=128, n_heads=4, n_layers=2,
                  n_kv_heads=4, d_ff=256, max_seq_len=512, dtype="float32")
SHARED = list(range(1, 17))  # two full pages at page_size 8

async def main():
    s = DisaggLLMServer(cfg, n_prefill=1, n_decode=2, max_batch=4,
                        page_size=8, n_pages=64, max_seq_len=128)
    ok = err = 0
    for wave in range(3):
        reqs = [SHARED + [100 + wave, 200 + j] for j in range(4)]
        res = await asyncio.gather(
            *(s({"prompt_tokens": r, "max_tokens": 6}) for r in reqs),
            return_exceptions=True)
        for r in res:
            if isinstance(r, Exception):
                err += 1
                print("ERR", type(r).__name__, r, flush=True)
            else:
                ok += 1
    st = await s.stats()
    await s.shutdown()
    print("RES=" + json.dumps({
        "ok": ok, "err": err,
        "duplicate_prefills": st["duplicate_prefills"],
        "hit_rate": st["prefix_cache"]["hit_rate"],
        "kv_driver_bytes": st["kv_plane"]["kv_driver_bytes"],
        "kv_array_bytes": st["kv_plane"]["kv_array_bytes"]}), flush=True)

ray_tpu.init(num_cpus=8)
asyncio.run(main())
ray_tpu.shutdown()
"""


def test_decode_kill_plan_completes_every_request(tmp_path):
    """Acceptance: the checked-in seeded plan SIGKILLs a decode actor
    mid-adoption (and drops one manifest's pages); every in-flight
    request still completes — re-adoption on a live worker or re-prefill
    from the cached prefix — with error rate 0 and bounded duplicate
    prefill work."""
    log_dir = str(tmp_path / "chaos")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "RT_CHAOS_ENABLED": "1",
           "RT_CHAOS_PLAN": KILL_PLAN, "RT_CHAOS_LOG_DIR": log_dir}
    proc = subprocess.run([sys.executable, "-c", _CHAOS_CHILD], env=env,
                          capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RES=")][0]
    res = json.loads(line[4:])
    assert res["ok"] == 12 and res["err"] == 0, res
    # bounded duplicate work: at most one re-prefill per injected fault
    assert res["duplicate_prefills"] <= 2, res
    # shared-prefix workload: the cache carried most requests
    assert res["hit_rate"] > 0.5, res
    # zero-copy proof under chaos: pages moved off-driver
    assert res["kv_array_bytes"] > 50 * res["kv_driver_bytes"], res
    # the plan must actually have struck, or this proves nothing
    from ray_tpu.devtools.chaos.cli import read_events

    events = read_events(log_dir)
    kills = [e for e in events if e["action"] == "kill"
             and e["point"] == "llm.kv_ship"]
    assert kills and kills[0]["ctx"]["role"] == "decode"

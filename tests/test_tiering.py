"""Memory tiering tests (ISSUE 18): spill/restore as a storage tier.

Covers the tentpole surfaces end to end against the in-process cluster:
byte-identical spill->restore round trips for KV pages and shards
(tier legs stamped and promoted), the spilled-radix-hit path
(token-identical to a shm hit, measurably cheaper than re-prefill),
pull-admission back-pressure (typed refusal with a retry hint), the
pinned-pages-never-spill invariant, the freed-while-spilling orphan
handshake, spill-failure backoff accounting, the telemetry/state
surfaces, and the checked-in ``tests/plans/spill_churn.json`` chaos plan
(decode death mid-churn completes every request with ZERO duplicate
prefills — recovery restores from tier-1 instead of recomputing).
"""

import asyncio
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.core import tiering
from ray_tpu.llm.disagg.kv_plane import adopt_pages, ship_pages
from ray_tpu.llm.disagg.prefix_cache import PrefixCache
from ray_tpu.models.llama import LlamaConfig

HERE = os.path.dirname(os.path.abspath(__file__))
CHURN_PLAN = os.path.join(HERE, "plans", "spill_churn.json")

PS = 8


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
    from ray_tpu.models.llama import llama_init

    return cfg, llama_init(jax.random.PRNGKey(0), cfg)


def _core():
    from ray_tpu.core import api

    return api.get_core()


def _raylet():
    from ray_tpu.core import api

    return api._owned_cluster.raylets[0]


# ------------------------------------------------------ spill round trips
def test_kv_page_spill_restore_byte_identical(rt):
    """KV pages spilled to tier-1 restore byte-identically through the
    batched adopt path, with the manifest tier legs stamped on spill and
    promoted back on restore."""
    import jax.numpy as jnp

    from ray_tpu.llm import llama as _programs
    from ray_tpu.llm.disagg import telemetry

    cfg = _tiny_cfg()
    kpool, vpool = _programs.make_kv_pools(cfg, PS, 16, None)
    rng = np.random.default_rng(7)
    kpool = jnp.asarray(rng.normal(size=kpool.shape), kpool.dtype)
    vpool = jnp.asarray(rng.normal(size=vpool.shape), vpool.dtype)
    toks = list(range(1, 2 * PS + 1))
    m = ship_pages(kpool, vpool, [3, 5], toks, page_size=PS)
    core = _core()
    oids = [ref.id for p in m.pages for ref in p.refs.values()]
    res = core.spill_objects(oids)
    assert res and all(v["ok"] for v in res.values()), res
    # the kv staging tracker's sink stamped every entry's tier leg
    assert all(p.tier == tiering.TIER_DISK and p.spill_path
               for p in m.pages)
    assert not any(core.store.contains(o) for o in oids)
    before = telemetry.counters()
    k_stack, v_stack = adopt_pages(m)
    np.testing.assert_array_equal(
        k_stack, np.asarray(kpool[:, jnp.asarray([3, 5])]))
    np.testing.assert_array_equal(
        v_stack, np.asarray(vpool[:, jnp.asarray([3, 5])]))
    # restore promoted the tier legs back to shm and hit the disk ledger
    assert all(p.tier == tiering.TIER_SHM for p in m.pages)
    after = telemetry.counters()
    assert after["pages_restored"] > before.get("pages_restored", 0)
    assert after["kv_disk_bytes"] > before.get("kv_disk_bytes", 0)


def test_shard_spill_restore_byte_identical(rt):
    """put_sharded shards survive a spill->get_sharded cycle
    byte-identically; ShardEntry tier legs stamp and promote."""
    jax = pytest.importorskip("jax")
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.parallel.mesh import MeshSpec

    mesh = MeshSpec(dp=2, tp=2, sp=2).build()
    arr = np.arange(16 * 8, dtype=np.float32).reshape(16, 8)
    garr = jax.device_put(arr, NamedSharding(mesh, P("dp", "tp")))
    sref = rt.put_sharded(garr)
    core = _core()
    oids = [s.ref.id for s in sref.manifest.shards]
    res = core.spill_objects(oids)
    assert res and all(v["ok"] for v in res.values()), res
    assert all(s.tier == tiering.TIER_DISK and s.spill_path
               for s in sref.manifest.shards)
    assert not any(core.store.contains(o) for o in oids)
    out = rt.get_sharded(sref, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(out), arr)
    assert all(s.tier == tiering.TIER_SHM for s in sref.manifest.shards)


# ------------------------------------------------------- spilled radix hit
def test_spilled_radix_hit_token_identical_and_cheaper(rt, tiny):
    """A prefix-cache hit whose pages live on tier-1 produces the SAME
    tokens as a shm hit — one sequential disk restore, not a re-prefill
    — and the restore leg costs less wall-clock than re-prefilling."""
    from ray_tpu.llm.disagg import telemetry
    from ray_tpu.llm.disagg.pools import DecodeWorker, PrefillWorker

    cfg, params = tiny
    prompt = list(range(1, 1 + 3 * PS))  # 3 full pages

    async def run():
        pf = PrefillWorker(cfg, params, page_size=PS, n_pages=64,
                           wave_wait_s=0.001)
        dw = DecodeWorker(cfg, params, max_batch=2, page_size=PS,
                          n_pages=64, max_seq_len=128)
        full_m, _ = await pf.prefill(prompt)
        cache = PrefixCache(PS, capacity_bytes=1 << 30, spill=True,
                            spill_cold_after_s=0.0)
        cache.insert(full_m)

        async def one_request():
            pm = cache.lookup(prompt, max_tokens=len(prompt) - 1)
            assert pm is not None and pm.n_pages == 2
            sm, first = await pf.prefill(prompt[pm.n_tokens:], prefix=pm)
            out = await dw.decode_adopted(prompt, pm, sm, first,
                                          max_tokens=8, temperature=0.0)
            cache.release(pm)
            return out

        shm_out = await one_request()          # baseline: shm hit
        assert cache.stats()["tier1_hits"] == 0
        spilled = cache.spill_all()            # force the pages cold
        assert spilled >= 1
        t1_out = await one_request()           # tier-1 hit
        st = cache.stats()
        assert st["tier1_hits"] >= 1 and st["spills"] >= spilled
        assert telemetry.counters().get("pages_restored", 0) >= 1

        # cost: restoring the cached pages beats re-running the prefill
        cache.spill_all()
        pm = cache.lookup(prompt, max_tokens=len(prompt) - 1)
        t0 = time.perf_counter()
        adopt_pages(pm, role="prefill")
        t_restore = time.perf_counter() - t0
        cache.release(pm)
        t0 = time.perf_counter()
        await pf.prefill(prompt)               # warm: jit long compiled
        t_prefill = time.perf_counter() - t0
        await dw.stop()
        return shm_out, t1_out, t_restore, t_prefill

    shm_out, t1_out, t_restore, t_prefill = asyncio.run(run())
    assert t1_out == shm_out  # token-identical across tiers
    assert t_restore < t_prefill, (
        f"tier-1 restore ({t_restore * 1e3:.2f}ms) should beat "
        f"re-prefill ({t_prefill * 1e3:.2f}ms)")


# -------------------------------------------------------- pull admission
def test_pull_admission_window_fifo_and_shed():
    """Unit: the PullAdmission window byte-bounds concurrency, parks
    FIFO, sheds at the deadline with a retry hint, and admits an
    oversized single object only when alone."""
    from ray_tpu.config import get_config
    from ray_tpu.core.raylet import PullAdmission, PullBackPressure

    class _Store:
        capacity = 1 << 30
        bytes_in_use = 0

    class _BG:
        def __init__(self):
            self.tasks = []

        def spawn(self, coro):
            self.tasks.append(asyncio.get_running_loop().create_task(coro))

    class _Raylet:
        cfg = get_config()
        store = _Store()

    async def run():
        r = _Raylet()
        r._bg = _BG()
        pa = PullAdmission(r)
        pa.max_bytes = 100
        await pa.acquire(80)  # fits
        assert pa.in_flight == 80
        now = time.monotonic()
        shed = pa.acquire(80, deadline=now + 0.3)     # parks, then sheds
        behind = pa.acquire(10, deadline=now + 10.0)  # FIFO: parked behind
        with pytest.raises(PullBackPressure) as ei:
            await asyncio.wait_for(shed, timeout=5)
        assert ei.value.retry_after_s > 0
        await asyncio.wait_for(behind, timeout=5)  # head gone: admits
        assert pa.shed == 1 and pa.in_flight == 90
        pa.release(80)
        pa.release(10)
        assert pa.in_flight == 0
        # oversized single object: admits when alone, never when not
        await pa.acquire(10_000)
        assert pa.in_flight == 10_000
        pa.release(10_000)
        for t in r._bg.tasks:
            t.cancel()

    asyncio.run(run())


def test_adoption_shed_surfaces_backpressure(rt):
    """Functional: a saturated admission window sheds a batched KV
    adoption at its deadline and the plane surfaces the serve layer's
    typed BackPressureError with retry_after_s — then succeeds once the
    window drains."""
    import jax.numpy as jnp

    from ray_tpu.llm import llama as _programs
    from ray_tpu.serve.exceptions import BackPressureError

    cfg = _tiny_cfg()
    kpool, vpool = _programs.make_kv_pools(cfg, PS, 16, None)
    rng = np.random.default_rng(3)
    kpool = jnp.asarray(rng.normal(size=kpool.shape), kpool.dtype)
    vpool = jnp.asarray(rng.normal(size=vpool.shape), vpool.dtype)
    m = ship_pages(kpool, vpool, [1, 2], list(range(1, 2 * PS + 1)),
                   page_size=PS)
    core = _core()
    oids = [ref.id for p in m.pages for ref in p.refs.values()]
    res = core.spill_objects(oids)
    assert all(v["ok"] for v in res.values()), res
    raylet = _raylet()
    pa = raylet._pull_admission
    old_max, old_timeout = pa.max_bytes, core.cfg.pull_admission_timeout_s
    pa.max_bytes = 1
    pa.in_flight = 1  # saturated: nothing (even oversized) admits
    core.cfg.pull_admission_timeout_s = 0.3
    try:
        with pytest.raises(BackPressureError) as ei:
            adopt_pages(m)
        assert ei.value.retry_after_s > 0
    finally:
        pa.max_bytes = old_max
        pa.in_flight = 0
        core.cfg.pull_admission_timeout_s = old_timeout
    k_stack, _v = adopt_pages(m)  # window drained: restore succeeds
    np.testing.assert_array_equal(
        k_stack, np.asarray(kpool[:, jnp.asarray([1, 2])]))
    assert pa.stats()["shed"] >= 1


# ---------------------------------------------------- pinned never spill
def test_pinned_pages_never_spill(rt):
    """A pinned cache path (mid-adoption) is invisible to the spill
    candidate provider and survives spill_all untouched; releasing the
    pin makes it spillable."""
    core = _core()
    from ray_tpu.llm.disagg.kv_plane import KVPageEntry, KVPageManifest

    page = np.arange(2048, dtype=np.float32)
    pages = []
    for i in range(2):
        refs = {"k": core.put_value(page.copy(), prefer_shm=True),
                "v": core.put_value(page.copy(), prefer_shm=True)}
        pages.append(KVPageEntry(refs=refs, nbytes=2 * page.nbytes))
    toks = list(range(0, 2 * PS))
    m = KVPageManifest(token_ids=tuple(toks), page_size=PS,
                       kv_dtype="native", pages=pages)
    c = PrefixCache(PS, capacity_bytes=1 << 30, spill=True,
                    spill_cold_after_s=0.0)
    c.insert(m)
    pinned = c.lookup(toks)
    time.sleep(0.05)
    assert c._spill_candidates(1 << 30, 0.0) == []  # all pinned: nothing
    assert c.spill_all() == 0
    assert all(p.tier == tiering.TIER_SHM for p in m.pages)
    c.release(pinned)
    # frontier recedes leaf-upward: only the leaf (k,v) qualifies while
    # its parent still has a tier-0 child
    assert len(c._spill_candidates(1 << 30, 0.0)) == 2
    assert c.spill_all() == 2
    assert all(p.tier == tiering.TIER_DISK for p in m.pages)
    # and the bytes really left the arena, restorable on read
    oid = m.pages[0].refs["k"].id
    assert not core.store.contains(oid)
    np.testing.assert_array_equal(ray_tpu.get(m.pages[0].refs["k"]), page)


def test_arena_watermarks_track_spill_restore_cycle(rt):
    """The tiering arena watermarks (rollup plane, ISSUE 19) track peak
    bytes through a spill/restore pressure cycle: live bytes move from
    the shm arena to tier-1 on spill and back on a tier-1 hit, while the
    shm watermark's peak remembers the pre-spill high-water mark."""
    from ray_tpu.llm.disagg.kv_plane import KVPageEntry, KVPageManifest

    core = _core()
    page = np.arange(4096, dtype=np.float32)
    pages = []
    for _ in range(3):
        refs = {"k": core.put_value(page.copy(), prefer_shm=True),
                "v": core.put_value(page.copy(), prefer_shm=True)}
        pages.append(KVPageEntry(refs=refs, nbytes=2 * page.nbytes))
    toks = list(range(0, 3 * PS))
    m = KVPageManifest(token_ids=tuple(toks), page_size=PS,
                       kv_dtype="native", pages=pages)
    c = PrefixCache(PS, capacity_bytes=1 << 30, spill=True,
                    spill_cold_after_s=0.0)
    c.insert(m)
    st = tiering.sample_arenas()
    live0 = st["prefix_cache"]["bytes"]
    assert live0 == c.bytes > 0
    assert st["prefix_cache"]["capacity"] == c.capacity_bytes
    # pressure: push the whole radix tree to tier-1
    assert c.spill_all() >= 1
    st = tiering.sample_arenas()
    assert st["prefix_cache"]["bytes"] < live0
    assert st["prefix_cache_tier1"]["bytes"] > 0
    # the shm arena's watermark remembers the pre-spill high water
    wm = tiering.arena_watermark("prefix_cache")
    assert wm is not None and wm.peak >= live0
    assert st["prefix_cache"]["peak"] >= live0
    # restore: a tier-1 hit promotes the pages back into the shm arena
    pm = c.lookup(toks)
    assert pm is not None
    adopt_pages(pm, role="prefill")
    c.release(pm)
    st = tiering.sample_arenas()
    assert st["prefix_cache"]["bytes"] == c.bytes > 0
    assert tiering.arena_watermark("prefix_cache").live == c.bytes


# ------------------------------------------- freed-while-spilling orphan
def test_freed_while_spilling_leaves_no_orphan_file(rt):
    """Freeing an object while its spill write is in flight must not
    leak the spill file: the raylet's freed-while-spilling handshake
    drops it when the write lands."""
    from ray_tpu.devtools import chaos
    from ray_tpu.devtools.chaos import ChaosPlan

    core = _core()
    raylet = _raylet()
    ref = core.put_value(np.arange(1 << 16, dtype=np.uint8),
                         prefer_shm=True)
    oid = ref.id
    plan = ChaosPlan(seed=18, rules=[
        {"point": "store.spill", "match": {"phase": "write"},
         "action": "delay", "delay_ms": 800, "max_fires": 1}])
    chaos.enable(plan)
    try:
        t = threading.Thread(target=lambda: core.spill_objects([oid]))
        t.start()
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and oid not in raylet._spilling_now:
            time.sleep(0.005)
        assert oid in raylet._spilling_now, "spill never started"
        del ref  # owner free lands inside the widened spill window
        t.join(30)
    finally:
        chaos.disable()
    path = os.path.join(raylet.spill_dir, oid.hex())
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        if not os.path.exists(path) and oid not in raylet._spilled:
            break
        time.sleep(0.1)
    assert not os.path.exists(path), "orphan spill file leaked"
    assert oid not in raylet._spilled


# --------------------------------------------------- spill-failure backoff
def test_spill_failure_backoff_and_counter(rt):
    """Failed spills back off per-oid exponentially and surface in
    SharedObjectStore.stats(); a later success clears the backoff."""
    from ray_tpu.devtools import chaos
    from ray_tpu.devtools.chaos import ChaosPlan

    core = _core()
    raylet = _raylet()
    ref = core.put_value(np.arange(1 << 14, dtype=np.uint8),
                         prefer_shm=True)
    oid = ref.id
    # the counter lives on the raylet's store instance (the process that
    # runs the spill), not the client's view of the arena
    before = raylet.store.stats()["spill_failures"]
    plan = ChaosPlan(seed=4, rules=[
        {"point": "store.spill", "match": {"phase": "write"},
         "action": "error", "max_fires": 2}])
    chaos.enable(plan)
    try:
        res = core.spill_objects([oid])
        assert not res[oid.hex()]["ok"]
        assert raylet.store.stats()["spill_failures"] == before + 1
        assert raylet._spill_backoff_s(oid) == pytest.approx(0.5)
        res = core.spill_objects([oid])
        assert not res[oid.hex()]["ok"]
        assert raylet._spill_backoff_s(oid) == pytest.approx(1.0)  # 2^n
        assert raylet.store.stats()["spill_failures"] == before + 2
    finally:
        chaos.disable()
    res = core.spill_objects([oid])  # fault cleared: spill lands
    assert res[oid.hex()]["ok"]
    assert raylet._spill_backoff_s(oid) == 0.0  # success resets backoff
    np.testing.assert_array_equal(np.asarray(ray_tpu.get(ref)).ravel(),
                                  np.arange(1 << 14, dtype=np.uint8))


# ----------------------------------------------------- telemetry surfaces
def test_tiering_telemetry_and_state_surfaces(rt):
    """spill/restore ride the recorder/stage-window plumbing and
    state.list_tiering() exposes the panel the dashboard serves."""
    from ray_tpu import state
    from ray_tpu.llm.disagg import telemetry
    from ray_tpu.utils import recorder

    assert recorder.STAGE_NAMES[recorder.SPILL] == "spill"
    assert recorder.STAGE_NAMES[recorder.RESTORE] == "restore"
    telemetry.record(telemetry.SPILL, 1_000_000, 4096)
    telemetry.record(telemetry.RESTORE, 2_000_000, 4096)
    assert telemetry.stage_window(telemetry.SPILL)
    assert telemetry.stage_window(telemetry.RESTORE)
    out = state.list_tiering()
    assert set(out) == {"stages", "gauges"}
    # the spill counters published through the metrics flush eventually;
    # shape-only here (values covered by the round-trip tests)
    for name in out["gauges"]:
        assert name.startswith("rt_")


# ------------------------------------------------------- seeded chaos plan
_CHURN_CHILD = r"""
import asyncio, json
import ray_tpu
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.llm.disagg.scheduler import DisaggLLMServer
from ray_tpu.llm.disagg import telemetry

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
        # push the whole radix tree to tier-1 between waves: the next
        # wave's hits MUST restore from disk while the plan churns
        s.cache.spill_all()
    st = await s.stats()
    await s.shutdown()
    pc = st["prefix_cache"]
    print("RES=" + json.dumps({
        "ok": ok, "err": err,
        "duplicate_prefills": st["duplicate_prefills"],
        "decode_retries": st["decode_retries"],
        "hit_rate": pc["hit_rate"],
        "tier1_hits": pc["tier1_hits"],
        "spills": pc["spills"],
        "pages_restored": st["kv_plane"].get("pages_restored", 0),
        "kv_disk_bytes": st["kv_plane"].get("kv_disk_bytes", 0)}),
        flush=True)

ray_tpu.init(num_cpus=8)
asyncio.run(main())
ray_tpu.shutdown()
"""


def test_spill_churn_plan_zero_duplicate_prefills(tmp_path):
    """Acceptance: the checked-in seeded plan widens the mid-spill
    window and SIGKILLs a decode worker mid-adoption while every wave's
    pages sit on tier-1. Every request completes, recovery re-adopts
    through the restore path, and duplicate prefills stay at ZERO — the
    tier-1 copy makes re-prefill unnecessary."""
    log_dir = str(tmp_path / "chaos")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "RT_CHAOS_ENABLED": "1",
           "RT_CHAOS_PLAN": CHURN_PLAN, "RT_CHAOS_LOG_DIR": log_dir,
           "RT_PREFIX_CACHE_SPILL": "1", "RT_SPILL_COLD_AFTER_S": "0"}
    proc = subprocess.run([sys.executable, "-c", _CHURN_CHILD], env=env,
                          capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RES=")][0]
    res = json.loads(line[4:])
    assert res["ok"] == 12 and res["err"] == 0, res
    assert res["duplicate_prefills"] == 0, res      # the headline
    assert res["tier1_hits"] >= 1, res              # hits served off disk
    assert res["spills"] >= 1, res
    assert res["pages_restored"] >= 1, res
    # the plan must actually have struck, or this proves nothing
    from ray_tpu.devtools.chaos.cli import read_events

    events = read_events(log_dir)
    kills = [e for e in events if e["action"] == "kill"
             and e["point"] == "llm.kv_ship"]
    assert kills and kills[0]["ctx"]["role"] == "decode"
    delays = [e for e in events if e["action"] == "delay"
              and e["point"] == "store.spill"]
    assert delays, "spill-window delay never fired"

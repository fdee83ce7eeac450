"""OOM protection + GCS persistence/restart tests (ref test strategy:
python/ray/tests/test_memory_pressure.py, test_gcs_fault_tolerance.py)."""

import time

import pytest

import ray_tpu


# ------------------------------------------------------------- memory monitor
def test_memory_monitor_kills_newest_lease():
    from ray_tpu.core.memory_monitor import MemoryMonitor

    class FakeProc:
        def __init__(self):
            self.killed = False

        def poll(self):
            return None

        def kill(self):
            self.killed = True

        @property
        def pid(self):
            return 1234

    class FakeWorker:
        def __init__(self, actor_id=None):
            self.proc = FakeProc()
            self.actor_id = actor_id

    class FakeLease:
        def __init__(self, lease_id, actor_id=None):
            self.lease_id = lease_id
            self.worker = FakeWorker(actor_id)

    class FakeRaylet:
        # lease 4 is an ACTOR worker (newest), must be spared while plain
        # task workers exist
        leases = {1: FakeLease(1), 2: FakeLease(2), 3: FakeLease(3),
                  4: FakeLease(4, actor_id=b"actor")}

    mem = {"avail": 100, "total": 100}
    mon = MemoryMonitor(FakeRaylet, threshold=0.9, min_interval_s=0.0,
                        reader=lambda: (mem["avail"], mem["total"]))
    assert not mon.maybe_kill()  # plenty free
    mem["avail"] = 5  # 95% used
    assert mon.maybe_kill()
    # newest NON-ACTOR lease (3) is the victim; older work and the actor
    # worker (4) survive
    assert FakeRaylet.leases[3].worker.proc.killed
    assert not FakeRaylet.leases[1].worker.proc.killed
    assert not FakeRaylet.leases[4].worker.proc.killed
    assert mon.kills and mon.kills[0]["lease_id"] == 3


def test_oom_kill_retries_task():
    """E2e: the monitor kills a worker mid-task; the owner sees a worker
    crash and the retry succeeds once memory 'frees' (ref: OOM-killed
    tasks are retriable)."""
    ray_tpu.init(num_cpus=4)
    try:
        from ray_tpu.core.api import _owned_cluster

        raylet = _owned_cluster.raylets[0]
        from ray_tpu.core.memory_monitor import MemoryMonitor

        mem = {"avail": 100, "total": 100}
        raylet.memory_monitor = MemoryMonitor(
            raylet, threshold=0.9, min_interval_s=0.5,
            reader=lambda: (mem["avail"], mem["total"]),
        )

        @ray_tpu.remote(max_retries=3)
        def slowish(path):
            import os
            import time as _t

            first = not os.path.exists(path)
            if first:
                open(path, "w").close()
                _t.sleep(8.0)  # long enough for the monitor to strike
            return "done"

        import tempfile

        marker = tempfile.mktemp()
        ref = slowish.remote(marker)
        # wait for the task to start, then simulate memory pressure
        deadline = time.monotonic() + 30
        import os

        while not os.path.exists(marker) and time.monotonic() < deadline:
            time.sleep(0.1)
        assert os.path.exists(marker)
        mem["avail"] = 2  # 98% used -> kill
        deadline = time.monotonic() + 30
        while not raylet.memory_monitor.kills and time.monotonic() < deadline:
            time.sleep(0.2)
        assert raylet.memory_monitor.kills, "monitor never fired"
        mem["avail"] = 100  # pressure gone; retry can succeed
        assert ray_tpu.get(ref, timeout=120) == "done"
    finally:
        ray_tpu.shutdown()


# --------------------------------------------------------- GCS persistence/FT
def test_gcs_snapshot_restore(tmp_path):
    from ray_tpu.core.gcs import GcsServer
    from ray_tpu.utils import rpc as _rpc

    snap = str(tmp_path / "gcs.snap")
    io = _rpc.EventLoopThread()
    try:
        gcs = GcsServer(persist_path=snap)
        host, port = io.run(gcs.start())
        conn = io.run(_rpc.connect(host, port))
        io.run(conn.call("kv_put", {"ns": "app", "key": "k1", "value": b"v1"}))
        io.run(conn.call("register_job", {}))
        time.sleep(1.5)  # a persist tick
        io.run(conn.close())
        io.run(gcs.stop())

        gcs2 = GcsServer(persist_path=snap)
        host2, port2 = io.run(gcs2.start())
        conn2 = io.run(_rpc.connect(host2, port2))
        assert io.run(conn2.call("kv_get", {"ns": "app", "key": "k1"})) == b"v1"
        # job counter continues, no id reuse
        jid = io.run(conn2.call("register_job", {}))
        assert int.from_bytes(jid.binary(), "little") >= 2
        io.run(conn2.close())
        io.run(gcs2.stop())
    finally:
        io.stop()


def test_raylet_reconnects_to_restarted_gcs(tmp_path):
    """The GCS dies and comes back (same address, restored snapshot); the
    raylet's heartbeat loop reconnects and re-registers
    (ref: gcs client reconnection, test_gcs_fault_tolerance.py)."""
    from ray_tpu.core.gcs import GcsServer
    from ray_tpu.core.raylet import Raylet
    from ray_tpu.utils import rpc as _rpc

    snap = str(tmp_path / "gcs.snap")
    io = _rpc.EventLoopThread()
    raylet = None
    gcs2 = None
    try:
        gcs = GcsServer(persist_path=snap)
        host, port = io.run(gcs.start())

        async def mk_raylet():
            r = Raylet((host, port), resources={"CPU": 2.0})
            await r.start()
            return r

        raylet = io.run(mk_raylet())
        io.run(gcs.stop())

        gcs2 = GcsServer(port=port, persist_path=snap)  # same address
        io.run(gcs2.start())

        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if gcs2.nodes and any(n.alive for n in gcs2.nodes.values()):
                break
            time.sleep(0.3)
        else:
            pytest.fail("raylet never re-registered with the restarted GCS")
    finally:
        if raylet is not None:
            try:
                io.run(raylet.stop())
            except Exception:
                pass
        if gcs2 is not None:
            try:
                io.run(gcs2.stop())
            except Exception:
                pass
        io.stop()


# ------------------------------------------------------- GCS write-ahead log
def test_gcs_wal_survives_kill_between_mutations(tmp_path):
    """VERDICT r4 task 6: SIGKILL the GCS process between two KV/actor
    mutations — BOTH must survive recovery via WAL replay, including
    everything newer than the last snapshot (the snapshot loop runs at
    1s; the kill lands well inside that window)."""
    import os
    import signal
    import subprocess
    import sys

    from ray_tpu.utils import rpc as _rpc
    from ray_tpu.utils.ids import ActorID

    snap = str(tmp_path / "gcs.snap")
    addr_file = str(tmp_path / "gcs.addr")
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu.core.gcs", "--persist", snap,
         "--address-file", addr_file],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    io = _rpc.EventLoopThread()
    try:
        deadline = time.monotonic() + 60
        while not (time.monotonic() > deadline) and not (
                __import__("os").path.exists(addr_file)):
            time.sleep(0.1)
        host, port = open(addr_file).read().strip().split(":")

        async def mutate():
            c = await _rpc.connect(host, int(port), timeout=10)
            assert await c.call("kv_put", {"ns": "t", "key": "k1",
                                           "value": b"v1"})
            aid = ActorID.generate()
            await c.call("register_actor", {"spec": {
                "actor_id": aid, "name": "wal_actor",
                "resources": {"CPU": 0.0}}})
            # the SECOND kv mutation — the one a snapshot-only design
            # loses when the process dies before the next snapshot tick
            assert await c.call("kv_put", {"ns": "t", "key": "k2",
                                           "value": b"v2"})
            await c.close()
            return aid

        aid = io.run(mutate())
        os.kill(proc.pid, signal.SIGKILL)  # no final flush, no snapshot
        proc.wait(timeout=30)

        from ray_tpu.core.gcs import GcsServer

        gcs2 = GcsServer(persist_path=snap)
        io.run(gcs2.start())
        try:
            assert gcs2.kvstore.get("t", "k1") == b"v1"
            assert gcs2.kvstore.get("t", "k2") == b"v2", (
                "second mutation lost: WAL replay failed")
            assert aid in gcs2.actors, "actor registration lost"
            assert gcs2.named_actors.get("wal_actor") == aid
        finally:
            io.run(gcs2.stop())
    finally:
        if proc.poll() is None:
            proc.kill()
        io.stop()


def test_legacy_migration_survives_crash_midway(tmp_path):
    """ADVICE r5 (gcs.py:645): a crash mid legacy-format migration must
    not drop the unmigrated remainder. A partial pass leaves
    wal_records > 0 but NO ("legacy_migrated",) sentinel — the next start
    re-runs the (idempotent) migration instead of skipping it."""
    import pickle

    from ray_tpu.core.gcs import GcsServer
    from ray_tpu.core.gcs_store import NativeGcsStore
    from ray_tpu.utils import rpc as _rpc

    snap = str(tmp_path / "gcs.snap")
    # a legacy-format (pre-native) whole-state pickle snapshot
    with open(snap, "wb") as f:
        pickle.dump({
            "kv": {"app": {"k1": b"v1", "k2": b"v2", "k3": b"v3"}},
            "job_counter": 3, "actors": {}, "named_actors": {}, "pgs": {},
        }, f)
    # simulate the interrupted first pass: one key migrated (natively
    # journaled), then death — before k2/k3 and before the sentinel
    partial = NativeGcsStore(snap)
    assert not partial.had_snapshot  # legacy magic rejected by the engine
    partial.put("app", "k1", b"v1", journal=True)
    partial.close()

    io = _rpc.EventLoopThread()
    gcs = GcsServer(persist_path=snap)
    io.run(gcs.start())
    try:
        assert gcs.kvstore.wal_records > 0  # the old skip condition
        for k, v in (("k1", b"v1"), ("k2", b"v2"), ("k3", b"v3")):
            assert gcs.kvstore.get("app", k) == v, (
                f"legacy key {k} dropped by the interrupted migration")
        assert gcs.job_counter == 3
    finally:
        io.run(gcs.stop())

    # completed migration journals the sentinel: a restart (still no
    # native snapshot tick needed) must NOT re-clobber newer native state
    store = NativeGcsStore(snap)
    store.put("app", "k2", b"v2-updated", journal=True)
    store.close()
    gcs2 = GcsServer(persist_path=snap)
    io.run(gcs2.start())
    try:
        assert gcs2.kvstore.get("app", "k2") == b"v2-updated", (
            "sentinel ignored: migration re-ran over newer native state")
    finally:
        io.run(gcs2.stop())
        io.stop()


# --------------------------------------------------------------- chaos harness
def test_chaos_interval_killer_workload_completes():
    """VERDICT r4 task 7 (ref: _private/test_utils.py:1419
    ResourceKiller): a 3-node cluster loses a non-head raylet every few
    seconds — hard kill, no goodbyes — while a retryable task workload
    runs to completion. Retries + lease spillback must absorb every
    loss; replacement nodes keep capacity from draining to zero. The
    killer is the reusable seeded chaos.killers.IntervalKiller
    (devtools/chaos): same seed, same cluster shape ⇒ same victims."""
    from ray_tpu.core import api as _api
    from ray_tpu.core.cluster import Cluster
    from ray_tpu.core.core_client import CoreClient
    from ray_tpu.devtools.chaos.killers import IntervalKiller
    from ray_tpu.utils import rpc as _rpc

    io = _rpc.EventLoopThread()
    cluster = Cluster(io=io)
    head = cluster.add_node(num_cpus=4.0)
    for _ in range(2):
        cluster.add_node(num_cpus=4.0)
    core = CoreClient(loop=io.loop)
    io.run(core.connect(cluster.gcs_address, head.server.address))
    old = _api._core
    _api._core = core

    # PROGRESS-paced strikes (strike_once per wave, drawn off the same
    # seeded victim stream) instead of the wall-clock interval thread:
    # a 2s cadence couples the fault schedule to host speed — under full
    # tier-1 load the same waves take several times longer, so the same
    # seed landed several times MORE kills per task attempt, and the
    # occasional run piled enough mid-recovery kills onto one wave to
    # stall its get() past the timeout (the flake). One kill per
    # in-flight wave is the same experiment on every box.
    killer = IntervalKiller(cluster, seed=0, interval_s=2.0, restore=True)
    try:
        @ray_tpu.remote(max_retries=8, num_cpus=1.0)
        def work(i):
            import time as _t

            _t.sleep(0.3)  # long enough that kills land mid-task
            return i * 2

        results = []
        for wave in range(6):
            refs = [work.remote(wave * 8 + j) for j in range(8)]
            if wave:  # strike with the wave in flight: kills land
                killer.strike_once()  # mid-task, victims still seeded
            results.extend(ray_tpu.get(refs, timeout=300))
        assert sorted(results) == [i * 2 for i in range(48)]
        assert len(killer.kills) >= 2, \
            f"chaos never struck (kills={len(killer.kills)})"
        assert all(k["target"] == "raylet" for k in killer.kills)
    finally:
        killer.stop()
        _api._core = old
        try:
            io.run(core.close(), timeout=10)
        except Exception:
            pass  # links already torn by the last kill
        cluster.shutdown()
        io.stop()


# ------------------------------------------------------------- health loop
@pytest.mark.parametrize("stall_s,alive", [
    # the process that hosts the GCS did not run for 8 s (first seen: a whole
    # four-chip host paused while four TPU backends started). It could not
    # have taken a heartbeat meanwhile: its own pause is not a node's death
    (8.0, True),
    # the GCS ran all along and the node sent nothing for 8 s: dead
    (0.0, False),
])
def test_health_loop_forgives_its_own_stall(stall_s, alive):
    import asyncio

    from ray_tpu.core.gcs import GcsServer, NodeInfo
    from ray_tpu.utils.ids import NodeID

    async def run():
        gcs = GcsServer()
        nid = NodeID.generate()
        silent_for = 8.0  # > health_check_period_s * failure_threshold
        gcs.nodes[nid] = NodeInfo(
            node_id=nid, address=("127.0.0.1", 1), store_name="s",
            resources_total={"CPU": 1.0}, resources_available={"CPU": 1.0},
            last_heartbeat=time.monotonic() - silent_for)
        gcs._forgive_own_stall(stall_s, time.monotonic())
        task = asyncio.get_running_loop().create_task(gcs._health_loop())
        await asyncio.sleep(gcs.cfg.health_check_period_s + 0.3)  # one sweep
        gcs._stopping = True
        task.cancel()
        return gcs.nodes[nid].alive

    assert asyncio.run(run()) is alive


def test_stall_forgiveness_never_dates_a_heartbeat_ahead():
    """The loop was late but this node's heartbeat had been handled: its
    timestamp stays at or before now, so its death right afterwards is
    detected no later than any other."""
    from ray_tpu.core.gcs import GcsServer, NodeInfo
    from ray_tpu.utils.ids import NodeID

    gcs = GcsServer()
    now = time.monotonic()
    fresh, stale = NodeID.generate(), NodeID.generate()
    for nid, age in ((fresh, 0.5), (stale, 9.0)):
        gcs.nodes[nid] = NodeInfo(
            node_id=nid, address=("127.0.0.1", 1), store_name="s",
            resources_total={"CPU": 1.0}, resources_available={"CPU": 1.0},
            last_heartbeat=now - age)
    gcs._forgive_own_stall(8.0, now)
    assert gcs.nodes[fresh].last_heartbeat == now
    assert gcs.nodes[stale].last_heartbeat == pytest.approx(now - 1.0)

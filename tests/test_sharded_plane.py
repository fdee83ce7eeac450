"""Sharded object plane tests (ISSUE 7): manifest round-trip, reshard
correctness vs the jax.device_put oracle, partition-rule-driven
placement, shard GC, single-shard lineage recovery (plain + seeded
chaos plan), pjit-aware submission, telemetry surfaces, and a 2-actor
dp·tp end-to-end step through ShardedObjectRef inputs/outputs."""

import gc
import json
import os
import pickle
import subprocess
import sys
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.parallel.mesh import MeshSpec
from ray_tpu.parallel.sharding import PartitionRules

HERE = os.path.dirname(os.path.abspath(__file__))
PLAN = os.path.join(HERE, "plans", "sharded_shard_loss.json")

jax = pytest.importorskip("jax")
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402


@pytest.fixture(scope="module")
def rt():
    ray_tpu.init(num_cpus=8)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def mesh():
    return MeshSpec(dp=2, tp=2, sp=2).build()


def _arr(rows=16, cols=8, dtype=np.float32):
    return np.arange(rows * cols, dtype=dtype).reshape(rows, cols)


# ------------------------------------------------------------- manifest
def test_manifest_roundtrip(rt, mesh):
    arr = _arr()
    garr = jax.device_put(arr, NamedSharding(mesh, P("dp", "tp")))
    sref = rt.put_sharded(garr)
    assert sref.shape == (16, 8)
    assert sref.dtype == "float32"
    assert sref.spec == ("dp", "tp")
    assert sref.num_shards() == 4  # dp=2 x tp=2, sp replicas deduped
    assert sref.nbytes == arr.nbytes
    # pickle round trip: the manifest travels, the refs ride the
    # borrower protocol and resolve back to owned handles here
    clone = pickle.loads(pickle.dumps(sref))
    assert clone.manifest.global_shape == sref.manifest.global_shape
    assert clone.manifest.spec == sref.manifest.spec
    assert [s.box for s in clone.manifest.shards] == \
        [s.box for s in sref.manifest.shards]
    out = rt.get_sharded(clone, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(out), arr)


def test_put_get_never_materializes_global(rt, mesh):
    """put_sharded of a sharded jax array stores per-shard blobs only:
    each sealed object is one tile, not the array."""
    arr = _arr(32, 8)
    garr = jax.device_put(arr, NamedSharding(mesh, P("dp",)))
    sref = rt.put_sharded(garr)
    assert sref.num_shards() == 2
    for entry in sref.manifest.shards:
        assert entry.nbytes == arr.nbytes // 2  # a tile, not the whole
    out = rt.get_sharded(sref, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(out), arr)
    assert out.sharding.spec == P("dp")


# -------------------------------------------------------------- reshard
def test_reshard_matches_device_put_oracle(rt, mesh):
    arr = _arr(16, 8)
    sref = rt.put_sharded(
        jax.device_put(arr, NamedSharding(mesh, P("dp", "tp"))))
    for target in (P("tp"), P(None, ("dp", "tp")), P(("dp", "tp"),)):
        out = rt.reshard(sref, target, mesh=mesh)
        oracle = jax.device_put(arr, NamedSharding(mesh, target))
        got = rt.get_sharded(out, mesh=mesh)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(oracle))
        assert got.sharding.spec == oracle.sharding.spec


def test_reshard_same_spec_is_noop(rt, mesh):
    arr = _arr()
    sref = rt.put_sharded(jax.device_put(arr, NamedSharding(mesh, P("dp"))))
    assert rt.reshard(sref, P("dp"), mesh=mesh) is sref


# ------------------------------------------------------------ placement
def test_placement_follows_partition_rules(rt):
    """put_sharded(rules=..., path=...) picks its spec through the SAME
    spec_for table the train layer shards parameters with."""
    mesh = MeshSpec(fsdp=2, tp=2).build()
    w = _arr(8, 8)
    sref = rt.put_sharded(w, mesh=mesh, rules=PartitionRules.llama(),
                          path="layers/0/attn/wq/kernel")
    assert sref.spec == ("fsdp", "tp")  # column-parallel rule
    assert sref.num_shards() == 4
    out = rt.get_sharded(sref, mesh=mesh)
    oracle = jax.device_put(w, NamedSharding(mesh, P("fsdp", "tp")))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(oracle))
    # replicated norm rule -> one shard
    norm = rt.put_sharded(np.ones(8, np.float32), mesh=mesh,
                          rules=PartitionRules.llama(), path="ln_f/scale")
    assert norm.spec == ()
    assert norm.num_shards() == 1


def test_shard_tasks_route_to_owning_node(rt, mesh):
    """Every shard seals on this node and the submission resolves its
    routing target to this node's raylet without a directory hop."""
    core = rt.get_core()
    sref = rt.put_sharded(
        jax.device_put(_arr(), NamedSharding(mesh, P("dp"))))
    local = core.node_id.binary()
    assert all(s.node == local for s in sref.manifest.shards)

    @ray_tpu.remote(in_specs=P("dp"), out_specs=P("dp"))
    def f(x):
        return x

    addr_of = f._node_addresses(core, [sref], [0])
    assert addr_of[local] == tuple(core.raylet_address)


# ------------------------------------------------------------------- gc
def test_shard_gc_releases_shm(rt, mesh):
    core = rt.get_core()
    # earlier tests' shards are released asynchronously: a free that lands
    # between this reading and the put below reads as a put that stored
    # too little (512 bytes short, whenever allocation timing moved)
    gc.collect()
    base = core.store.stats()["bytes_in_use"]
    for _ in range(25):
        time.sleep(0.2)
        settled = core.store.stats()["bytes_in_use"]
        if settled == base:
            break
        base = settled
    arr = np.random.randn(8, 65_536).astype(np.float32)  # 2MB
    sref = rt.put_sharded(
        jax.device_put(arr, NamedSharding(mesh, P("dp"))))
    assert core.store.stats()["bytes_in_use"] >= base + arr.nbytes
    del sref
    gc.collect()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if core.store.stats()["bytes_in_use"] <= base + 4096:
            break
        time.sleep(0.1)
    assert core.store.stats()["bytes_in_use"] <= base + 4096, \
        "shard shm not released after the manifest died"


# ----------------------------------------------------------- submission
def test_sharded_submission_elementwise(rt, mesh):
    arr = _arr(16, 8)
    sref = rt.put_sharded(jax.device_put(arr, NamedSharding(mesh, P("dp"))))

    @ray_tpu.remote(in_specs=P("dp"), out_specs=P("dp"))
    def triple(x):
        return x * 3

    out = triple.remote(sref)
    assert out.num_shards() == sref.num_shards()
    got = rt.get_sharded(out, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(got), arr * 3)


def test_spec_mismatch_consumer_resharded(rt, mesh):
    """A consumer whose in_spec disagrees with the stored spec gets a
    collective-backed redistribute, and its result is bit-identical to
    running on the jax.device_put oracle layout."""
    arr = _arr(16, 8)
    stored = rt.put_sharded(
        jax.device_put(arr, NamedSharding(mesh, P("dp", "tp"))))

    @ray_tpu.remote(in_specs=P("tp"), out_specs=P("tp"))
    def fn(x):
        return x * 2 + 1

    out = fn.remote(stored)  # stored (dp,tp) != declared (tp): reshard
    assert out.spec == ("tp",)
    got = np.asarray(rt.get_sharded(out, mesh=mesh))
    oracle = np.asarray(
        jax.device_put(arr, NamedSharding(mesh, P("tp")))) * 2 + 1
    np.testing.assert_array_equal(got, oracle)
    from ray_tpu.sharded import stats

    assert stats()["reshards"] >= 1


def test_multi_arg_sharded_submission(rt, mesh):
    x = _arr(16, 8)
    y = np.ones_like(x) * 10
    sx = rt.put_sharded(jax.device_put(x, NamedSharding(mesh, P("dp"))))
    sy = rt.put_sharded(jax.device_put(y, NamedSharding(mesh, P("dp"))))

    @ray_tpu.remote(in_specs=(P("dp"), P("dp"), None), out_specs=P("dp"))
    def axpy(a, b, k):
        return a * k + b

    out = axpy.remote(sx, sy, 2.0)
    got = np.asarray(rt.get_sharded(out, mesh=mesh))
    np.testing.assert_array_equal(got, x * 2.0 + y)


# ------------------------------------------------------------- recovery
def test_single_shard_recovery_from_lineage(rt, mesh, tmp_path):
    """Losing ONE output shard re-runs only its producing task."""
    cdir = str(tmp_path)
    arr = np.arange(4 * 80_000, dtype=np.float32).reshape(4, 80_000)
    m4 = MeshSpec(dp=4).build()
    sref = rt.put_sharded(jax.device_put(arr, NamedSharding(m4, P("dp"))))

    @ray_tpu.remote(in_specs=P("dp"), out_specs=P("dp"))
    def work(x):
        import os as _os
        import uuid as _uuid

        open(_os.path.join(cdir, f"{x[0, 0]:.0f}-{_uuid.uuid4().hex[:6]}"),
             "w").close()
        return x + 1

    out = work.remote(sref)
    got = rt.get_sharded(out, mesh=m4)
    np.testing.assert_array_equal(np.asarray(got), arr + 1)
    del got
    gc.collect()  # drop the zero-copy views pinning the shard
    core = rt.get_core()
    lost = out.manifest.shards[2].ref
    core.store.delete(lost.id)
    assert not core.store.contains(lost.id)
    got2 = rt.get_sharded(out, mesh=m4)
    np.testing.assert_array_equal(np.asarray(got2), arr + 1)
    counts = {}
    for f in os.listdir(cdir):
        k = f.split("-")[0]
        counts[k] = counts.get(k, 0) + 1
    assert counts["160000"] == 2, counts  # the lost shard re-ran once
    assert sum(counts.values()) == 5, counts  # ...and NOTHING else did


_CHAOS_CHILD = """
import numpy as np, jax, os, json
jax.config.update("jax_platforms", "cpu")
from jax.sharding import NamedSharding, PartitionSpec as P
import ray_tpu
from ray_tpu.parallel.mesh import MeshSpec

cdir = os.environ["RT_TEST_CDIR"]
ray_tpu.init(num_cpus=8)
mesh = MeshSpec(dp=4).build()
arr = np.arange(4 * 80_000, dtype=np.float32).reshape(4, 80_000)
sref = ray_tpu.put_sharded(jax.device_put(arr, NamedSharding(mesh, P("dp"))))

@ray_tpu.remote(in_specs=P("dp"), out_specs=P("dp"))
def work(x):
    import os, uuid
    open(os.path.join(os.environ["RT_TEST_CDIR"],
                      f"{x[0,0]:.0f}-{uuid.uuid4().hex[:6]}"), "w").close()
    return x + 1

out = work.remote(sref)
g = ray_tpu.get_sharded(out, mesh=mesh)
ok = bool(np.array_equal(np.asarray(g), arr + 1))
counts = {}
for f in os.listdir(cdir):
    k = f.split("-")[0]
    counts[k] = counts.get(k, 0) + 1
print("RES=" + json.dumps({"ok": ok, "counts": counts}))
ray_tpu.shutdown()
"""


@pytest.mark.parametrize("plan", [PLAN])
def test_seeded_chaos_shard_loss_plan(plan, tmp_path):
    """The checked-in seeded shard-loss plan: a cluster_once kill at
    sharded.shard_seal SIGKILLs the worker sealing shard 2 — the wave
    completes, only that shard's task re-runs, and the fired fault is
    in the chaos log."""
    log_dir = str(tmp_path / "chaos")
    cdir = str(tmp_path / "execs")
    os.makedirs(cdir)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "RT_CHAOS_ENABLED": "1", "RT_CHAOS_PLAN": plan,
           "RT_CHAOS_LOG_DIR": log_dir, "RT_TEST_CDIR": cdir}
    proc = subprocess.run([sys.executable, "-c", _CHAOS_CHILD], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RES=")][0]
    res = json.loads(line[4:])
    assert res["ok"], "wave result wrong after seeded shard loss"
    counts = res["counts"]
    assert counts.get("160000", 0) >= 2, counts  # struck shard re-ran
    assert sum(counts.values()) <= 4 + 2, counts  # not the whole wave
    from ray_tpu.devtools.chaos.cli import read_events

    kills = [e for e in read_events(log_dir)
             if e["action"] == "kill" and e["point"] == "sharded.shard_seal"]
    assert len(kills) == 1, kills  # cluster_once: exactly one strike


# ------------------------------------------------------------ telemetry
def test_sharded_stages_in_latency_and_metrics(rt, mesh):
    from ray_tpu import state
    from ray_tpu.sharded import stats

    arr = _arr()
    sref = rt.put_sharded(jax.device_put(arr, NamedSharding(mesh, P("dp"))))
    rt.reshard(sref, P("tp"), mesh=mesh)
    s = stats()
    assert s["shards_sealed"] >= 3 and s["reshards"] >= 1
    assert s["driver_bytes"] > 0 and s["array_bytes"] >= arr.nbytes
    deadline = time.monotonic() + 8
    stages = {}
    while time.monotonic() < deadline:  # published on the 1Hz flush
        stages = state.list_task_latency()
        if all(k in stages for k in ("shard_seal", "shard_fetch",
                                     "reshard")):
            break
        time.sleep(0.3)
    for k in ("shard_seal", "shard_fetch", "reshard"):
        assert k in stages, sorted(stages)
        assert stages[k]["count"] >= 1
        assert stages[k]["p99_us"] >= 0
    # Prometheus side: the same stage tags on the task-stage families
    from ray_tpu.utils import metrics

    snap = metrics.registry().snapshot()["metrics"]
    hist = snap["rt_task_stage_seconds"]["samples"]
    tags = {s["tags"].get("stage") for s in hist}
    assert {"shard_seal", "shard_fetch", "reshard"} <= tags


# --------------------------------------------------- 2-actor dp·tp step
@ray_tpu.remote
class TpActor:
    """One data-parallel rank running a tensor-parallel step on its own
    virtual tp mesh; consumes/produces ShardedObjectRefs."""

    def __init__(self):
        self.mesh = MeshSpec(tp=2).build()

    def step(self, x_sref, dp_rank, w_sref):
        import jax as _jax

        from ray_tpu import sharded as _sh

        x = np.asarray(_sh.fetch_shard(x_sref, dp_rank))  # my dp shard
        w = _sh.get_sharded(w_sref, mesh=self.mesh)  # tp-sharded weight
        gx = _jax.device_put(x, NamedSharding(self.mesh, P()))
        y = _jax.jit(
            lambda a, b: a @ b,
            out_shardings=NamedSharding(self.mesh, P(None, "tp")),
        )(gx, w)
        return _sh.put_sharded(y)  # actor-owned output manifest


def test_two_actor_dp_tp_end_to_end(rt):
    dp, d_in, d_out = 2, 8, 8
    x = np.random.randn(4 * dp, d_in).astype(np.float32)
    w = np.random.randn(d_in, d_out).astype(np.float32)
    dp_mesh = MeshSpec(dp=dp).build()
    tp_mesh = MeshSpec(tp=2).build()
    x_sref = rt.put_sharded(
        jax.device_put(x, NamedSharding(dp_mesh, P("dp"))))
    w_sref = rt.put_sharded(
        jax.device_put(w, NamedSharding(tp_mesh, P(None, "tp"))))
    actors = [TpActor.remote() for _ in range(dp)]
    out_refs = [a.step.remote(x_sref, i, w_sref)
                for i, a in enumerate(actors)]
    out_srefs = rt.get(out_refs)  # small manifests, not array bytes
    parts = []
    for sref in out_srefs:
        assert sref.spec == (None, "tp")
        parts.append(np.asarray(rt.get_sharded(sref, mesh=tp_mesh)))
    got = np.concatenate(parts, axis=0)
    np.testing.assert_allclose(got, x @ w, rtol=1e-5, atol=1e-5)
    for a in actors:
        rt.kill(a)

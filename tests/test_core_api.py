"""End-to-end task/actor API tests (modeled on the reference's
python/ray/tests/test_basic.py coverage)."""

import gc
import time

import numpy as np
import pytest

import ray_tpu


@pytest.fixture(scope="module")
def rt():
    ray_tpu.init(num_cpus=8)
    yield ray_tpu
    ray_tpu.shutdown()


def test_put_get(rt):
    ref = rt.put({"x": 1})
    assert rt.get(ref) == {"x": 1}


def test_put_get_large_numpy(rt):
    arr = np.random.randn(1_000_000)  # 8MB: goes through shm
    ref = rt.put(arr)
    out = rt.get(ref)
    np.testing.assert_array_equal(out, arr)


def test_simple_task(rt):
    @rt.remote
    def add(a, b):
        return a + b

    assert rt.get(add.remote(1, 2)) == 3


def test_task_with_ref_arg(rt):
    @rt.remote
    def double(x):
        return x * 2

    ref = rt.put(21)
    assert rt.get(double.remote(ref)) == 42


def test_task_chain(rt):
    @rt.remote
    def inc(x):
        return x + 1

    ref = inc.remote(0)
    for _ in range(5):
        ref = inc.remote(ref)
    assert rt.get(ref) == 6


def test_many_parallel_tasks(rt):
    @rt.remote
    def square(i):
        return i * i

    refs = [square.remote(i) for i in range(50)]
    assert rt.get(refs) == [i * i for i in range(50)]


def test_task_large_return(rt):
    @rt.remote
    def big():
        return np.ones(500_000)  # 4MB

    out = rt.get(big.remote())
    assert out.sum() == 500_000


def test_task_exception_propagates(rt):
    @rt.remote
    def boom():
        raise ValueError("kaboom")

    from ray_tpu.core.ref import TaskError

    with pytest.raises(TaskError, match="kaboom"):
        rt.get(boom.remote())


def test_num_returns(rt):
    @rt.remote(num_returns=2)
    def two():
        return 1, 2

    r1, r2 = two.remote()
    assert rt.get(r1) == 1
    assert rt.get(r2) == 2


def test_nested_tasks(rt):
    @rt.remote
    def inner(x):
        return x + 1

    @rt.remote
    def outer(x):
        import ray_tpu as rtw

        return rtw.get(inner.remote(x)) + 10

    assert rt.get(outer.remote(0)) == 11


def test_wait(rt):
    @rt.remote
    def fast():
        return "fast"

    @rt.remote
    def slow():
        time.sleep(2.0)
        return "slow"

    # warm TWO workers first: a cold spawn costs ~3s on a loaded 1-CPU
    # box, which can otherwise hand `slow` a live worker while `fast`
    # waits to be forked — inverting the readiness order this asserts
    rt.get([fast.remote(), fast.remote()], timeout=60)
    f, s = fast.remote(), slow.remote()
    ready, pending = rt.wait([f, s], num_returns=1, timeout=10)
    assert ready == [f]
    assert pending == [s]
    ready, pending = rt.wait([f, s], num_returns=2, timeout=10)
    assert len(ready) == 2


def test_actor_basics(rt):
    @rt.remote
    class Counter:
        def __init__(self, start=0):
            self.n = start

        def inc(self, by=1):
            self.n += by
            return self.n

        def value(self):
            return self.n

    c = Counter.remote(10)
    assert rt.get(c.inc.remote()) == 11
    assert rt.get(c.inc.remote(5)) == 16
    assert rt.get(c.value.remote()) == 16


def test_actor_ordering(rt):
    @rt.remote
    class Accumulator:
        def __init__(self):
            self.items = []

        def add(self, i):
            self.items.append(i)

        def items_list(self):
            return self.items

    a = Accumulator.remote()
    for i in range(20):
        a.add.remote(i)
    assert rt.get(a.items_list.remote()) == list(range(20))


def test_a_handle_collected_inside_the_count_lock_does_not_wait_for_itself(rt):
    """The keys of the core client's counts hash in Python, so a garbage
    collection can start inside any section of their lock and run an
    ``ActorHandle.__del__`` there, which takes the same lock on the same
    thread: with a plain lock the thread waited for itself for ever
    ("Garbage-collecting" above ``note_actor_handle_dropped`` in the stack
    of a test at its time limit)."""
    from ray_tpu.utils.ids import ActorID

    @rt.remote
    class Held:
        def ping(self):
            return 1

    h = Held.remote()
    assert rt.get(h.ping.remote()) == 1
    core = h._core
    with core._rc_lock:
        core.note_actor_handle_dropped(ActorID.generate())  # an unknown id
        del h  # the last handle of an enrolled actor, collected HERE
        gc.collect()


def test_async_actor(rt):
    @rt.remote
    class AsyncWorker:
        async def work(self, x):
            import asyncio

            await asyncio.sleep(0.01)
            return x * 2

    w = AsyncWorker.remote()
    assert rt.get(w.work.remote(21)) == 42


def test_named_actor(rt):
    @rt.remote
    class Registry:
        def ping(self):
            return "pong"

    Registry.options(name="the-registry").remote()
    h = rt.get_actor("the-registry")
    assert rt.get(h.ping.remote()) == "pong"


def test_actor_exception(rt):
    @rt.remote
    class Bad:
        def fail(self):
            raise RuntimeError("actor-boom")

    from ray_tpu.core.ref import TaskError

    b = Bad.remote()
    with pytest.raises(TaskError, match="actor-boom"):
        rt.get(b.fail.remote())


def test_failed_actor_creation_returns_its_lease(rt):
    """An actor whose creation fails (a constructor that raises; on a TPU
    host a backend that cannot start) leaves no leased worker behind: its
    allocation comes back, or the next actor that needs it — a TPU worker's
    chips — waits for ever."""
    from ray_tpu.core.ref import ActorError

    @rt.remote(num_cpus=5)  # of the node's 8: two of them never fit
    class MostOfTheNode:
        def __init__(self, fail):
            if fail:
                raise RuntimeError("no backend")

        def ping(self):
            return "ok"

    bad = MostOfTheNode.remote(True)
    with pytest.raises(ActorError, match="actor creation failed"):
        rt.get(bad.ping.remote(), timeout=60)
    good = MostOfTheNode.remote(False)
    assert rt.get(good.ping.remote(), timeout=60) == "ok"
    rt.kill(good)


def test_kill_actor(rt):
    @rt.remote
    class Victim:
        def ping(self):
            return "ok"

    v = Victim.remote()
    assert rt.get(v.ping.remote()) == "ok"
    rt.kill(v)
    from ray_tpu.core.ref import ActorError

    time.sleep(0.5)
    with pytest.raises(ActorError):
        rt.get(v.ping.remote(), timeout=10)


def test_actor_handle_passed_to_task(rt):
    @rt.remote
    class Holder:
        def __init__(self):
            self.v = 7

        def get_v(self):
            return self.v

    @rt.remote
    def reads_actor(h):
        import ray_tpu as rtw

        return rtw.get(h.get_v.remote())

    h = Holder.remote()
    assert rt.get(reads_actor.remote(h)) == 7


def test_cluster_resources(rt):
    total = rt.cluster_resources()
    assert total.get("CPU", 0) >= 8


def test_actor_fifo_preserved_across_crash(rt, tmp_path):
    """In-flight actor calls replay IN ORDER after a crash+restart (ref:
    actor_task_submitter sequence replay; VERDICT r1 weak #10). Execution
    is at-least-once, but order never inverts."""
    log = str(tmp_path / "calls.log")

    @ray_tpu.remote(max_restarts=2)
    class Ordered:
        def record(self, i, log_path, crash_at):
            import os

            with open(log_path, "a") as f:
                f.write(f"{i},")
            if i == crash_at and not os.path.exists(log_path + ".crashed"):
                open(log_path + ".crashed", "w").close()
                os._exit(1)
            return i

    a = Ordered.remote()
    refs = [a.record.remote(i, log, crash_at=5) for i in range(12)]
    results = []
    for r in refs:
        try:
            results.append(ray_tpu.get(r, timeout=120))
        except Exception:
            results.append(None)  # the crashing call itself may fail
    assert results[:5] == [0, 1, 2, 3, 4]
    # every non-crashing call completed
    assert all(results[i] == i for i in range(12) if i != 5), results
    # the actor observed a non-decreasing first-occurrence order
    seen = [int(x) for x in open(log).read().strip(",").split(",")]
    firsts = []
    for x in seen:
        if x not in firsts:
            firsts.append(x)
    assert firsts == sorted(firsts), f"order inverted: {firsts}"


def test_cancel_pending_task(rt):
    """Queued tasks cancel cleanly with TaskCancelledError (ref: ray.cancel)."""
    from ray_tpu.core.ref import TaskCancelledError

    @ray_tpu.remote
    def blocker():
        import time

        time.sleep(2)
        return "done"

    @ray_tpu.remote
    def queued(dep):
        return "ran"

    # the victim is dependency-blocked behind the running blocker, so the
    # cancel deterministically lands before it can dispatch
    dep = blocker.remote()
    victim = queued.remote(dep)
    ray_tpu.cancel(victim)
    with pytest.raises(TaskCancelledError):
        ray_tpu.get(victim, timeout=60)
    # the rest of the cluster is unharmed
    assert ray_tpu.get(dep, timeout=120) == "done"


def test_cancel_actor_task_refused(rt):
    """Actor tasks cannot be cancelled: cancel must refuse loudly instead of
    half-cancelling the caller's ref while the method still runs."""

    @ray_tpu.remote
    class A:
        def m(self):
            return 7

    a = A.remote()
    try:
        ref = a.m.remote()
        with pytest.raises(ValueError):
            ray_tpu.cancel(ref)
        assert ray_tpu.get(ref, timeout=30) == 7  # result intact
    finally:
        ray_tpu.kill(a)  # free the worker slot for later tests


def test_cancel_force_kills_running_task(rt):
    from ray_tpu.core.ref import TaskCancelledError

    @ray_tpu.remote(max_retries=2)
    def forever(path):
        import time

        open(path, "w").close()
        time.sleep(120)

    import tempfile
    import time as _t

    marker = tempfile.mktemp()
    ref = forever.remote(marker)
    deadline = _t.monotonic() + 60
    import os

    while not os.path.exists(marker) and _t.monotonic() < deadline:
        _t.sleep(0.1)
    assert os.path.exists(marker), "task never started"
    ray_tpu.cancel(ref, force=True)
    with pytest.raises(TaskCancelledError):
        ray_tpu.get(ref, timeout=60)  # killed, not retried


def test_runtime_context(rt):
    ctx = ray_tpu.get_runtime_context()
    assert ctx.job_id is not None
    assert ctx.node_id is not None
    assert ctx.gcs_address is not None
    assert ctx.get_actor_id() is None  # driver side

    @ray_tpu.remote
    class Inspector:
        def who(self):
            c = ray_tpu.get_runtime_context()
            return c.get_actor_id() is not None, c.node_id is not None

    a = Inspector.remote()
    has_actor_id, has_node = ray_tpu.get(a.who.remote(), timeout=60)
    assert has_actor_id and has_node


def test_actor_concurrency_groups(rt):
    """Named concurrency groups (ref: concurrency_group_manager.cc): each
    group gets its own bounded pool, isolated from the default executor."""
    import threading
    import time as _t

    @ray_tpu.remote(num_cpus=0, max_concurrency=1, concurrency_groups={"io": 2})
    class Mixed:
        def __init__(self):
            self.lock = threading.Lock()
            self.active = 0
            self.peak = 0

        @ray_tpu.method(concurrency_group="io")
        def io_op(self, dur):
            with self.lock:
                self.active += 1
                self.peak = max(self.peak, self.active)
            _t.sleep(dur)
            with self.lock:
                self.active -= 1
            return "io"

        def compute(self):
            return "compute"

        def stats(self):
            return self.peak

    a = Mixed.remote()
    try:
        ray_tpu.get(a.compute.remote(), timeout=120)  # wait for ALIVE first
        # 4 io calls over 2 slots: at least two must overlap
        refs = [a.io_op.remote(0.7) for _ in range(4)]
        # the default group stays responsive while io is saturated
        t0 = _t.monotonic()
        assert ray_tpu.get(a.compute.remote(), timeout=60) == "compute"
        assert _t.monotonic() - t0 < 0.7, "default group blocked behind io"
        assert ray_tpu.get(refs, timeout=120) == ["io"] * 4
        peak = ray_tpu.get(a.stats.remote(), timeout=60)
        assert peak == 2, f"io group peak concurrency {peak}, want exactly 2"
        # per-call group override
        assert ray_tpu.get(
            a.compute.options(concurrency_group="io").remote(), timeout=60
        ) == "compute"
        # an undeclared group fails loudly, not silently unisolated
        from ray_tpu.core.ref import TaskError

        with pytest.raises(TaskError, match="not declared"):
            ray_tpu.get(
                a.compute.options(concurrency_group="oi").remote(), timeout=60)
    finally:
        ray_tpu.kill(a)


def test_method_num_returns_annotation(rt):
    @ray_tpu.remote(num_cpus=0)
    class Splitter:
        @ray_tpu.method(num_returns=2)
        def pair(self):
            return "a", "b"

    s = Splitter.remote()
    try:
        r1, r2 = s.pair.remote()
        assert ray_tpu.get([r1, r2], timeout=120) == ["a", "b"]
    finally:
        ray_tpu.kill(s)


def test_threaded_actor_sync_methods_overlap(rt):
    """max_concurrency > 1 actors must never ride the ring fast lane: the
    pump runs ring records sequentially in one executor job, so two sync
    methods that coordinate (wait/signal) would deadlock. Regression for
    the attach-time + per-record gates in worker.rpc_attach_fast_ring /
    _fast_actor_pump."""
    import threading

    @ray_tpu.remote(num_cpus=0, max_concurrency=2)
    class Coord:
        def __init__(self):
            self.evt = threading.Event()

        def wait_for_signal(self):
            return self.evt.wait(timeout=30)

        def signal(self):
            self.evt.set()
            return "signaled"

    a = Coord.remote()
    try:
        waiter = a.wait_for_signal.remote()
        assert ray_tpu.get(a.signal.remote(), timeout=60) == "signaled"
        assert ray_tpu.get(waiter, timeout=60) is True
    finally:
        ray_tpu.kill(a)


def test_actor_fast_lane_fifo_across_downgrade(rt):
    """Same-node actor calls ride the shm ring; an ineligible call
    (ObjectRef arg) permanently downgrades the lane to RPC — and the
    caller's submission order must hold exactly across that switch."""
    import time as _t

    @ray_tpu.remote(num_cpus=0)
    class Log:
        def __init__(self):
            self.log = []

        def add(self, x):
            if not isinstance(x, int):
                x = int(x)
            self.log.append(x)
            return len(self.log)

        def get_log(self):
            return list(self.log)

    a = Log.remote()
    ray_tpu.get(a.add.remote(-1), timeout=120)  # conn + lane attach
    _t.sleep(0.5)
    refs = [a.add.remote(i) for i in range(5)]
    refs.append(a.add.remote(ray_tpu.put(100)))  # ineligible: retires lane
    refs += [a.add.remote(i) for i in range(5, 10)]
    ray_tpu.get(refs, timeout=120)
    log = ray_tpu.get(a.get_log.remote(), timeout=60)
    assert log == [-1, 0, 1, 2, 3, 4, 100, 5, 6, 7, 8, 9], log


def test_actor_fast_lane_survives_restart(rt):
    """Actor crash + restart: the stale ring lane breaks, calls replay
    over RPC, and a fresh lane attaches to the new incarnation."""
    import os
    import signal
    import time as _t

    @ray_tpu.remote(num_cpus=0, max_restarts=2)
    class P:
        def pid(self):
            return os.getpid()

    r = P.remote()
    p1 = ray_tpu.get(r.pid.remote(), timeout=120)
    ray_tpu.get([r.pid.remote() for _ in range(5)], timeout=60)  # lane warm
    os.kill(p1, signal.SIGKILL)
    _t.sleep(1)
    p2 = None
    for _ in range(30):
        try:
            p2 = ray_tpu.get(r.pid.remote(), timeout=60)
            break
        except Exception:
            _t.sleep(1)
    assert p2 is not None and p2 != p1
    assert set(ray_tpu.get([r.pid.remote() for _ in range(20)],
                           timeout=60)) == {p2}

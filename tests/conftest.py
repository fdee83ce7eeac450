"""Test harness: force an 8-device virtual CPU mesh before jax initializes.

Mirrors the reference's one-machine multi-node test strategy
(ref: python/ray/tests/conftest.py:589-719, cluster_utils.py:135): tests run
against virtual topology, not real hardware. ``JAX_PLATFORMS=cpu`` keeps
this process off any chip; ``RT_FORCE_CPU_DEVICES`` does the same for every
process the runtime spawns (ray_tpu/utils/device.py), chip leases included.
"""

import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"
# Children spawned by the runtime inherit these so worker processes also use
# the virtual CPU mesh during tests.
os.environ["RT_FORCE_CPU_DEVICES"] = "8"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import faulthandler  # noqa: E402
import gc  # noqa: E402
import signal  # noqa: E402
import threading  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402

import ray_tpu  # noqa: E402

# A time limit of its own for every test: for its set-up, its body and its
# tear-down, each. The slowest test takes about half a minute; one still
# running after TEST_LIMIT_S waits for something that will not come (a
# lost reply behind a ``ray_tpu.get`` with no timeout), and until the
# run's own limit it would hold its xdist worker and every test queued
# behind it. At the limit every thread's stack goes to stderr and the
# test fails with TestTimeLimit; the blocking calls of ray_tpu (get,
# wait, a dataset's take_all) let the signal through. The cluster such a
# test leaves behind has lost work somewhere, and the next tests of its
# module would wait for the same thing: so before the next test the
# runtime is shut down and started again as the module's fixture started
# it (the fixtures hand out the ``ray_tpu`` module itself, which follows),
# and the rest of the module gets AFTER_LIMIT_S a test. Those tests still
# run and still count, and a module that cannot be saved costs the run
# no more than TEST_LIMIT_S plus AFTER_LIMIT_S a test.
TEST_LIMIT_S = 300
AFTER_LIMIT_S = 30
_over_limit: set = set()  # modules in which a test ran into its limit
_restart: set = set()     # of those, the ones whose runtime is still the old
_last_init = None         # (args, kwargs) of this process's last ray_tpu.init
_real_stderr = None
_init = ray_tpu.init


def _recording_init(*args, **kwargs):
    global _last_init
    _last_init = (args, kwargs)
    return _init(*args, **kwargs)


ray_tpu.init = _recording_init


class TestTimeLimit(Exception):
    __test__ = False  # not a test class, whatever its name starts with


def pytest_configure(config):
    # output capture is not on yet: descriptor 2 is still the run's stderr
    global _real_stderr
    if _real_stderr is None:
        _real_stderr = os.fdopen(os.dup(2), "w")


def _limited(item, phase: str):
    """Generator body of the three hook wrappers below: SIGALRM after the
    item's limit, raised as TestTimeLimit inside whatever the phase runs."""
    if threading.current_thread() is not threading.main_thread():
        yield  # a signal handler belongs to the main thread
        return
    module = item.nodeid.split("::", 1)[0]
    limit = AFTER_LIMIT_S if module in _over_limit else TEST_LIMIT_S

    def on_alarm(signum, frame):
        _over_limit.add(module)
        _restart.add(module)
        print(f"\n{item.nodeid}: {phase} still running after {limit} s",
              file=_real_stderr, flush=True)
        faulthandler.dump_traceback(file=_real_stderr, all_threads=True)
        raise TestTimeLimit(f"{phase} still running after {limit} s")

    before = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        if (phase == "set-up" and module in _restart
                and ray_tpu.is_initialized() and _last_init is not None):
            _restart.discard(module)
            ray_tpu.shutdown()
            ray_tpu.init(*_last_init[0], **_last_init[1])
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    yield from _limited(item, "set-up")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    yield from _limited(item, "test")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item):
    yield from _limited(item, "tear-down")


def _mappings() -> int:
    """Memory mappings this process holds (0 where /proc does not say)."""
    try:
        with open("/proc/self/maps", "rb") as f:
            return sum(1 for _ in f)
    except OSError:
        return 0


def _mappings_allowed() -> int:
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            return int(f.read())
    except (OSError, ValueError):
        return 65530  # the kernel's default


@pytest.fixture(autouse=True)
def _compiled_programs_released():
    """XLA's CPU compiler maps several regions of memory for every program
    it compiles or reads from the compile cache, jax keeps every program of
    every jitted function for the life of the process, and a process may
    hold ``vm.max_map_count`` mappings (65,530). An xdist worker that ran
    enough serving-engine modules in a row reached it (one such module
    leaves 10,000-16,000 behind, the largest 52,000): LLVM's next mapping
    failed ("LLVM compilation error: Cannot allocate memory") and the
    worker died of a segmentation fault inside ``backend_compile_and_load``
    or the compile cache's read or write, in whatever test stood next. So
    after a test that leaves the process with more than half its allowance,
    jax's caches are cleared: the programs go with them (16,000 mappings
    fall to under 1,000), and what the next test needs again it reads from
    the compile cache's directory. An engine a fixture still holds keeps
    its own programs."""
    yield
    if _mappings() > _mappings_allowed() // 2:
        gc.collect()  # engines in cycles hold their compiled programs
        jax.clear_caches()


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual cpu devices, got {devs}"
    return devs

"""JAX platform/device configuration: who holds a chip, and where
compiled programs are kept.

A TPU chip belongs to one process at a time: the first process that
initialises a TPU backend takes libtpu's lock and every later one fails
or hangs on it. The runtime hands chips to *processes*, so the backend a
process may initialise is decided from what the process was granted,
before any backend exists (``configure_jax``):

- ``RT_FORCE_CPU_DEVICES=N`` (tests): N virtual CPU devices, whatever the
  lease says.
- a worker the raylet spawned for a lease that carries chips (the raylet
  exports ``TPU_VISIBLE_CHIPS`` + bounds into that worker's environment
  at spawn): the TPU backend on exactly those chips, even when the parent
  exported ``JAX_PLATFORMS=cpu`` to keep itself off the chip.
  ``verify_leased_chips`` then holds the worker to it.
- a worker the raylet spawned with no chips (plain task workers, the serve
  controller and proxies) and the standalone raylet / GCS processes
  (``pin_cpu``): the CPU backend, so that touching jax there — unpickling
  a jax array runs ``jax.device_put`` — can never take a chip. They are
  pinned through ``JAX_PLATFORMS`` and do not import jax to be so.
- the driver is the user's process and is not pinned.

The persistent compilation cache is placed here and nowhere else: where
``JAX_COMPILATION_CACHE_DIR`` is set jax reads it itself and no directory
is set in code; otherwise it is ``<checkout>/.jax_cache``. The path is part
of nothing but the checkout, so replicas, trainer workers and
``chip_smoke.py`` (which inherit the environment) share one cache.

What the cache keys a program on is fixed here too: its operations, shapes
and ``jax.named_scope`` names, and nothing of where its source lies. jax would
write the Python frames that reached a lowering (path, line, columns) into
every location, a Pallas kernel's Mosaic payload carries them and the key
reads the payload: a line moved above a kernel, or the same tree in another
directory, would re-key programs whose operations did not change. So no frame
is written (an operator loses the Python frame in an XLA runtime error's
location) and the scopes are in the key (``_CACHE_SETTINGS``): a cached
executable carries its tree's (``tests/test_compile_key.py``).
"""

from __future__ import annotations

import os
import sys

#: the checkout's root: .../ray_tpu/utils/device.py -> three levels up
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: programs that compile faster than this are not worth a cache file; the
#: engine's smaller programs (page scatter, prefill at short pads) compile
#: in well under jax's 1 s default and would otherwise be recompiled by
#: every replica
_CACHE_MIN_COMPILE_SECS = 0.1
#: the cache's settings, a pinned process's variables (names in upper case)
_CACHE_SETTINGS = {
    "jax_persistent_cache_min_compile_time_secs": _CACHE_MIN_COMPILE_SECS,
    # Python frames in an operation's location (jax's default: 10): with
    # none, a moved line or another checkout directory changes no key
    "jax_traceback_in_locations_limit": 0,
    # the key reads the named scopes: a cached executable has its tree's
    "jax_compilation_cache_include_metadata_in_key": True,
}

_configured = False
_listening = False


class AcceleratorMismatchError(RuntimeError):
    """The devices a process sees are not the chips its lease granted."""


def forced_cpu_devices(env=os.environ) -> int:
    return int(env.get("RT_FORCE_CPU_DEVICES", "0") or 0)


def leased_chips(env=os.environ) -> list[str]:
    """Chip ids the raylet granted this worker (exported at spawn)."""
    visible = env.get("TPU_VISIBLE_CHIPS", "")
    return [c for c in visible.split(",") if c]


def platform_for_process(env=os.environ) -> str | None:
    """The jax platform this process must use, or None where the library
    does not decide (the driver). Pure function of the environment."""
    if forced_cpu_devices(env) > 0:
        return "cpu"
    if "RT_WORKER_ID" not in env:
        return None  # not spawned by a raylet: the user's process
    # "tpu,cpu": the TPU is the default backend and must initialise (a
    # listed platform that cannot is an error, never a silent CPU), and
    # host-side jax.devices("cpu") keeps working
    return "tpu,cpu" if leased_chips(env) else "cpu"


def compilation_cache_dir(env=os.environ) -> str | None:
    """Directory to set in code, or None when jax's own variable places
    the cache (jax reads ``JAX_COMPILATION_CACHE_DIR`` itself)."""
    if env.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return os.path.join(_REPO_ROOT, ".jax_cache")


def pin_cpu() -> None:
    """Pin this process to the CPU backend (standalone raylet and GCS
    mains). Does not import jax: the variable is read when jax is."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_platforms", "cpu")


def configure_jax() -> None:
    """Apply the ownership rule and the compile-cache rule of the module
    docstring. Call before any jax backend use; idempotent.

    A process pinned to the CPU that has not imported jax is configured
    through the variables jax reads at import and imports nothing: most
    plain workers, the serve controller and the proxies never touch jax,
    and importing it costs each of them seconds of start-up.

    A process that has jax also hears of every program jax builds from then
    on (``_listen_to_builds``); one that was pinned without it does from its
    next call here after its own ``import jax``."""
    global _configured
    if not _configured:
        _configured = True
        _place_backend_and_cache()
    if not _listening and "jax" in sys.modules:
        _listen_to_builds()


def _place_backend_and_cache() -> None:
    n = forced_cpu_devices()
    if n > 0:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n}"
            ).strip()
    platform = platform_for_process()
    cache_dir = compilation_cache_dir()
    if platform == "cpu" and "jax" not in sys.modules:
        pin_cpu()
        if cache_dir is not None:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
        os.environ.update(
            {name.upper(): str(v) for name, v in _CACHE_SETTINGS.items()})
        return
    import jax

    if platform is not None:
        # config, not the variable: it also overrides a JAX_PLATFORMS the
        # parent exported for itself and every child inherited
        jax.config.update("jax_platforms", platform)
    if cache_dir is not None:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    for name, v in _CACHE_SETTINGS.items():
        jax.config.update(name, v)


def _listen_to_builds() -> None:
    """Register, once a process, the listeners that sum what jax reports of
    each program it traces, lowers, reads from the compile cache or compiles
    into ``rt_bringup_seconds`` (``utils/tracing.py`` ``build_duration``).
    They fire only when jax builds a program: never on a step's path."""
    global _listening
    _listening = True
    from jax import monitoring

    from ray_tpu.utils import tracing

    monitoring.register_event_listener(tracing.build_event)
    monitoring.register_scalar_listener(tracing.build_begin)
    monitoring.register_event_duration_secs_listener(tracing.build_duration)


def verify_leased_chips(chips: list[str]) -> None:
    """Hold a chip worker to its lease: it was born with exactly these
    chips in its environment, every device is a TPU and there are
    ``len(chips)`` of them. Initialises the backend (seconds: call it off
    the event loop). The device check is skipped under
    ``RT_FORCE_CPU_DEVICES`` (tests lease pretend chips)."""
    chips = [str(c) for c in chips]
    if leased_chips() != chips:
        raise AcceleratorMismatchError(
            f"lease carries TPU chips {chips} but this worker was spawned "
            f"with TPU_VISIBLE_CHIPS={os.environ.get('TPU_VISIBLE_CHIPS')!r}")
    if not chips or forced_cpu_devices() > 0:
        return
    from ray_tpu.utils import tracing

    with tracing.stage("backend_start", chips=len(chips)):
        configure_jax()
        import jax

        devs = jax.devices()
    if len(devs) != len(chips) or any(d.platform != "tpu" for d in devs):
        raise AcceleratorMismatchError(
            f"lease granted TPU chips {list(chips)} but this worker (pid "
            f"{os.getpid()}) sees {len(devs)} device(s): "
            f"{[(d.platform, d.device_kind, d.id) for d in devs]} "
            f"(TPU_VISIBLE_CHIPS={os.environ.get('TPU_VISIBLE_CHIPS')!r}, "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")


def holds_tpu_backend() -> bool:
    """True when this process has already initialised a TPU backend (and
    so holds its chips). Never initialises one."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    # backends() only returns the existing table once one is initialised
    return (xla_bridge.backends_are_initialized()
            and "tpu" in xla_bridge.backends())


def device_report() -> dict:
    """What this process really runs on, for health checks and smokes, and
    ``bringup``: the seconds and counts of every stage of bring-up this
    process has been through (``rt_bringup_seconds`` by
    ``tracing.STAGES``) — a train worker has no engine to ask."""
    configure_jax()
    import jax

    from ray_tpu.utils import metrics

    devs = jax.devices()
    stats = [d.memory_stats() or {} for d in devs]
    return {
        "pid": os.getpid(),
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
        "bytes_in_use": [s.get("bytes_in_use") for s in stats],
        "bytes_limit": stats[0].get("bytes_limit"),
        "bringup": metrics.family_totals(metrics.bringup_seconds),
    }

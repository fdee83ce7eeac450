"""Who holds a chip, and where compiled programs are kept
(ray_tpu/utils/device.py, and the raylet's side of the same rule)."""

import os
import subprocess
import sys

import pytest

from ray_tpu.utils import device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spawned(**extra):
    """Environment of a worker the raylet spawned."""
    return {"RT_WORKER_ID": "ab" * 14, **extra}


@pytest.mark.parametrize("env,want", [
    # no chip in the lease: pinned to the CPU, even when the parent exports
    # a TPU platform for itself
    (_spawned(JAX_PLATFORMS="tpu,cpu"), "cpu"),
    (_spawned(), "cpu"),
    # a chip lease: never pinned to the CPU, even when the parent keeps
    # itself off the chip with JAX_PLATFORMS=cpu and every child inherits it
    (_spawned(JAX_PLATFORMS="cpu", TPU_VISIBLE_CHIPS="0"), "tpu,cpu"),
    (_spawned(TPU_VISIBLE_CHIPS="0,1,2,3"), "tpu,cpu"),
    # the driver is the user's process: the library does not decide
    ({"JAX_PLATFORMS": "cpu"}, None),
    ({"TPU_VISIBLE_CHIPS": "0"}, None),
    # tests: the virtual CPU mesh, whatever the lease says
    (_spawned(RT_FORCE_CPU_DEVICES="8", TPU_VISIBLE_CHIPS="0"), "cpu"),
    ({"RT_FORCE_CPU_DEVICES": "8"}, "cpu"),
])
def test_platform_follows_the_lease(env, want):
    assert device.platform_for_process(env) == want


def test_worker_environment_follows_the_lease(monkeypatch):
    """The raylet's spawn environment, end to end with the rule above: a
    no-chip worker loses an inherited TPU_VISIBLE_CHIPS, a chip worker
    is born with its chips."""
    from ray_tpu.core.raylet import Raylet

    captured = []

    class FakeProc:
        pid = 0

    def fake_popen(argv, env, **kw):
        captured.append(env)
        return FakeProc()

    class Stub:
        _n_tpu_chips = 4
        store_name = "s"
        session = "s"
        log_dir = "/nonexistent/never/created"
        gcs_address = ("127.0.0.1", 1)
        all_workers: dict = {}

        class server:
            address = ("127.0.0.1", 2)

        class node_id:
            hex = staticmethod(lambda: "n")

        class cgroups:
            isolate_worker = staticmethod(lambda *a: None)

    from ray_tpu.config import get_config

    Stub.cfg = get_config()
    monkeypatch.setattr("ray_tpu.core.raylet.subprocess.Popen", fake_popen)
    monkeypatch.setattr("ray_tpu.core.raylet.os.makedirs",
                        lambda *a, **k: (_ for _ in ()).throw(OSError()))
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0,1,2,3")  # the node's own
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("RT_FORCE_CPU_DEVICES", raising=False)

    Raylet._spawn_worker(Stub, "python", None)
    Raylet._spawn_worker(Stub, "python", ["2"])
    plain, chip = captured
    assert "TPU_VISIBLE_CHIPS" not in plain
    assert device.platform_for_process(plain) == "cpu"
    assert chip["TPU_VISIBLE_CHIPS"] == "2"
    assert chip["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,1,1"
    assert device.leased_chips(chip) == ["2"]
    assert device.platform_for_process(chip) == "tpu,cpu"


def test_lease_carries_its_chips_or_waits():
    """A TPU demand the ledger would grant either comes with that many chip
    ids or is not granted: never a lease with the resource and no chip."""
    from ray_tpu.core.raylet import Raylet, ResourceLedger

    class Stub:
        ledger = ResourceLedger({"CPU": 4.0, "TPU": 1.0})
        _tpu_chips_free: list = []  # an exiting worker still holds the chip

    want = {"CPU": 1.0, "TPU": 1.0}
    assert Raylet._try_allocate(Stub, want, None) is None
    assert Stub.ledger.available["TPU"] == 1.0  # nothing was taken
    Stub._tpu_chips_free.append("0")  # the process exited
    assert Raylet._try_allocate(Stub, want, None) == ["0"]
    assert Stub._tpu_chips_free == []
    assert Raylet._try_allocate(Stub, {"CPU": 1.0}, None) == []
    # handing an unused allocation back returns the chip at once
    Raylet._free_resources(Stub, want, None, ["0"])
    assert Stub._tpu_chips_free == ["0"]
    assert Stub.ledger.available["TPU"] == 1.0


@pytest.mark.parametrize("demand", [0.5, 1.5, 3.0])
def test_lease_refuses_a_demand_no_chips_can_carry(demand):
    from ray_tpu.core.raylet import Raylet

    reply = Raylet._refuse_tpu_demand(None, {"CPU": 1.0, "TPU": demand})
    assert reply["granted"] is False and reply["infeasible"] is True
    assert "chip configurations" in reply["error"]
    assert Raylet._refuse_tpu_demand(None, {"CPU": 1.0}) is None
    assert Raylet._refuse_tpu_demand(None, {"TPU": 4.0}) is None


def test_compile_cache_rule():
    # variable set: jax reads it itself and no directory is set in code
    assert device.compilation_cache_dir(
        {"JAX_COMPILATION_CACHE_DIR": "/somewhere/else"}) is None
    # unset: a fixed path under the checkout — no pid, session, time or
    # temporary name in it, so every process of every run finds the same
    assert device.compilation_cache_dir({}) == os.path.join(REPO, ".jax_cache")


_CACHE_CHILD = """
import os, sys
sys.path.insert(0, {repo!r})
from ray_tpu.utils.device import configure_jax
configure_jax()
print("jax" in sys.modules)
print(os.environ.get("JAX_TRACEBACK_IN_LOCATIONS_LIMIT"))
import jax
print(jax.config.jax_compilation_cache_dir)
print(jax.config.jax_persistent_cache_min_compile_time_secs)
print(jax.config.jax_platforms)
print(jax.config.jax_traceback_in_locations_limit)
print(jax.config.jax_compilation_cache_include_metadata_in_key)
"""


@pytest.mark.parametrize("var", [None, "/elsewhere/cache"])
@pytest.mark.parametrize("chips", [None, "0"])
def test_configure_jax_places_the_cache(var, chips, tmp_path):
    """configure_jax() in a fresh worker process: where the variable is set,
    the directory jax reports is the variable's; where it is not, the
    checkout's. A chip-less worker is pinned to the CPU on the way, whatever
    platform its parent exported, and does not pay for importing jax to be
    so; a chip worker needs jax anyway and lists the TPU first. Either way
    the process writes no Python frame into a program's locations and keys
    the cache on its scopes (``tests/test_compile_key.py``): the pinned one
    through the variable jax reads at import."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "RT_FORCE_CPU_DEVICES",
                        "TPU_VISIBLE_CHIPS")}
    env["RT_WORKER_ID"] = "cd" * 14
    # the parent's own choice: must not be inherited either way
    env["JAX_PLATFORMS"] = "cpu" if chips else "tpu,cpu"
    if chips:
        env["TPU_VISIBLE_CHIPS"] = chips
    if var is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = var
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_CHILD.format(repo=REPO)], env=env,
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    (imported, frames_var, cache_dir, min_secs, platforms, frames,
     scopes_in_key) = out.stdout.split()
    assert imported == str(bool(chips))
    assert frames_var == ("None" if chips else "0")
    assert (frames, scopes_in_key) == ("0", "True")
    assert cache_dir == (var or os.path.join(REPO, ".jax_cache"))
    assert float(min_secs) < 1.0
    assert platforms == ("tpu,cpu" if chips else "cpu")


def test_holds_tpu_backend_is_false_off_the_chip():
    import jax

    jax.devices()
    assert device.holds_tpu_backend() is False


def test_verify_leased_chips_names_what_it_saw(monkeypatch):
    """A chip worker whose birth environment names other chips than its
    lease, or that finds CPU devices, fails with the typed error."""
    monkeypatch.delenv("RT_FORCE_CPU_DEVICES")
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "1")
    with pytest.raises(device.AcceleratorMismatchError, match="spawned with"):
        device.verify_leased_chips(["0"])
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0")
    with pytest.raises(device.AcceleratorMismatchError) as e:
        device.verify_leased_chips(["0"])
    assert "cpu" in str(e.value) and "['0']" in str(e.value)
    # under the test rig's forced CPU mesh the pretend chips pass
    monkeypatch.setenv("RT_FORCE_CPU_DEVICES", "8")
    device.verify_leased_chips(["0"])

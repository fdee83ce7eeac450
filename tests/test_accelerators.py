"""TPU accelerator-manager tests with faked topology env
(ref test strategy: python/ray/tests/accelerators/test_tpu.py)."""

import pytest

from ray_tpu.accelerators import tpu as tpu_mod
from ray_tpu.accelerators.tpu import TPUAcceleratorManager as Mgr


@pytest.fixture(autouse=True)
def clean_env(monkeypatch):
    for var in (
        "TPU_ACCELERATOR_TYPE", "TPU_WORKER_ID", "TPU_NAME",
        "TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_HOST_BOUNDS", "TPU_HOST_BOUNDS",
    ):
        monkeypatch.delenv(var, raising=False)
    # no chip device files unless a test fakes them: detection must not
    # depend on the machine the suite runs on
    _fake_dev(monkeypatch, accel=[], vfio=None)
    yield


def _fake_dev(monkeypatch, *, accel, vfio):
    """Fake the host's chip device files: ``accel`` is what
    ``/dev/accel*`` globs to, ``vfio`` the entries of ``/dev/vfio``
    (None: the directory does not exist)."""
    import os

    real_listdir = os.listdir

    def listdir(path="."):
        if path == "/dev/vfio":
            if vfio is None:
                raise FileNotFoundError(path)
            return list(vfio)
        return real_listdir(path)

    monkeypatch.setattr(tpu_mod.glob, "glob",
                        lambda pat: list(accel) if pat == "/dev/accel*" else [])
    monkeypatch.setattr(tpu_mod.os, "listdir", listdir)


def test_pod_type_and_generation(monkeypatch):
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v4-16")
    assert Mgr.get_current_node_tpu_pod_type() == "v4-16"
    assert Mgr.get_current_node_accelerator_type() == "TPU-V4"
    assert Mgr.get_num_workers_in_current_tpu_pod() == 2  # 16 cores / 8 per host


def test_chips_per_host_by_generation():
    assert tpu_mod.get_num_tpu_visible_chips_per_host("v4-8") == 4
    assert tpu_mod.get_num_tpu_visible_chips_per_host("v5litepod-16") == 8
    assert tpu_mod.get_tpu_cores_per_chip("v4-8") == 2
    assert tpu_mod.get_tpu_cores_per_chip("v5litepod-16") == 1
    with pytest.raises(ValueError):
        tpu_mod.get_num_tpu_visible_chips_per_host("h100-8")


def test_accelerator_type_validation():
    assert Mgr.is_valid_tpu_accelerator_type("v4-16")
    assert Mgr.is_valid_tpu_accelerator_type("v5litepod-256")
    assert not Mgr.is_valid_tpu_accelerator_type("v4")
    assert not Mgr.is_valid_tpu_accelerator_type("tpu-v4-16")
    assert not Mgr.is_valid_tpu_accelerator_type("v4-16-x")


def test_node_resources_worker0(monkeypatch):
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v4-16")
    monkeypatch.setenv("TPU_NAME", "my-tpu")
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    res = Mgr.get_current_node_tpu_resources()
    assert res == {
        "TPU": 4.0,
        "TPU-V4": 4.0,
        "my-tpu": 1.0,
        "TPU-v4-16-head": 1.0,
    }
    labels = Mgr.get_current_node_tpu_labels()
    assert labels == {
        "tpu-pod-type": "v4-16",
        "tpu-name": "my-tpu",
        "tpu-worker-id": "0",
    }


def test_node_resources_worker1_no_head(monkeypatch):
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v4-16")
    monkeypatch.setenv("TPU_NAME", "my-tpu")
    monkeypatch.setenv("TPU_WORKER_ID", "1")
    res = Mgr.get_current_node_tpu_resources()
    assert "TPU-v4-16-head" not in res
    assert res["my-tpu"] == 1.0


@pytest.mark.parametrize("accel,vfio,pod_type,want", [
    # the one-chip machine of PR 21: one numbered vfio group beside the
    # container device, while the topology variable still names the
    # four-chip host it was cut from — the files win
    ([], ["2", "vfio"], "v5litepod-4", 1),
    # its four-chip host
    ([], ["0", "1", "2", "3", "vfio"], "v5litepod-4", 4),
    # a GCE TPU VM
    (["/dev/accel0", "/dev/accel1", "/dev/accel2", "/dev/accel3"], None,
     None, 4),
    # no device files visible: the slice topology variable
    ([], None, "v4-16", 4),
    # nothing at all
    ([], None, None, 0),
])
def test_chip_detection(monkeypatch, accel, vfio, pod_type, want):
    _fake_dev(monkeypatch, accel=accel, vfio=vfio)
    if pod_type:
        monkeypatch.setenv("TPU_ACCELERATOR_TYPE", pod_type)
    assert Mgr.get_current_node_num_accelerators() == want
    res = Mgr.get_current_node_tpu_resources()
    assert res.get("TPU", 0.0) == float(want)
    if want and pod_type:
        gen = "TPU-" + pod_type.split("-")[0].upper()
        assert res[gen] == float(want)
    elif want:
        # generation unreadable: TPU without an invented TPU-{gen} marker
        assert set(res) == {"TPU"}


def test_visible_chips_isolation():
    env = Mgr.visible_chips_env(["1"], 4)
    assert env == {"TPU_VISIBLE_CHIPS": "1",
                   "TPU_CHIPS_PER_HOST_BOUNDS": "1,1,1",
                   "TPU_HOST_BOUNDS": "1,1,1"}
    env = Mgr.visible_chips_env(["0", "1"], 4)
    assert env["TPU_VISIBLE_CHIPS"] == "0,1"
    assert env["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,2,1"
    # a subset with no published bounds removes inherited ones
    env = Mgr.visible_chips_env(["0", "1", "2"], 8)
    assert env["TPU_CHIPS_PER_HOST_BOUNDS"] is None
    assert env["TPU_HOST_BOUNDS"] is None


def test_visible_chips_full_host_keeps_host_bounds():
    # the whole host: the chips are named (it is how the worker knows it
    # holds a chip lease) and the host's own bounds are left alone
    assert Mgr.visible_chips_env(["0", "1", "2", "3"], 4) == {
        "TPU_VISIBLE_CHIPS": "0,1,2,3"}
    assert Mgr.visible_chips_env(["0"], 1) == {"TPU_VISIBLE_CHIPS": "0"}


def test_chip_quantity_validation():
    ok, _ = Mgr.validate_resource_request_quantity(4)
    assert ok
    bad, msg = Mgr.validate_resource_request_quantity(3)
    assert not bad and "chip configurations" in msg
    # a fraction of a chip is refused, not rounded
    bad, msg = Mgr.validate_resource_request_quantity(0.5)
    assert not bad and "chip configurations" in msg


def test_scaling_config_topology():
    from ray_tpu.train import ScalingConfig

    sc = ScalingConfig(topology="v4-16")
    assert sc.num_workers == 2
    assert sc.use_tpu
    assert sc.placement_strategy == "STRICT_SPREAD"
    assert sc.worker_resources()["TPU"] == 4.0
    assert sc.worker_resources()["TPU-V4"] == 4.0
    assert sc.backend() == "xla"

    sc = ScalingConfig(topology="v5litepod-16")  # 16 chips, 8 per host
    assert sc.num_workers == 2
    assert sc.worker_resources()["TPU"] == 8.0


def test_slice_placement_group_shape(monkeypatch):
    """slice_placement_group builds one bundle per slice host without
    needing a live cluster (patch placement_group)."""
    captured = {}

    def fake_pg(bundles, strategy="PACK", name=""):
        captured["bundles"] = bundles
        captured["strategy"] = strategy
        return "PG"

    import ray_tpu.core.api as api

    monkeypatch.setattr(api, "placement_group", fake_pg)
    assert tpu_mod.slice_placement_group("v4-16") == "PG"
    assert captured["strategy"] == "STRICT_SPREAD"
    assert captured["bundles"] == [
        {"TPU": 4.0, "TPU-V4": 4.0},
        {"TPU": 4.0, "TPU-V4": 4.0},
    ]


def test_e2e_chip_isolation_through_lease():
    """A task leasing TPU:2 on a 4-chip node runs with TPU_VISIBLE_CHIPS
    set to its 2 granted chip ids (ref: worker-side accelerator env
    isolation); chips return to the pool with the lease."""
    import os

    import ray_tpu

    ray_tpu.init(num_cpus=8, num_tpus=4)
    try:

        @ray_tpu.remote(num_tpus=2)
        def which_chips():
            return os.environ.get("TPU_VISIBLE_CHIPS")

        chips = ray_tpu.get(which_chips.remote(), timeout=60)
        assert chips is not None and len(chips.split(",")) == 2

        # both 2-chip leases can be live at once on a 4-chip node
        a, b = which_chips.remote(), which_chips.remote()
        got = ray_tpu.get([a, b], timeout=60)
        assert all(g is not None and len(g.split(",")) == 2 for g in got)

        # a worker leased no chip is born without the variable, whatever
        # the node's own environment says
        @ray_tpu.remote
        def no_chips():
            return os.environ.get("TPU_VISIBLE_CHIPS")

        assert ray_tpu.get(no_chips.remote(), timeout=60) is None

        # a fraction of a chip is refused by the raylet, the authority on
        # leases, and reaches the caller through the ordinary error paths
        from ray_tpu.core.ref import ActorError, SchedulingError

        with pytest.raises(SchedulingError, match="chip configurations"):
            ray_tpu.get(which_chips.options(num_tpus=0.5).remote(), timeout=60)

        @ray_tpu.remote(num_tpus=0.5)
        class Half:
            def ping(self):
                return 1

        with pytest.raises(ActorError, match="chip configurations"):
            ray_tpu.get(Half.remote().ping.remote(), timeout=60)
    finally:
        ray_tpu.shutdown()


def test_e2e_chip_released_to_next_lease():
    """On a one-chip node a second TPU lease taken right after the first
    is returned waits for the first worker to exit and then carries the
    chip — never a granted TPU resource with no chip id."""
    import os

    import ray_tpu

    ray_tpu.init(num_cpus=4, num_tpus=1)
    try:

        @ray_tpu.remote(num_tpus=1)
        class Holder:
            def chips(self):
                return os.environ.get("TPU_VISIBLE_CHIPS"), os.getpid()

        seen = []
        for _ in range(3):
            h = Holder.remote()
            seen.append(ray_tpu.get(h.chips.remote(), timeout=90))
            ray_tpu.kill(h)
        assert [c for c, _ in seen] == ["0", "0", "0"]
        assert len({pid for _, pid in seen}) == 3  # never a reused process
    finally:
        ray_tpu.shutdown()

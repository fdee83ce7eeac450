"""TPU topology as a first-class scheduling resource.

TPU-native equivalent of the reference TPUAcceleratorManager (ref:
python/ray/_private/accelerators/tpu.py:24-61 chip detection + env
isolation, :232 set_current_process_visible_accelerator_ids, :236
_get_current_node_tpu_pod_type, :416 get_current_node_additional_resources
pod-head resources). Differences by design:

- Chips are counted from the device files the host really exposes
  (``/dev/accel*``, else the numbered ``/dev/vfio/<group>`` entries), the
  GKE-style ``TPU_*`` variables give topology and generation — no GCE
  metadata-server dependency (zero-egress environments), and no jax
  backend is initialised (that would take the chips).
- Topology is also exposed as node LABELS (tpu-pod-type / tpu-name /
  tpu-worker-id) so label-aware placement can gang-schedule a slice, not
  just count chips.

Node resources produced for a v4-16 worker 0 host:
    {"TPU": 4, "TPU-V4": 4, "my-tpu": 1, "TPU-v4-16-head": 1}
"""
from __future__ import annotations

import glob
import os
import re

TPU_VALID_CHIP_OPTIONS = (1, 2, 4, 8)
TPU_ACCELERATOR_TYPE_ENV = "TPU_ACCELERATOR_TYPE"  # e.g. "v4-16"
TPU_WORKER_ID_ENV = "TPU_WORKER_ID"
TPU_NAME_ENV = "TPU_NAME"
TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
TPU_CHIPS_PER_HOST_BOUNDS_ENV = "TPU_CHIPS_PER_HOST_BOUNDS"
TPU_HOST_BOUNDS_ENV = "TPU_HOST_BOUNDS"
_CHIPS_PER_HOST_BOUNDS_1 = "1,1,1"
_CHIPS_PER_HOST_BOUNDS_2 = "1,2,1"
_SINGLE_HOST_BOUNDS = "1,1,1"

# v2/v3/v4/v5p: 4 chips/host, 2 cores/chip; v5e(=v5litepod)/v6e: 8 chips, 1 core
_8_CHIP_TYPES = ("v5litepod", "v5e", "v6e")
_1_CORE_TYPES = ("v5litepod", "v5e", "v6e")
VALID_TPU_TYPES = ("v2", "v3", "v4", "v5p", "v5litepod", "v5e", "v6e")


def _accelerator_type_check(accelerator_type: str) -> None:
    # accept anything shaped v{generation}[variant]-{cores}: unknown future
    # generations fall back to the 4-chip/2-core default rather than
    # crashing node detection
    if not re.match(r"^v\d+[a-zA-Z]*(-\d+)?$", accelerator_type):
        raise ValueError(
            f"Invalid accelerator type: {accelerator_type!r}; expected "
            f"v<generation>-<cores>, e.g. one of {VALID_TPU_TYPES}"
        )


def get_num_tpu_visible_chips_per_host(accelerator_type: str) -> int:
    _accelerator_type_check(accelerator_type)
    return 8 if accelerator_type.startswith(_8_CHIP_TYPES) else 4


def get_tpu_cores_per_chip(accelerator_type: str) -> int:
    _accelerator_type_check(accelerator_type)
    return 1 if accelerator_type.startswith(_1_CORE_TYPES) else 2


class TPUAcceleratorManager:
    """Static env/topology introspection (one instance per process)."""

    @staticmethod
    def get_resource_name() -> str:
        return "TPU"

    # ---------------------------------------------------------- detection
    @staticmethod
    def get_current_process_visible_accelerator_ids() -> list[str] | None:
        visible = os.environ.get(TPU_VISIBLE_CHIPS_ENV)
        if visible is None:
            return None
        if visible == "":
            return []
        return visible.split(",")

    @staticmethod
    def get_current_node_chip_files() -> list[str]:
        """Device files of this host's chips: ``/dev/accel*`` (GCE TPU
        VMs), else the numbered ``/dev/vfio/<group>`` entries (one per
        chip; ``/dev/vfio/vfio`` is the container device, not a chip)."""
        accel = sorted(glob.glob("/dev/accel*"))
        if accel:
            return accel
        try:
            groups = [e for e in os.listdir("/dev/vfio") if e.isdigit()]
        except FileNotFoundError:
            return []
        return [f"/dev/vfio/{g}" for g in sorted(groups, key=int)]

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        """Chips on this host: an explicit ``TPU_VISIBLE_CHIPS``, else the
        device files, else the slice topology variable (ref:
        get_current_node_num_accelerators :137). The files go before the
        topology because a sandbox may expose one chip of a host whose
        ``TPU_ACCELERATOR_TYPE`` still names all four."""
        visible = TPUAcceleratorManager.get_current_process_visible_accelerator_ids()
        if visible is not None:
            return len(visible)
        files = TPUAcceleratorManager.get_current_node_chip_files()
        if files:
            return len(files)
        pod_type = os.environ.get(TPU_ACCELERATOR_TYPE_ENV)
        if pod_type and TPUAcceleratorManager.is_valid_tpu_accelerator_type(pod_type):
            per_host = get_num_tpu_visible_chips_per_host(pod_type)
            cores = int(pod_type.split("-")[1])
            total_chips = cores // get_tpu_cores_per_chip(pod_type)
            return min(per_host, total_chips)
        return 0

    @staticmethod
    def is_valid_tpu_accelerator_type(tpu_accelerator_type: str) -> bool:
        """v{generation}{variant}-{cores} shape check (ref: :158)."""
        return re.match(r"^v\d+[a-zA-Z]*-\d+$", tpu_accelerator_type) is not None

    @staticmethod
    def get_current_node_tpu_pod_type() -> str | None:
        """The slice topology string, e.g. 'v4-16' (ref: :236)."""
        t = os.environ.get(TPU_ACCELERATOR_TYPE_ENV, "")
        if t and TPUAcceleratorManager.is_valid_tpu_accelerator_type(t):
            return t
        return None

    @staticmethod
    def get_current_node_tpu_name() -> str | None:
        return os.environ.get(TPU_NAME_ENV) or None

    @staticmethod
    def get_current_node_tpu_worker_id() -> int | None:
        w = os.environ.get(TPU_WORKER_ID_ENV)
        try:
            return int(w) if w is not None else None
        except ValueError:
            return None

    @staticmethod
    def get_num_workers_in_current_tpu_pod() -> int | None:
        """Hosts in this slice (ref: :316): ceil(total_cores / cores_per_host)."""
        pod_type = TPUAcceleratorManager.get_current_node_tpu_pod_type()
        if not pod_type:
            return None
        return slice_shape(pod_type)[0]

    @staticmethod
    def get_current_node_accelerator_type() -> str | None:
        """Generation marker resource, e.g. 'TPU-V4' (ref: :330)."""
        pod_type = TPUAcceleratorManager.get_current_node_tpu_pod_type()
        if pod_type is None:
            return None
        return "TPU-" + pod_type.split("-")[0].upper()

    # ---------------------------------------------------------- resources
    @staticmethod
    def get_current_node_tpu_resources() -> dict[str, float]:
        """Full TPU resource dict for node registration: chip count,
        generation marker, slice name, and the pod-head marker on worker 0
        (ref: get_current_node_additional_resources :416)."""
        n = TPUAcceleratorManager.get_current_node_num_accelerators()
        if n <= 0:
            return {}
        resources: dict[str, float] = {"TPU": float(n)}
        gen = TPUAcceleratorManager.get_current_node_accelerator_type()
        if gen:
            resources[gen] = float(n)
        name = TPUAcceleratorManager.get_current_node_tpu_name()
        worker_id = TPUAcceleratorManager.get_current_node_tpu_worker_id()
        pod_type = TPUAcceleratorManager.get_current_node_tpu_pod_type()
        if name and worker_id is not None and pod_type:
            resources[name] = 1.0
            if worker_id == 0:
                resources[f"TPU-{pod_type}-head"] = 1.0
        return resources

    @staticmethod
    def get_current_node_tpu_labels() -> dict[str, str]:
        """Topology labels for label-aware slice placement."""
        labels: dict[str, str] = {}
        pod_type = TPUAcceleratorManager.get_current_node_tpu_pod_type()
        if pod_type:
            labels["tpu-pod-type"] = pod_type
        name = TPUAcceleratorManager.get_current_node_tpu_name()
        if name:
            labels["tpu-name"] = name
        worker_id = TPUAcceleratorManager.get_current_node_tpu_worker_id()
        if worker_id is not None:
            labels["tpu-worker-id"] = str(worker_id)
        return labels

    # ---------------------------------------------------------- isolation
    @staticmethod
    def validate_resource_request_quantity(quantity: float) -> tuple[bool, str | None]:
        """A TPU demand is a whole number of chips in a shape a host can
        isolate. A fractional ``num_tpus`` is refused, not rounded: a chip
        belongs to one process, so two half-chip leases could never share
        one, and rounding up would grant what was not asked for."""
        if quantity not in TPU_VALID_CHIP_OPTIONS:
            return (
                False,
                f"requested TPU={quantity}, but only chip configurations "
                f"{TPU_VALID_CHIP_OPTIONS} map onto TPU hosts",
            )
        return True, None

    @staticmethod
    def visible_chips_env(visible_chips: list[str],
                          num_node_chips: int) -> dict[str, str | None]:
        """Environment that restricts a process to ``visible_chips``: the
        triplet libtpu reads at first init (ref: :195 — the documented
        TPU_VISIBLE_CHIPS / *_BOUNDS combination). The raylet merges it
        into a chip worker's environment at spawn; None removes a
        variable. ``TPU_VISIBLE_CHIPS`` is always set — it is also how
        the worker knows it was leased chips (utils/device.py) — and
        removed for a worker leased none, whatever the node's own
        environment names. A lease of the whole host keeps the host's own
        bounds."""
        n = len(visible_chips)
        if n == 0:
            return {TPU_VISIBLE_CHIPS_ENV: None}
        env: dict[str, str | None] = {
            TPU_VISIBLE_CHIPS_ENV: ",".join(str(c) for c in visible_chips)}
        if n == num_node_chips:
            return env
        bounds = {1: _CHIPS_PER_HOST_BOUNDS_1, 2: _CHIPS_PER_HOST_BOUNDS_2,
                  # half of an 8-chip host (the documented chip-subset shape)
                  4: "2,2,1"}.get(n)
        # no published bounds for other subsets: clear inherited values
        # rather than leaving another shape's bounds behind
        env[TPU_CHIPS_PER_HOST_BOUNDS_ENV] = bounds
        env[TPU_HOST_BOUNDS_ENV] = _SINGLE_HOST_BOUNDS if bounds else None
        return env


# ------------------------------------------------------------------ helpers
def slice_shape(accelerator_type: str) -> tuple[int, int, str]:
    """(num_hosts, chips_per_bundle_host, generation_marker) for a slice —
    the one place the host math lives (ScalingConfig.topology,
    slice_placement_group, and pod-worker counting all call this)."""
    _accelerator_type_check(accelerator_type)
    chips_per_host = get_num_tpu_visible_chips_per_host(accelerator_type)
    cores_per_chip = get_tpu_cores_per_chip(accelerator_type)
    cores_per_host = chips_per_host * cores_per_chip
    num_cores = int(accelerator_type.split("-")[1])
    num_hosts = max(1, (num_cores + cores_per_host - 1) // cores_per_host)
    host_chips = max(1, min(chips_per_host, num_cores // cores_per_chip))
    gen = "TPU-" + accelerator_type.split("-")[0].upper()
    return num_hosts, host_chips, gen


def slice_placement_group(accelerator_type: str, *, strategy: str = "STRICT_SPREAD"):
    """Placement group spanning every host of one TPU slice: one bundle per
    host, each requesting that host's full chip count plus the generation
    marker (the TPU-first answer to 'STRICT_PACK = one contiguous slice').

    Usage:
        pg = slice_placement_group("v4-16")
        # bundle i -> host i of the slice
    """
    import ray_tpu

    num_hosts, host_chips, gen = slice_shape(accelerator_type)
    bundles = [
        {"TPU": float(host_chips), gen: float(host_chips)} for _ in range(num_hosts)
    ]
    return ray_tpu.placement_group(bundles, strategy=strategy)


def pod_head_resource(accelerator_type: str) -> dict[str, float]:
    """Resource dict targeting worker 0 of a slice, for launch-once pod
    coordination tasks (ref: the TPU-{pod}-head pattern, tpu.py:404)."""
    return {f"TPU-{accelerator_type}-head": 1.0}

"""Starting and ending the runtime around one run, from a parent that stays
off jax (the pattern of ``chip_smoke.py``): the chip belongs to the worker
that is leased it. Every process this run starts is ended and waited for."""
from __future__ import annotations

import contextlib
import glob
import os
import signal
import tempfile
import threading
import time


def descendants() -> list[int]:
    ppid: dict[int, int] = {}
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                ppid[int(entry)] = int(f.read().rsplit(")", 1)[1].split()[1])
        except (OSError, IndexError, ValueError):
            continue
    out, frontier = [], [os.getpid()]
    while frontier:
        parent = frontier.pop()
        kids = [p for p, pp in ppid.items() if pp == parent]
        out += kids
        frontier += kids
    return out


def chip_files() -> list[str]:
    from ray_tpu.accelerators.tpu import TPUAcceleratorManager

    return TPUAcceleratorManager.get_current_node_chip_files()


class Runtime:
    """``with Runtime(chips, allow_cpu):`` — ray_tpu up, and down again with
    nothing left running, whatever happens inside."""

    def __init__(self, chips: int, allow_cpu: bool, deadline_s: float):
        self.chips, self.allow_cpu, self.deadline_s = chips, allow_cpu, deadline_s
        self._seen = {os.getpid()}
        self._stop = threading.Event()

    def __enter__(self):
        import ray_tpu
        from ray_tpu.config import get_config, set_config

        if self.allow_cpu:
            os.environ["RT_FORCE_CPU_DEVICES"] = str(self.chips)
        else:
            os.environ.pop("RT_FORCE_CPU_DEVICES", None)
        cfg = get_config()
        cfg.worker_start_timeout_s = 600.0
        # session files under TMPDIR, never a fixed /tmp path
        cfg.temp_dir = os.path.join(tempfile.gettempdir(), "ray_tpu_bench")
        set_config(cfg)

        def watch():
            while not self._stop.wait(0.5):
                self._seen.update(descendants())

        threading.Thread(target=watch, daemon=True).start()

        def expired():
            print(f"[bench] FAILED: exceeded the {self.deadline_s:.0f}s deadline",
                  flush=True)
            self._kill()
            os._exit(3)

        self._timer = threading.Timer(self.deadline_s, expired)
        self._timer.daemon = True
        self._timer.start()
        ray_tpu.init(num_tpus=self.chips if self.allow_cpu else None)
        have = ray_tpu.cluster_resources().get("TPU", 0)
        if have < self.chips:
            raise RuntimeError(f"the cell needs {self.chips} chip(s), the "
                               f"runtime found {have:g}")
        return self

    def _kill(self) -> None:
        for pid in descendants():
            with contextlib.suppress(OSError):
                os.kill(pid, signal.SIGKILL)

    def __exit__(self, *exc):
        import ray_tpu

        self._timer.cancel()
        with contextlib.suppress(Exception):
            from ray_tpu import serve

            serve.shutdown()
        with contextlib.suppress(Exception):
            ray_tpu.shutdown()
        deadline = time.monotonic() + 20
        while descendants() and time.monotonic() < deadline:
            time.sleep(0.2)
        self._kill()
        deadline = time.monotonic() + 10
        while descendants() and time.monotonic() < deadline:
            time.sleep(0.1)
        self._stop.set()
        for pattern in [f"rt_c{os.getpid()}_*",
                        *(f"rt_fp_{pid}_*" for pid in sorted(self._seen))]:
            for leftover in glob.glob(f"/dev/shm/{pattern}"):
                with contextlib.suppress(OSError):
                    os.unlink(leftover)
        return False


def worker_log_tails(since: float, lines: int = 30) -> str:
    from ray_tpu.config import get_config

    out = []
    pattern = os.path.join(get_config().temp_dir, "**", "worker-*.err")
    for path in sorted(glob.glob(pattern, recursive=True), key=os.path.getmtime):
        if os.path.getmtime(path) < since or not os.path.getsize(path):
            continue
        with open(path, errors="replace") as f:
            out.append(f"--- {path}\n{''.join(f.readlines()[-lines:])}")
    return "\n".join(out)

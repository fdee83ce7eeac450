"""The readers of what the program times and counts itself (PR 25): stage
means, phase shares and counter ratios on counter snapshots written down by
hand, the device's idle time by host phase on planes written down by hand,
and the shared clock on a small trace recorded on a v5e chip
(``record_trace_spans.py``) and kept beside this file."""
import os
from types import SimpleNamespace as NS

import pytest

from benchmarks.lib import xplane
from benchmarks.lib.configs import load_json, load_module
from benchmarks.readers import idle_by_phase

HERE = os.path.dirname(os.path.abspath(__file__))


def read(name, run):
    spec = load_json("layer_metrics", name + ".json")
    return load_module("readers", spec["reader"]).read(run, **spec["args"])


def hist(total, count):
    return {"sum": total, "count": count}


def snapshot(t, scale):
    """The ``stages`` key as ``engine_stats()`` gives it, every number
    ``scale`` times a base: cumulative since the process started."""
    return {"t": t, "stages": {
        "rt_llm_engine_phase_seconds": {
            "engine.block_sync": hist(7.0 * scale, 10 * scale),
            "engine.prefill_sync": hist(1.0 * scale, 4 * scale),
            "engine.admit": hist(0.5 * scale, 8 * scale),
            "engine.emit": hist(0.1 * scale, 10 * scale)},
        "rt_serve_lane_seconds": {"ring": hist(0.004 * scale, 4 * scale),
                                  "loop": hist(3.2 * scale, 4 * scale)},
        "rt_llm_queue_wait_seconds": {"": hist(2.0 * scale, 4 * scale)},
        "rt_llm_prefill_wait_seconds": {"": hist(0.6 * scale, 4 * scale)},
        "rt_llm_prefill_waves_total": {"": {"sum": 4.0 * scale}},
        "rt_llm_prefill_prompts_total": {"": {"sum": 6.0 * scale}},
        "rt_llm_prefill_true_tokens_total": {"": {"sum": 6000.0 * scale}},
        "rt_llm_prefill_padded_tokens_total": {"": {"sum": 8000.0 * scale}}}}


def test_stage_means_shares_and_ratios():
    # between the snapshots every stage grew by twice the base, in 20 s
    run = {"counters": {"before": snapshot(100.0, 1), "after": snapshot(120.0, 3)}}
    assert read("replica.ring_wait_mean_ms", run) == pytest.approx(1.0)
    assert read("replica.loop_wait_mean_ms", run) == pytest.approx(800.0)
    assert read("engine.queue_wait_mean_ms", run) == pytest.approx(500.0)
    assert read("engine.prefill_wait_mean_ms", run) == pytest.approx(150.0)
    # (14 + 2) s of block_sync and prefill_sync in 20 s of wall
    assert read("engine.loop_blocked_share.chat", run) == pytest.approx(80.0)
    assert read("engine.loop_blocked_share.batch", run) == pytest.approx(80.0)
    assert read("engine.admission_stall_share.chat", run) == pytest.approx(15.0)
    assert read("engine.prompts_per_prefill_counted.batch", run) == pytest.approx(1.5)
    assert read("engine.prefill_pad_waste.batch", run) == pytest.approx(25.0)


def test_a_program_without_stages_reads_as_nothing():
    old = {"counters": {"before": {"t": 1.0, "steps": 5},
                        "after": {"t": 9.0, "steps": 50}}}
    for name in ("replica.loop_wait_mean_ms", "engine.queue_wait_mean_ms",
                 "engine.loop_blocked_share.chat",
                 "engine.admission_stall_share.chat",
                 "engine.prompts_per_prefill_counted.batch",
                 "engine.prefill_pad_waste.batch"):
        assert read(name, old) is None and read(name, {}) is None, name
    # the key is there but nothing of the stage was ever observed
    quiet = {"counters": {"before": {"t": 1.0, "stages": {}},
                          "after": {"t": 9.0, "stages": {}}}}
    assert read("replica.loop_wait_mean_ms", quiet) is None
    assert read("engine.prefill_pad_waste.batch", quiet) is None


def ev(name, start_us, dur_us, **stats):
    return NS(name=name, start_ns=start_us * 1000, duration_ns=dur_us * 1000,
              stats=list(stats.items()))


def hand_planes(annotated=True):
    """1,100 us of one chip: programs at 0-200, 300-700 and 1000-1100, so
    it idles 200-300 and 700-1000; the loop thread was in decode_dispatch
    250-320, block_sync 320-720, emit 720-800, free 800-810, admit 850-950
    and in no phase 810-850 and 950-1000."""
    mods = NS(name="XLA Modules", events=[
        ev("jit_paged_decode_multi(1)", 0, 200),
        ev("jit_paged_decode_multi(1)", 300, 400),
        ev("jit_paged_prefill_batch(2)", 1000, 100)])
    ops = NS(name="XLA Ops", events=[
        ev("%fusion.1", 0, 200), ev("%fusion.1", 300, 400),
        ev("%fusion.2", 1000, 100)])
    loop = NS(name="python3", events=[
        ev("PjitFunction(paged_decode_multi)", 260, 50),
        ev("engine.decode_dispatch", 250, 70, steps=8, live=3),
        ev("engine.block_sync", 320, 400, steps=8),
        ev("engine.emit", 720, 80, tokens=24),
        ev("engine.free", 800, 10, freed=1),
        ev("engine.admit", 850, 100, pad=1024, wave=1, prompts=1)])
    other = NS(name="tf_pjrt/7", events=[ev("ThunkExecute", 0, 1100)])
    host = NS(name="/host:CPU", lines=[loop, other] if annotated else [other])
    return [NS(name="/device:TPU:0", lines=[ops, mods]), host]


def hand_run(monkeypatch, annotated=True, span_s=1200e-6):
    planes = hand_planes(annotated)
    monkeypatch.setattr(idle_by_phase, "load_planes", lambda: planes)
    return {"trace": xplane.reduce_planes(planes), "trace_span_s": span_s}


def test_idle_by_phase_adds_up_to_the_idle_share(monkeypatch, capsys):
    run = hand_run(monkeypatch)
    us = 100.0 / 1200  # percent of the 1,200 us span a microsecond is
    # idle 200-300: dispatch 250-300; idle 700-1000: sync 700-720, emit
    # 720-800, free 800-810, admit 850-950, nothing 810-850 and 950-1000
    assert read("device.idle_in_dispatch.chat", run) == pytest.approx(50 * us)
    assert read("device.idle_in_sync_emit.chat", run) == pytest.approx(110 * us)
    assert read("device.idle_in_admit.batch", run) == pytest.approx(100 * us)
    # between programs in no phase 50 + 40 + 50, and the span's edge 100
    assert read("device.idle_unattributed.batch", run) == pytest.approx(240 * us)
    four = sum(read(f"device.idle_{k}.chat", run) for k in
               ("in_sync_emit", "in_admit", "in_dispatch", "unattributed"))
    assert four == pytest.approx(read("device.idle_share.chat", run))
    assert four == pytest.approx(500 * us)
    out = capsys.readouterr().out
    assert "[bench] phase engine.block_sync: 0.0004s" in out
    assert ("in engine.admit between jit_paged_decode_multi_-_"
            "jit_paged_prefill_batch") in out


def test_idle_by_phase_reads_nothing_without_annotations(monkeypatch):
    run = hand_run(monkeypatch, annotated=False)
    for name in ("device.idle_in_sync_emit.chat", "device.idle_unattributed.chat"):
        assert read(name, run) is None
    assert read("device.idle_in_admit.chat", {"trace": None}) is None

    def gone():
        raise FileNotFoundError("no .xplane.pb")

    monkeypatch.setattr(idle_by_phase, "load_planes", gone)
    run.pop("idle_by_phase")
    assert read("device.idle_in_admit.chat", run) is None


def test_recorded_trace_shares_one_clock(monkeypatch):
    """Two requests on an idle tiny engine, recorded on a v5e chip: a
    prefill wave and three decode blocks each (8, 8 and 4 steps: the
    second is dispatched before the first comes back). In this file the
    device's stamps lie up to 0.8 ms before the host's — the first
    ``jit__threefry_split`` starts 0.74 ms before the ``engine.admit`` that
    dispatched it opens — which is how far "one clock" goes."""
    path = os.path.join(HERE, "recorded_trace_spans")
    if not os.path.isdir(path):
        pytest.skip("no recorded trace beside this file")
    monkeypatch.setattr(idle_by_phase, "find_xplane", lambda _: xplane.find_xplane(path))
    planes = idle_by_phase.load_planes()  # the readers' own loader
    phases = idle_by_phase.phase_intervals(planes)
    names = {p[2] for p in phases}
    assert {"engine.admit", "engine.prefill_sync", "engine.decode_dispatch",
            "engine.block_sync", "engine.emit", "engine.free"} <= names, names
    mods = sorted((ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
                   xplane.program_name(ev.name))
                  for plane in planes if xplane.DEVICE_PLANE.match(plane.name)
                  for line in plane.lines if line.name == xplane.MODULES_LINE
                  for ev in line.events)
    decodes = [m for m in mods if m[2] == "jit_paged_decode_multi"]
    dispatches = [p for p in phases if p[2] == "engine.decode_dispatch"]
    assert len(decodes) == len(dispatches) == 6
    for (host_start, host_end, _), (dev_start, _, _) in zip(dispatches, decodes):
        # the host opens the phase, copies the arrays, calls the program;
        # the device starts it after the phase opened and within 1 ms of
        # the call's return
        assert host_start < dev_start < host_end + 1e-3, (host_start, dev_start)
    # both planes cover the same stretch of the one clock: every program
    # ran between the first phase's start and the last phase's end, give
    # or take the millisecond above
    first, last = min(p[0] for p in phases), max(p[1] for p in phases)
    assert first - 1e-3 < mods[0][0] and mods[-1][1] < last
    assert last - first < 1.0  # seconds, not two unrelated epochs
    # and through the metric files: the device of this trace idles most of
    # the time (a tiny model), all of it found again by host phase
    busy = sum(e - s for s, e, _ in mods)
    window = mods[-1][1] - mods[0][0]
    run = {"trace": {"busy_s": busy, "window_s": window}, "trace_span_s": window}
    parts = [read(f"device.idle_{k}.chat", run) for k in
             ("in_sync_emit", "in_admit", "in_dispatch", "unattributed")]
    assert all(p is not None and p >= 0 for p in parts), parts
    assert sum(parts) == pytest.approx(read("device.idle_share.chat", run))
    assert parts[3] < 0.1 * sum(parts)  # under a tenth in no phase

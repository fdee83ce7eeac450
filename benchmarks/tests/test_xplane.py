"""The reduction from a profiler trace to busy time, program time, operation
time and idle gaps: on a trace written down by hand, and on a small trace
recorded on a v5e chip and kept beside this file."""
import os
from types import SimpleNamespace as NS

import pytest

from benchmarks.lib import xplane

HERE = os.path.dirname(os.path.abspath(__file__))


def ev(name, start_us, dur_us, **stats):
    return NS(name=name, start_ns=start_us * 1000, duration_ns=dur_us * 1000,
              stats=list(stats.items()))


def hand_trace():
    ops = NS(name="XLA Ops", events=[
        ev("%fusion.12", 0, 100), ev("%fusion.12", 100, 100),
        ev("%while.3", 300, 400), ev("%fusion.7", 300, 150),   # nested in the while
        ev("%copy.1", 1000, 50)])
    mods = NS(name="XLA Modules", events=[
        ev("jit_paged_decode_multi(123)", 0, 200),
        ev("jit_paged_prefill_batch(77)", 300, 400),
        ev("jit_paged_decode_multi(123)", 1000, 50)])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[ev("x", 0, 5000)])])
    return [NS(name="/device:TPU:0", lines=[ops, mods]), host]


def test_hand_trace():
    r = xplane.reduce_planes(hand_trace())
    # busy: 0-200, 300-700 (the while covers its body), 1000-1050 microseconds
    assert r["busy_s"] == pytest.approx(650e-6)
    assert r["window_s"] == pytest.approx(1050e-6)
    assert r["chips"] == 1
    assert r["programs"]["jit_paged_decode_multi"]["count"] == 2
    assert r["programs"]["jit_paged_decode_multi"]["seconds"] == pytest.approx(250e-6)
    assert r["programs"]["jit_paged_prefill_batch"]["durations"] == [
        pytest.approx(400e-6)]
    ops = dict(r["ops"])
    assert ops["fusion"] == pytest.approx(350e-6) and "while" not in ops
    gaps = dict(r["idle_gaps"])
    assert gaps["jit_paged_decode_multi_-_jit_paged_prefill_batch"] == pytest.approx(100e-6)
    assert gaps["jit_paged_prefill_batch_-_jit_paged_decode_multi"] == pytest.approx(300e-6)


def test_no_device_plane_reads_as_nothing():
    r = xplane.reduce_planes(hand_trace()[1:])
    assert r["busy_s"] == 0.0 and r["programs"] == {}


def test_names():
    assert xplane.program_name("jit_step(5417823)") == "jit_step"
    assert xplane.op_name(ev("%fusion.123", 0, 1)) == "fusion"
    assert xplane.op_name(ev(
        "%broadcast.4 = bf16[16,2048,8,4,128]{4,3,2,1,0:T(8,128)(2,1)} broadcast("
        "bf16[16,2048,8,128]{3,2,1,0} %x), dimensions={0,1,2,4}", 0, 1)
    ) == "broadcast:bf16_16_2048_8_4_128"
    assert xplane.op_name(ev(
        "%fusion.16 = (u32[1]{0:T(128)}, u32[1]{0:T(128)}) fusion(u32[2]{0} %key.1), "
        "kind=kLoop", 0, 1)) == "fusion:u32_1"
    assert xplane.op_name(ev(
        '%checkpoint.3 = bf16[64,4096,128]{2,1,0} custom-call(bf16[64,4096,128]{2,1,0} %q), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints={}', 0, 1)
    ) == "pallas:checkpoint:bf16_64_4096_128"
    assert xplane.op_name(ev("%compare_reduce_fusion = pred[]{:T(512)} fusion("
                             "f32[16]{0} %t)", 0, 1)) == "compare_reduce_fusion:pred"


def test_recorded_trace():
    path = os.path.join(HERE, "recorded_trace")
    if not os.path.isdir(path):
        pytest.skip("no recorded trace beside this file")
    r = xplane.reduce_trace(path)
    assert r["chips"] == 1 and 0 < r["busy_s"] <= r["window_s"]
    assert "jit_recorded_step" in r["programs"]
    assert r["programs"]["jit_recorded_step"]["count"] == 8

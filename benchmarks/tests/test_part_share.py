"""Device time by layer part (``lib/xplane_parts.py``, ``readers/
part_share.py``): on planes and a table written down by hand, as
``test_xplane.py`` feeds ``reduce_planes``."""
from types import SimpleNamespace as NS

import pytest

from benchmarks.lib import xplane, xplane_parts
from benchmarks.readers import part_share


def ev(name, start_us, dur_us):
    return NS(name=name, start_ns=start_us * 1000, duration_ns=dur_us * 1000,
              stats=[])


FFN = ("%fusion.12 = bf16[16,128]{1,0:T(8,128)(2,1)} fusion(bf16[16,64]{1,0} "
       "%x), kind=kOutput, calls=%fused_computation.3")
ATT = ('%paged.4 = bf16[16,8,128]{2,1,0} custom-call(bf16[16,8,128]{2,1,0} %q), '
       'custom_call_target="tpu_custom_call"')
TUPLE = ("%fusion.7 = (f32[16]{0:T(128)}, f32[16]{0:T(128)}) fusion(f32[16]{0} "
         "%a), kind=kLoop")
COPY = "%copy.1 = bf16[4,4]{1,0} copy(bf16[4,4]{0,1} %p)"


def planes():
    ops = NS(name="XLA Ops", events=[
        ev(FFN, 0, 100), ev(ATT, 100, 60),             # decode, first call
        ev("%while.3 = (s32[]) while((s32[]) %t)", 300, 400),  # a container
        ev(FFN, 300, 150), ev(TUPLE, 450, 250),        # prefill, inside it
        ev(FFN, 1000, 30), ev(COPY, 1030, 20),         # decode, second call
        ev(COPY, 2000, 10)])                           # outside any program
    mods = NS(name="XLA Modules", events=[
        ev("jit_decode(123)", 0, 200), ev("jit_prefill(77)", 300, 400),
        ev("jit_decode(123)", 1000, 50)])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[ev("x", 0, 9)])])
    return [NS(name="/device:TPU:0", lines=[ops, mods]), host]


TABLES = {
    "jit_decode": {"parts": {"fusion.12|bf16[16,128]": "ffn",
                             "paged.4|bf16[16,8,128]": "attention"},
                   "stale": False, "variants": 2, "seconds": 0.01},
    # two shape variants of the prefill gave fusion.12 different parts
    "jit_prefill": {"parts": {"fusion.12|bf16[16,128]": "?",
                              "fusion.7|f32[16]": "router"},
                    "stale": False, "variants": 2, "seconds": 0.02},
}


def test_event_keys():
    assert xplane_parts.event_key(FFN) == ("fusion.12|bf16[16,128]", False)
    assert xplane_parts.event_key(TUPLE) == ("fusion.7|f32[16]", False)
    assert xplane_parts.event_key(
        "%while.3 = (s32[]) while((s32[]) %t)")[1] is True
    assert xplane_parts.event_key("%call = f32[2]{0} call(f32[2]{0} %a)")[1]
    assert xplane_parts.event_key("%fusion.9") == ("fusion.9|", False)


def test_events_go_to_the_program_that_holds_them_in_time():
    s = xplane_parts.part_seconds(planes(), TABLES)["seconds"]
    assert s[("jit_decode", "ffn")] == pytest.approx(130e-6)   # both calls
    assert s[("jit_decode", "attention")] == pytest.approx(60e-6)
    assert s[("jit_prefill", "router")] == pytest.approx(250e-6)
    # the same instruction name in the other program is the other table's
    assert s[("jit_prefill", "?")] == pytest.approx(150e-6)
    assert s[("jit_decode", "unnamed")] == pytest.approx(20e-6)
    assert s[(xplane_parts.NO_PROGRAM, "unnamed")] == pytest.approx(10e-6)
    # the container is skipped: its body's operations are counted themselves
    assert sum(s.values()) == pytest.approx(620e-6)


def _run(tables):
    trace = xplane.reduce_planes(planes())
    run = {"trace": trace, "counters": {"after": {"steps": 1}}}
    if tables is not None:
        run["counters"]["after"]["program_parts"] = tables
    return run


def test_shares_add_up_with_unnamed_to_the_busy_time(monkeypatch, capsys):
    monkeypatch.setattr(part_share, "load_planes", planes)
    run = _run(TABLES)
    assert run["trace"]["busy_s"] == pytest.approx(620e-6)
    named = part_share.read(run, ["ffn", "attention", "router"])
    rest = part_share.read(run, ["unnamed", "?"])
    assert named == pytest.approx(100 * 440 / 620)
    assert rest == pytest.approx(100 * 180 / 620)
    assert named + rest == pytest.approx(100.0)
    assert part_share.read(run, ["ffn"], ["decode"]) == pytest.approx(
        100 * 130 / 620)
    assert part_share.read(run, ["ffn"], ["prefill"]) == 0.0
    out = capsys.readouterr().out
    assert out.count("[bench] parts of jit_decode") == 1  # printed once
    assert "unnamed jit_decode copy:bf16_4_4" in out


def test_two_variants_that_disagree_and_a_stale_program_read_as_unnamed(
        monkeypatch):
    monkeypatch.setattr(part_share, "load_planes", planes)
    stale = {**TABLES, "jit_decode": {"parts": {}, "stale": True,
                                      "variants": 2, "seconds": 0.01}}
    run = _run(stale)
    # a named part of a stale program is not known: nothing, never 0
    assert part_share.read(run, ["ffn", "attention"]) is None
    assert part_share.read(run, ["ffn"], ["decode"]) is None
    assert part_share.read(run, ["router"], ["prefill"]) == pytest.approx(
        100 * 250 / 620)
    # decode's 210 us, prefill's ambiguous 150 us, the 10 us outside
    assert part_share.read(run, ["unnamed", "?"]) == pytest.approx(
        100 * 370 / 620)


def test_a_run_without_a_table_reads_nothing(monkeypatch):
    def never():
        raise AssertionError("no table: the trace is not opened")

    monkeypatch.setattr(part_share, "load_planes", never)
    assert part_share.read(_run(None), ["ffn"]) is None
    assert part_share.read({"trace": None}, ["ffn"]) is None
    assert part_share.read({"trace": {"busy_s": 0.0}}, ["ffn"]) is None

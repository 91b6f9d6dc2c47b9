"""The trace reduction: busy union, idle share, kernel time by name, on
hand-made events and on a small trace recorded on a TPU v5e."""

from __future__ import annotations

import json
import pathlib
import sys
from types import SimpleNamespace

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import roofline, run, trace  # noqa: E402

RECORDED = pathlib.Path(__file__).parent / "data" / "trace_small.json"

BID = ("%vmap_jit_bid_top2_pallas__.54 = (f32[8,8,1]{2,1,0:T(8,128)S(1)}, "
       "s32[8,8,1]{2,1,0:T(8,128)S(1)}, f32[8,8,1]{2,1,0:T(8,128)S(1)}) "
       "custom-call(f32[8,8,192]{2,1,0:T(8,128)S(1)} %get-tuple-element.2036,"
       " f32[8,128,192]{2,1,0:T(8,128)S(1)} %pad.197, f32[8,1,128]{2,1,0:T(1,"
       "128)S(1)} %bitcast.565, f32[8,1,128]{2,1,0:T(1,128)S(1)} %pad.198), "
       "custom_call_target=\"tpu_custom_call\"")
BID1 = ("%vmap_jit_bid_top2_pallas__.57 = (f32[8,1]{1,0:T(8,128)S(1)}, "
        "s32[8,1]{1,0:T(8,128)S(1)}, f32[8,1]{1,0:T(8,128)S(1)}) custom-call("
        "f32[8,192]{1,0:T(8,128)S(1)} %bitcast.551, f32[128,192]{1,0:T(8,128)"
        "S(1)} %pad_bitcast_fusion.13, f32[1,128]{1,0:T(1,128)S(1)} "
        "%bitcast.559, f32[1,128]{1,0:T(1,128)S(1)} %pad.211), "
        "custom_call_target=\"tpu_custom_call\"")
GATHER = ("%jit_gather_rows_pallas_.3 = f32[8192,192]{1,0:T(8,128)} "
          "custom-call(s32[8192]{0:T(1024)} %idx, f32[65536,1,256]{2,1,0:T(1,"
          "128)} %table), custom_call_target=\"tpu_custom_call\"")
WHILE = ("%while.184 = (s32[]{:T(128)}, f32[1,8,192]{2,1,0:T(8,128)S(1)}) "
         "while((s32[]{:T(128)}, f32[1,8,192]{2,1,0:T(8,128)S(1)}) %tuple.1),"
         " condition=%region_42, body=%region_8")
FUSION = ("%fusion.283 = s32[8,8]{1,0:T(8,128)} fusion(s32[8,8]{1,0:T(8,128)}"
          " %fusion.280), kind=kCustom, calls=%fused_computation.21")

# a while loop enclosing everything, a fusion, two bid calls (one stacked
# G=8, one flat) overlapping the fusion, and a gather; device 1 half busy
EVENTS = {"devices": {
    "0": [[WHILE, 0, 1000],
          [FUSION, 100, 200],      # 100..300
          [BID, 250, 150],         # 250..400 (overlaps the fusion)
          [BID1, 600, 100],        # 600..700
          [GATHER, 800, 100]],     # 800..900
    "1": [[FUSION, 100, 400],      # 100..500
          [FUSION, 500, 400]]}}    # 500..900


def test_control_flow_is_not_an_op():
    assert trace.is_control(WHILE)
    assert not trace.is_control(BID) and not trace.is_control(FUSION)
    assert trace.op_name(BID) == "vmap_jit_bid_top2_pallas__.54"


def test_busy_union_and_idle_share():
    red = trace.Reduced(EVENTS)
    assert red.window_ns == (100, 900)
    assert red.busy_s["0"] == pytest.approx(500e-9)  # 100..400,600..700,800..900
    assert red.busy_s["1"] == pytest.approx(800e-9)
    assert red.mean_busy_s == pytest.approx(650e-9)
    idle = run.load_reader("device.scan_idle_pct").read(SimpleNamespace(trace=red))
    assert idle == pytest.approx(100 * (1 - 650 / 800))


def test_idle_gaps_and_top_ops():
    red = trace.Reduced(EVENTS)
    gaps = red.breakdown()["idle_gaps"]
    assert gaps[0] == ["after vmap_jit_bid_top2_pallas__.54", 200e-9]
    assert gaps[1] == ["after vmap_jit_bid_top2_pallas__.57", 100e-9]
    ops = dict(red.breakdown()["device_ops"])
    assert ops["fusion.283"] == pytest.approx(200e-9)
    assert "while.184" not in ops


def test_kernel_time_by_name_and_shapes():
    red = trace.Reduced(EVENTS)
    bid = trace.kernel_calls(red.all_ops(), "bid_top2")
    assert [(c[1][0], c[2]) for c in bid] == [((8, 8, 192), 150e-9),
                                               ((8, 192), 100e-9)]
    gather = trace.kernel_calls(red.all_ops(), "gather")
    assert gather == [([(8192, 192)], [(65536, 1, 256)], 100e-9)]


def test_kernel_rooflines_from_the_events():
    red = trace.Reduced(EVENTS)
    r = SimpleNamespace(trace=red, peaks=roofline.peaks("TPU v5 lite"))
    ops = 2 * 8 * 8 * 8 * 192 + 2 * 8 * 8 * 192
    nbytes = 4 * (8 * (8 * 192 * 2 + 16 + 24) + (8 * 192 * 2 + 16 + 24))
    want = 100 * max(ops / 197e12, nbytes / 819e9) / 250e-9
    got = run.load_reader("bid_top2_roofline").read(r)
    assert got == pytest.approx(want)
    g = run.load_reader("gather_roofline").read(r)
    assert g == pytest.approx(100 * 4 * 8192 * (256 + 192 + 1) / 819e9
                              / 100e-9)


def test_no_kernel_event_gives_nothing():
    red = trace.Reduced({"devices": {"0": [[FUSION, 0, 10]]}})
    r = SimpleNamespace(trace=red, peaks=roofline.peaks("TPU v5 lite"))
    assert run.load_reader("bid_top2_roofline").read(r) is None
    assert run.load_reader("gather_roofline").read(r) is None


def test_recorded_trace():
    """1.5 ms of a streamed (16, 16) solve traced on one TPU v5e (op names
    kept, other op text cut)."""
    compact = json.loads(RECORDED.read_text())
    red = trace.Reduced(compact)
    assert list(red.ops) == ["0"]
    assert red.window_s == pytest.approx(0.001500188)
    assert red.mean_busy_s == pytest.approx(0.001314603)
    bid = trace.kernel_calls(red.all_ops(), "bid_top2")
    assert len(bid) == 124 and all(dt > 0 for *_s, dt in bid)
    assert {c[1][0] for c in bid} <= {(16, 192), (16, 16, 192)}
    share = run.load_reader("bid_top2_roofline").read(
        SimpleNamespace(trace=red, peaks=roofline.peaks("TPU v5 lite")))
    assert 0 < share < 100
    bd = red.breakdown()
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) == 10
    assert sum(s for _n, s in bd["idle_gaps"]) <= red.window_s
    # one sort per batch step: four in the slice, 0.354538 ms apart
    assert run.load_reader("lap.batch_ms").read(
        SimpleNamespace(trace=red)) == pytest.approx(
            (1334127 - 270512) / 3 / 1e6)


SORT = ("%sort.75 = (pred[1,16]{1,0:T(2,128)(4,1)}, s32[1,16]{1,0:T(1,128)})"
        " sort(pred[1,16]{1,0:T(2,128)(4,1)} %fusion.9, s32[1,16]{1,0:"
        "T(1,128)} %iota.3), dimensions={1}, is_stable=true")


@pytest.mark.parametrize("starts,ms", [
    ([0, 1_000_000, 2_000_000, 3_000_000], 1.0),   # evenly spaced
    ([500, 1_500, 4_500], 0.002),                   # uneven: the mean step
    ([0, 1_000_000], None),                         # too few marks
    ([], None),                                     # no sort in the slice
])
def test_batch_steps_marked_by_the_sort(starts, ms):
    """The sort ending each batch LAP marks the steps; the fusions between
    them and a while loop around them are no marks."""
    evs = [[WHILE, 0, 5_000_000]]
    for s in starts:
        evs += [[FUSION, s - 300, 200], [SORT, s, 100]]
    red = trace.Reduced({"devices": {"0": evs + [[FUSION, 0, 10]]}})
    got = run.load_reader("lap.batch_ms").read(SimpleNamespace(trace=red))
    assert got == (None if ms is None else pytest.approx(ms))

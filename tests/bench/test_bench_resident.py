"""``lap.resident_busy_pct``: the resident auction kernel's share of the
traced slice's busy device time, on hand-made events."""

from __future__ import annotations

import pathlib
import sys
from types import SimpleNamespace

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run, trace  # noqa: E402

RESIDENT = ("%resident_auction.7 = (s32[1,512,1]{2,1,0:T(8,128)}, "
            "f32[1,1,512]{2,1,0:T(1,128)}, s32[1,1,128]{2,1,0:T(1,128)}) "
            "custom-call(f32[1,1,128]{2,1,0:T(1,128)} %broadcast.3, "
            "f32[1,512,512]{2,1,0:T(8,128)} %cost, f32[1,1,512]{2,1,0:T(1,"
            "128)} %pad.4, s32[1,512,1]{2,1,0:T(8,128)} %pad.5), "
            "custom_call_target=\"tpu_custom_call\"")
FUSION = ("%fusion.12 = s32[1,512]{1,0:T(1,128)} fusion(s32[1,512]{1,0:"
          "T(1,128)} %p), kind=kLoop, calls=%fused_computation.3")
SORT = ("%sort.75 = (pred[1,512]{1,0:T(2,128)(4,1)}, s32[1,512]{1,0:"
        "T(1,128)}) sort(pred[1,512]{1,0:T(2,128)(4,1)} %fusion.9, "
        "s32[1,512]{1,0:T(1,128)} %iota.3), dimensions={1}, is_stable=true")
WHILE = ("%while.9 = (s32[]{:T(128)}) while((s32[]{:T(128)}) %tuple.1), "
         "condition=%region_1, body=%region_2")


def _read(devices):
    red = trace.Reduced({"devices": devices})
    return run.load_reader("lap.resident_busy_pct").read(
        SimpleNamespace(trace=red))


@pytest.mark.parametrize("devices,pct", [
    # two kernel calls of 400 ns in 1,000 ns busy (a gap of 200 left out)
    ({"0": [[WHILE, 0, 2000], [RESIDENT, 0, 400], [FUSION, 400, 100],
            [RESIDENT, 700, 400], [SORT, 1100, 100]]}, 80.0),
    # a fusion overlapping the kernel counts once in the busy union
    ({"0": [[RESIDENT, 0, 500], [FUSION, 250, 500]]}, 500 / 750 * 100),
    # two chips: kernel time over the busy time of both
    ({"0": [[RESIDENT, 0, 300], [SORT, 300, 100]],
      "1": [[FUSION, 0, 400]]}, 300 / 800 * 100),
])
def test_resident_share_of_busy_time(devices, pct):
    assert _read(devices) == pytest.approx(pct)


def test_no_resident_event_gives_nothing():
    """The XLA round loop (the parent, or n past the VMEM rule) runs no
    such kernel: the metric is left out, not read as 0."""
    assert _read({"0": [[FUSION, 0, 100], [SORT, 100, 50]]}) is None

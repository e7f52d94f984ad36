"""The reduction from a profiler trace to the per-layer numbers: on a
hand-made trace whose answers are worked out below, and on traces
recorded on a TPU v5e (fixtures/), against a plain re-computation."""
from __future__ import annotations

import gzip
import json

import pytest

from chipbench_tiny import CHIP
from records import Run
from trace_reduce import Trace, segmin_roofline_pct, shapes_of

PEAKS = {"hbm_bytes_per_s": 819e9}
SCATTER = ("%fusion.1 = s32[1024]{0:T(1024)} fusion(s32[4096]{0:T(1024)} %a, "
           "s32[4096]{0:T(1024)} %b, s32[]{:T(128)} %constant.3), kind=kCustom, "
           "calls=%fused_computation.1")
GATHER = ("%fusion.2 = f32[4096]{0} fusion(f32[1024]{0} %m, s32[4096]{0} %i), "
          "kind=kCustom, calls=%fused_computation.2")
PALLAS = ('%segment_min_flat.6 = s32[8,128]{1,0:T(8,128)} custom-call(s32[16,128]{1,0} '
          '%k, s32[16,128]{1,0} %s), custom_call_target="tpu_custom_call"')
HAND = {
    "window": [1000, 11000],
    "device": {"0": [
        ["%while.1 = (s32[4]) while((s32[4]) %t), condition=%c, body=%b", 1000, 9000],
        [SCATTER, 2000, 1000],
        [GATHER, 2500, 1000],
        [PALLAS, 6000, 2000],
        ["%copy.1 = s32[4]{0} copy(s32[4]{0} %x)", 10500, 1000],
    ]},
    "host": [
        ["bench.window", 1000, 10000, "python"],
        ["bench.solve", 1500, 8000, "python"],
        ["PjitFunction(step)", 3600, 2300, "python"],
        ["tiny", 4000, 10, "main"],
    ],
}


def _run(trace):
    return Run(cell={}, config={}, traffic={}, seed=0, setup_s=0.0, window_s=1.0,
               ops=[], facts={}, trace=trace, peaks=PEAKS)


def test_hand_trace_busy_idle():
    tr = Trace(HAND)
    # [2000,3500] from the overlapping pair, [6000,8000], and the copy
    # clipped to the window's end; the while's span does not count
    assert tr.busy_ns() == 1500 + 2000 + 500
    assert tr.idle_share() == pytest.approx(0.6)
    assert tr.window_s == pytest.approx(1e-5)
    assert tr.busy_ns(6500, 7000) == 500


def test_hand_trace_kernel_and_roofline():
    tr = Trace(HAND)
    events = tr.kernel_events("segmin")
    assert [e[0] for e in events] == [SCATTER, PALLAS]
    assert shapes_of(SCATTER) == [1024, 4096, 4096, 1]
    least_s = ((8 * 4096 + 4 * 1024) + (8 * 2048 + 4 * 1024)) / 819e9
    assert segmin_roofline_pct(_run(tr)) == pytest.approx(100 * least_s / 3000e-9)


def test_hand_trace_breakdown():
    b = Trace(HAND).breakdown()
    assert [n for n, _ in b["device_ops"]][0].startswith("%segment_min_flat.6 = s32[8,128]")
    assert [d for _, d in b["device_ops"]] == pytest.approx([2e-6, 1e-6, 1e-6])
    assert b["idle_gaps"] == [["bench.solve/PjitFunction(step)", pytest.approx(2.5e-6)],
                              ["bench.solve", pytest.approx(2.5e-6)],
                              ["bench.solve", pytest.approx(1e-6)]]


def test_no_events_no_roofline():
    raw = dict(HAND, device={"0": [[GATHER, 2500, 1000]]})
    assert segmin_roofline_pct(_run(Trace(raw))) is None
    assert segmin_roofline_pct(_run(None)) is None


def _plain_busy(raw):
    """Busy time by walking the sorted, clipped leaf intervals."""
    t0, t1 = raw["window"]
    iv = sorted((max(s, t0), min(s + d, t1)) for n, s, d in raw["device"]["0"]
                if not n.startswith(("%while", "%conditional", "%call")))
    busy, edge = 0, t0
    for s, e in iv:
        s = max(s, edge)
        if e > s:
            busy += e - s
            edge = e
    return busy


RECORDED = sorted((CHIP / "fixtures").glob("trace_*.json.gz"))


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.name)
def test_recorded_trace(path):
    raw = json.loads(gzip.decompress(path.read_bytes()))
    tr = Trace(raw)
    assert tr.busy_ns() == _plain_busy(raw)
    assert 0.0 < tr.idle_share() < 1.0
    b = tr.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    pct = segmin_roofline_pct(_run(tr), slots=raw.get("slots"),
                              segments=raw.get("segments"))
    if raw.get("expect_segmin"):
        assert 0.0 < pct < 100.0

"""The readers of the program's own spans: on a hand-made trace whose
answers are worked out below, and on traces recorded on a TPU v5e
(fixtures/trace_spans_*), against a plain walk over the events."""
from __future__ import annotations

import gzip
import json

import numpy as np
import pytest

from chipbench_tiny import CHECKOUT, CHIP
from records import Op, Run
from registry import Registry
from spans import percentile_ms, seconds_per_solve, union_share_pct
from trace_reduce import Trace

READERS = Registry(json.loads((CHECKOUT / "BENCHMARK.json").read_text()))


def _run(raw, solves=0):
    ops = [Op("solve", 0.0, 0.0, 1.0, True, 1) for _ in range(solves)]
    return Run(cell={}, config={}, traffic={}, seed=0, setup_s=0.0, window_s=1.0,
               ops=ops, facts={}, trace=Trace(raw), peaks={})


HAND = {
    "window": [1000, 11000],
    "device": {"0": []},
    "host": [
        ["bench.window", 1000, 10000, "python"],
        ["coarsen.canonical", 500, 1500, "python"],  # clipped to [1000, 2000]
        ["coarsen.level", 2000, 3000, "python"],
        ["coarsen.finalize", 9000, 1000, "python"],
        ["serve.flush", 4000, 2000, "python"],
        ["serve.respond", 5000, 2000, "python"],  # overlaps the flush by 1000
        ["stream.query", 7000, 100, "serve-query"],
        ["stream.query", 7200, 300, "serve-query"],
        ["stream.query", 10900, 500, "serve-query"],  # clipped to 100
    ],
}


def test_hand_trace_span_readers():
    run = _run(HAND, solves=2)
    assert seconds_per_solve(run, {"coarsen.canonical", "coarsen.finalize"}) == \
        pytest.approx((1000 + 1000) / 1e9 / 2)
    assert seconds_per_solve(run, {"coarsen.level"}) == pytest.approx(3000 / 1e9 / 2)
    assert union_share_pct(run.trace, {"serve.flush", "serve.respond"}) == \
        pytest.approx(100 * 3000 / 10000)
    assert percentile_ms(run.trace, {"stream.query"}, 50) == pytest.approx(100 / 1e6)
    # a program without the spans, or a window without solves: nothing to read
    assert seconds_per_solve(run, {"solve.fetch"}) is None
    assert seconds_per_solve(_run(HAND, solves=0), {"coarsen.level"}) is None
    assert union_share_pct(run.trace, {"serve.decode"}) is None
    assert percentile_ms(None, {"stream.query"}, 95) is None


def _fixture(cell):
    path = CHIP / "fixtures" / f"trace_spans_{cell}.json.gz"
    return json.loads(gzip.decompress(path.read_bytes()))


def _clipped(raw, names):
    t0, t1 = raw["window"]
    return [(max(s, t0), min(s + d, t1)) for n, s, d, *_ in raw["host"]
            if n in names and min(s + d, t1) > max(s, t0)]


def _solves(raw):
    t0, t1 = raw["window"]
    return sum(1 for n, s, d, *_ in raw["host"]
               if n == "bench.solve" and s >= t0 and s + d <= t1)


@pytest.mark.parametrize("metric, cell, names, lo, hi", [
    ("coarsen_host_s.solve", "kron20-coarsen",
     {"coarsen.canonical", "coarsen.finalize"}, 0.01, 3.0),
    ("levels_s.solve", "kron20-coarsen", {"coarsen.level"}, 1.0, 9.0),
    ("fetch_s.solve", "kron20-flat", {"solve.fetch"}, 1e-5, 1.0),
])
def test_recorded_per_solve_spans(metric, cell, names, lo, hi):
    raw = _fixture(cell)
    solves = _solves(raw)
    assert solves >= 1
    got = READERS.reader("per_layer", metric)(_run(raw, solves))
    want = sum(e - s for s, e in _clipped(raw, names)) / 1e9 / solves
    assert got == pytest.approx(want)
    assert lo < got < hi


def test_recorded_answer_p95():
    raw = _fixture("stream20-read")
    got = READERS.reader("per_layer", "answer_p95_ms.read")(_run(raw))
    durs = sorted(e - s for s, e in _clipped(raw, {"stream.query"}))
    assert len(durs) >= 20
    assert got == pytest.approx(float(np.percentile(durs, 95)) / 1e6)
    assert durs[0] / 1e6 <= got <= durs[-1] / 1e6


def test_recorded_loop_busy_share():
    raw = _fixture("stream20-read")
    got = READERS.reader("per_layer", "loop_busy_share.read")(_run(raw))
    # a plain sweep: sorted intervals, each counted past the last edge
    busy, edge = 0, raw["window"][0]
    for s, e in sorted(_clipped(raw, {"serve.decode", "serve.flush",
                                      "serve.respond"})):
        s = max(s, edge)
        if e > s:
            busy += e - s
            edge = e
    t0, t1 = raw["window"]
    assert got == pytest.approx(100.0 * busy / (t1 - t0))
    assert 0.0 < got < 100.0

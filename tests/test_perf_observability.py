"""Tier-1 coverage of the continuous-performance-observability stack
(DESIGN.md §11): the structured ``Measurement`` bench schema, the
append-only history store, the regression sentinel's decision rule
(including the acceptance gate — an injected synthetic 2x slowdown must
fail), analytic ``SolveReport.cost`` on flat/fused plans with obs off,
the MicroBatcher admission metrics, and an open-loop loadgen smoke run
with a concurrently mutating graph."""
from __future__ import annotations

import copy
import json
import sys
from pathlib import Path

import numpy as np
import pytest

_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_ROOT))  # benchmarks/ namespace package


def _sentinel():
    sys.path.insert(0, str(_ROOT / "tools"))
    try:
        import check_bench_regression as m
    finally:
        sys.path.pop(0)
    return m


def _doc(medians: dict, *, iqr=0.0, backend="cpu", devices=1, unit="us"):
    from benchmarks.common import Measurement, document

    rows = [
        Measurement(name=k, median=v, iqr=iqr, min=v, max=v, iters=3,
                    unit=unit)
        for k, v in medians.items()
    ]
    doc = document(rows)
    doc["env"]["backend"] = backend
    doc["env"]["device_count"] = devices
    doc["backend"], doc["device_count"] = backend, devices
    return doc


# ---------------------------------------------------------------------------
# Measurement / bench-rows/v2 schema
# ---------------------------------------------------------------------------


def test_measurement_from_samples_stats_and_csv_compat():
    from benchmarks.common import from_samples

    m = from_samples("t", [1e-3, 2e-3, 3e-3, 4e-3], warmup=2,
                     derived="k=v")
    assert m.unit == "us" and m.iters == 4 and m.warmup == 2
    assert m.median == pytest.approx(2500.0)
    assert m.min == pytest.approx(1000.0) and m.max == pytest.approx(4000.0)
    assert m.iqr == pytest.approx(1500.0)  # q75(3250) - q25(1750)
    # printed row stays v1-CSV shaped: name,us,derived
    assert str(m) == "t,2500.0,k=v"
    # ``per`` divides each sample (per-call reporting)
    assert from_samples("t", [2e-3], per=2).median == pytest.approx(1000.0)
    with pytest.raises(ValueError):
        from_samples("t", [])


def test_document_schema_and_write_json(tmp_path):
    from benchmarks.common import SCHEMA, point, write_json

    rows = [point("speedup", 3.5, "x", derived="a=b")]
    p = tmp_path / "BENCH_x.json"
    write_json(str(p), rows)
    doc = json.loads(p.read_text())
    assert doc["schema"] == SCHEMA == "bench-rows/v2"
    for key in ("jax", "backend", "device_count", "python", "machine"):
        assert key in doc["env"]
    (r,) = doc["rows"]
    assert r["name"] == "speedup" and r["unit"] == "x"
    assert r["median"] == 3.5 and "metrics" not in r  # obs off -> dropped
    # names with commas survive (the v1 CSV schema corrupted them)
    from benchmarks.common import Measurement, document

    d2 = document([Measurement(name="a,b", median=1.0)])
    assert d2["rows"][0]["name"] == "a,b"


def test_measurement_carries_obs_snapshot_when_metrics_on():
    from benchmarks.common import point
    from repro import obs

    obs.enable("metrics")
    try:
        obs.metrics_reset()
        obs.counter("x.y").inc(3)
        m = point("p", 1.0, "count")
        assert m.metrics is not None
        assert m.metrics["counters"]["x.y"] == 3
        assert m.as_dict()["metrics"]["counters"]["x.y"] == 3
    finally:
        obs.disable()
        obs.metrics_reset()


# ---------------------------------------------------------------------------
# history store
# ---------------------------------------------------------------------------


def test_history_append_and_load_streams_by_env(tmp_path):
    from benchmarks import history

    d_cpu = _doc({"a": 100.0})
    d_tpu = _doc({"a": 5.0}, backend="tpu", devices=8)
    p1 = history.append(str(tmp_path), "suite one", d_cpu, timestamp=1.0)
    history.append(str(tmp_path), "suite one", d_cpu, timestamp=2.0)
    p2 = history.append(str(tmp_path), "suite one", d_tpu, timestamp=3.0)
    assert p1 != p2  # different env -> different stream by construction
    assert Path(p1).name == "suite_one__cpu__1.jsonl"
    got = history.load(str(tmp_path), "suite one", "cpu", 1)
    assert [d["ts"] for d in got] == [1.0, 2.0]
    assert got[0]["suite"] == "suite_one" or got[0]["suite"] == "suite one"
    assert history.load(str(tmp_path), "absent", "cpu", 1) == []


# ---------------------------------------------------------------------------
# regression sentinel: decision rule
# ---------------------------------------------------------------------------


def test_sentinel_passes_unchanged_and_fails_2x_slowdown():
    m = _sentinel()
    base = _doc({"solve": 1000.0, "stream": 50.0}, iqr=100.0)
    status, _ = m.check_doc(base, copy.deepcopy(base))
    assert status == "ok"
    slow = _doc({"solve": 2100.0, "stream": 50.0}, iqr=100.0)
    status, msgs = m.check_doc(base, slow)
    assert status == "regression"
    assert any("REGRESSION solve" in s for s in msgs)
    assert any("ok       stream" in s for s in msgs)


def test_sentinel_needs_both_gates():
    m = _sentinel()
    base = _doc({"b": 100.0}, iqr=30.0)
    # +40%: inside tolerance (50%) -> ok even though outside IQR
    assert m.check_doc(base, _doc({"b": 140.0}))[0] == "ok"
    # +60%: outside tolerance AND outside median+iqr=130 -> regression
    assert m.check_doc(base, _doc({"b": 160.0}))[0] == "regression"
    # +60% but baseline IQR 80 covers it (160 <= 180) -> noise, ok
    wide = _doc({"b": 100.0}, iqr=80.0)
    assert m.check_doc(wide, _doc({"b": 160.0}))[0] == "ok"
    # tighter tolerance flips the +40% case
    assert m.check_doc(base, _doc({"b": 140.0}), tolerance=0.1)[0] == (
        "regression"
    )


def test_sentinel_skips_env_mismatch_and_ignores_non_time_rows():
    m = _sentinel()
    base = _doc({"b": 100.0})
    status, msgs = m.check_doc(base, _doc({"b": 500.0}, devices=8))
    assert status == "env-skip" and "env mismatch" in msgs[0]
    status, _ = m.check_doc(base, _doc({"b": 500.0}, backend="tpu"))
    assert status == "env-skip"
    # speedup rows are provenance, not gates — a 10x change passes
    s_base = _doc({"speedup": 8.0}, unit="x")
    assert m.check_doc(s_base, _doc({"speedup": 0.8}, unit="x"))[0] == "ok"


def test_sentinel_reports_new_and_gone_rows_without_failing():
    m = _sentinel()
    status, msgs = m.check_doc(_doc({"a": 1.0}), _doc({"b": 2.0}))
    assert status == "ok"
    assert any("new-row" in s and "b" in s for s in msgs)
    assert any("gone-row" in s and "a" in s for s in msgs)


# ---------------------------------------------------------------------------
# regression sentinel: CLI (first-run, --update, exit codes)
# ---------------------------------------------------------------------------


def test_sentinel_cli_first_run_update_then_2x_fails(tmp_path):
    m = _sentinel()
    bdir, cdir = tmp_path / "baselines", tmp_path / "run"
    cdir.mkdir()
    cur = _doc({"solve": 1000.0}, iqr=50.0)
    (cdir / "BENCH_solve_smoke.json").write_text(json.dumps(cur))

    # first-run without --update: pass, no baseline written
    assert m.main(["--baseline", str(bdir), "--current", str(cdir)]) == 0
    assert not (bdir / "solve_smoke.json").exists()
    # --update creates it (BENCH_ prefix stripped)
    assert m.main(["--baseline", str(bdir), "--current", str(cdir),
                   "--update"]) == 0
    assert (bdir / "solve_smoke.json").exists()
    # unchanged run passes
    assert m.main(["--baseline", str(bdir), "--current", str(cdir)]) == 0
    # the acceptance gate: synthetic 2x slowdown must exit 1
    slow = copy.deepcopy(cur)
    for r in slow["rows"]:
        r["median"] *= 2.1
    (cdir / "BENCH_solve_smoke.json").write_text(json.dumps(slow))
    assert m.main(["--baseline", str(bdir), "--current", str(cdir)]) == 1
    # usage errors exit 2
    assert m.main(["--baseline", str(bdir)]) == 2
    assert m.main(["--baseline", str(bdir), "--current", str(cdir),
                   "--tolerance", "-1"]) == 2


def test_sentinel_cli_history_appends(tmp_path):
    m = _sentinel()
    from benchmarks import history

    bdir, cdir, hdir = (tmp_path / d for d in ("b", "c", "h"))
    cdir.mkdir()
    (cdir / "BENCH_s.json").write_text(json.dumps(_doc({"a": 1.0})))
    m.main(["--baseline", str(bdir), "--current", str(cdir),
            "--update", "--history", str(hdir)])
    m.main(["--baseline", str(bdir), "--current", str(cdir),
            "--history", str(hdir)])
    assert len(history.load(str(hdir), "s", "cpu", 1)) == 2


def test_committed_baselines_match_sentinel_naming():
    """Every committed baseline must be loadable and carry the env the
    CI job that produces its BENCH_ file runs under."""
    m = _sentinel()
    bdir = _ROOT / "benchmarks" / "baselines"
    files = sorted(bdir.glob("*.json")) if bdir.exists() else []
    assert files, "no committed baselines under benchmarks/baselines"
    for p in files:
        doc = m._load(str(p))
        backend, devices = m._env(doc)
        assert backend == "cpu" and devices in (1, 8), p.name
        assert m._time_rows(doc), f"{p.name}: no time rows to gate on"


# ---------------------------------------------------------------------------
# SolveReport.cost (acceptance: flops > 0 for flat and fused, obs off)
# ---------------------------------------------------------------------------


def test_plan_cost_flat_and_fused_with_obs_off():
    from repro import obs
    from repro.coarsen.config import CoarsenConfig
    from repro.graphs.generators import random_graph
    from repro.solve import SolveSpec, plan

    assert not obs.metrics_active()
    g = random_graph(64, 256, seed=7)
    p_flat = plan(g, SolveSpec())
    rep = p_flat.solve()
    c = rep.cost
    assert c is not None and c.analyzed == "flat"
    assert c.flops > 0 and c.bytes > 0
    assert c.flops == c.dot_flops + c.ew_flops
    assert p_flat.cost is c  # plan exposes the same analysis
    # cached plan for the same (spec, shape) reuses the memoized cost
    assert plan(g, SolveSpec()).solve().cost is c

    cfg = CoarsenConfig(cutoff=16, fused=True)
    p_fused = plan(g, SolveSpec(mode="coarsen", coarsen=cfg))
    cf = p_fused.solve().cost
    assert cf is not None and cf.analyzed == "coarsen.level0.fused"
    assert cf.flops > 0 and cf.bytes > 0


def test_plan_cost_absent_for_stream_mode():
    from repro.solve import SolveSpec, plan

    p = plan(64, SolveSpec(mode="stream", batch_capacity=64))
    assert p.cost is None
    u, v = np.asarray([0, 1]), np.asarray([2, 3])
    rep = p.update(u, v, np.asarray([1.0, 2.0]))
    assert rep.cost is None


# ---------------------------------------------------------------------------
# MicroBatcher admission metrics
# ---------------------------------------------------------------------------


def test_microbatcher_obs_counters_and_gauge():
    from repro import obs
    from repro.solve import SolveSpec, plan
    from repro.stream.service import MicroBatcher, QueryService

    p = plan(32, SolveSpec(mode="stream", batch_capacity=64))
    u = np.arange(31, dtype=np.int32)
    p.update(u, u + 1, np.ones(31))  # a path: everything connected

    obs.enable("metrics")
    try:
        obs.metrics_reset()
        svc = QueryService(p.engine.snapshots)
        b = MicroBatcher(svc, max_queue=4)
        for i in range(9):  # 2 overflow auto-flushes + 1 open query
            b.ask_connected(i % 32, (i + 1) % 32)
        snap = obs.metrics_snapshot()
        assert snap["counters"]["stream.batcher.overflow"] == 2
        assert snap["counters"]["stream.batcher.flush"] == 2
        assert snap["counters"]["stream.batcher.flushed_queries"] == 8
        assert snap["gauges"]["stream.batcher.queue_depth"] == 1
        b.flush()
        snap = obs.metrics_snapshot()
        assert snap["counters"]["stream.batcher.flush"] == 3
        assert snap["counters"]["stream.batcher.flushed_queries"] == 9
        assert snap["gauges"]["stream.batcher.queue_depth"] == 0
    finally:
        obs.disable()
        obs.metrics_reset()


def test_microbatcher_silent_when_obs_off():
    from repro import obs
    from repro.solve import SolveSpec, plan
    from repro.stream.service import MicroBatcher, QueryService

    obs.metrics_reset()
    p = plan(16, SolveSpec(mode="stream", batch_capacity=16))
    p.update(np.asarray([0, 1]), np.asarray([1, 2]), np.ones(2))
    b = MicroBatcher(QueryService(p.engine.snapshots), max_queue=2)
    b.ask_connected(0, 1)
    b.ask_connected(0, 2)  # auto-flush
    assert b.result((0, 0)) is True
    snap = obs.metrics_snapshot()
    assert "stream.batcher.overflow" not in snap["counters"]
    assert "stream.batcher.queue_depth" not in snap["gauges"]


# ---------------------------------------------------------------------------
# loadgen smoke: open loop against a concurrently mutating graph
# ---------------------------------------------------------------------------


def test_loadgen_smoke_slo_report(tmp_path):
    from repro import obs
    from repro.launch import loadgen

    out = tmp_path / "SLO_smoke.json"
    try:
        rc = loadgen.main([
            "--qps", "120", "--duration", "1.5", "--scale", "8",
            "--micro-batch", "32", "--writer-batch", "256",
            "--seed", "0", "--out", str(out),
            # lenient targets: this asserts mechanism, not machine speed
            "--slo-p50-ms", "5000", "--slo-p99-ms", "20000",
            "--max-drop-frac", "0.9", "--min-qps-frac", "0.01",
        ])
    finally:
        obs.disable()
        obs.metrics_reset()
    assert rc == 0
    d = json.loads(out.read_text())
    assert d["schema"] == "slo-report/v1"
    q = d["queries"]
    assert q["answered"] > 0 and q["offered"] >= q["answered"]
    # open loop under a mutating graph: latency must be real, not zero
    lat = d["latency_ms"]
    assert lat["count"] == q["answered"]
    assert lat["p99"] >= lat["p95"] >= lat["p50"] > 0.0
    assert d["writer"]["updates"] > 0 and d["writer"]["snapshot_version"] > 0
    assert d["batcher"].get("flush", 0) > 0
    assert d["slo"]["passed"] and d["slo"]["failures"] == []
    assert d["achieved_qps"] > 0


def test_loadgen_exits_nonzero_on_missed_slo(tmp_path):
    from repro import obs
    from repro.launch import loadgen

    out = tmp_path / "SLO_fail.json"
    try:
        rc = loadgen.main([
            "--qps", "80", "--duration", "1.0", "--scale", "8",
            "--micro-batch", "32", "--out", str(out),
            "--slo-p50-ms", "0.000001",  # impossible target
        ])
    finally:
        obs.disable()
        obs.metrics_reset()
    assert rc == 1
    d = json.loads(out.read_text())
    assert not d["slo"]["passed"]
    assert any("p50" in f for f in d["slo"]["failures"])

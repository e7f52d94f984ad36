"""Observability (`repro.obs`) — spans on the profiler's clock, the
metrics registry, and the no-observer-effect contract (DESIGN.md §10).

Spans are TraceMe events: a ``jax.profiler`` session captured on the CPU
and read back with ``ProfileData`` must show each path's span names,
nested as the code nests them, and the same executables must give
bit-identical outputs with the profiler on and off.
"""
from __future__ import annotations

import glob

import numpy as np
import pytest

from repro import obs
from repro.graphs.generators import random_graph
from repro.graphs.structures import nx_free_n_components


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with obs off and an empty registry."""
    obs.disable()
    obs.metrics_reset()
    yield
    obs.disable()
    obs.metrics_reset()


def _profiled(tmp_path, fn):
    """``fn()`` under a ``jax.profiler`` session (Python tracer off), and
    the session's host events as (name, start_ns, end_ns, line)."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            events += [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns), i)
                       for e in line.events]
    return out, events


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_disabled_span_is_shared_noop_singleton():
    # With no profiler recording and metrics off, span() is one TraceMe
    # activity check: the same inert object every time, no registry use.
    s1 = obs.span("a")
    s2 = obs.span("b", level=3)
    assert s1 is s2 is obs.NOOP_SPAN
    with s1 as sp:
        assert sp is obs.NOOP_SPAN
    assert obs.metrics_snapshot()["histograms"] == {}


def test_enabled_is_upgrade_only():
    obs.enable("metrics")
    with obs.enabled("off"):  # must NOT downgrade the global mode
        assert obs.mode() == "metrics"
    obs.disable()
    with obs.enabled("metrics"):
        assert obs.mode() == "metrics"
        with obs.enabled("off"):
            assert obs.mode() == "metrics"
    assert obs.mode() == "off"
    with pytest.raises(ValueError):
        obs.enable("trace")


def test_metrics_mode_feeds_span_histograms():
    from repro.solve import SolveSpec, plan

    p = plan(256, SolveSpec(mode="stream", obs="metrics"))
    r = np.random.default_rng(5)
    for _ in range(3):
        p.update(r.integers(0, 256, 64), r.integers(0, 256, 64),
                 r.random(64).astype(np.float32))
    assert p.query(np.arange(8), np.arange(8, 16)).shape == (8,)
    h = obs.metrics_snapshot()["histograms"]
    assert h["span.stream.update"]["count"] == 3
    assert h["span.solve.stream.update"]["count"] == 3
    assert {"p50", "p95", "p99"} <= set(h["span.stream.query"])
    assert obs.mode() == "off"  # the spec's scope ended with each call


def _flat():
    from repro.solve import SolveSpec, plan
    from repro.solve.planner import clear_plan_cache

    clear_plan_cache()
    return plan(random_graph(256, 1024, seed=7), SolveSpec()).solve()


def _coarsen(fused):
    from repro.coarsen import CoarsenConfig
    from repro.solve import SolveSpec, plan

    g = random_graph(512, 2048, seed=11)
    cfg = CoarsenConfig(cutoff=32, rounds_per_level=2)
    return plan(g, SolveSpec(mode="coarsen", coarsen=cfg, fused=fused)).solve()


def _stream_plan():
    from repro.solve import SolveSpec, plan

    p = plan(256, SolveSpec(mode="stream"))
    r = np.random.default_rng(5)
    for _ in range(2):
        p.update(r.integers(0, 256, 64).astype(np.int32),
                 r.integers(0, 256, 64).astype(np.int32),
                 r.random(64).astype(np.float32))
    return p


def _stream_update():
    p = _stream_plan()
    return p.update(np.arange(8), np.arange(8, 16), np.ones(8, np.float32))


def _stream_query():
    p = _stream_plan()
    return p.query(np.arange(16), np.arange(16, 32))


def _served_batch():
    from repro import serve

    p = _stream_plan()
    handle = serve.start_in_thread(p, serve.ServeConfig())
    try:
        with serve.ServeClient(handle.address) as c:
            return c.connected(list(range(8)), list(range(8, 16)))
    finally:
        handle.drain()


# path -> (run, {parent span: spans that must nest inside one of them})
SPAN_PATHS = {
    "flat": (_flat, {
        "solve.flat": {"msf.flat", "solve.fetch"},
        None: {"plan.resolve", "plan.build", "plan.cost"},
    }),
    "coarsen_fused": (lambda: _coarsen(True), {
        "solve.coarsen": {"coarsen.canonical", "coarsen.level",
                          "coarsen.residual", "solve.fetch",
                          "coarsen.finalize"},
        "coarsen.residual": {"msf.flat"},
    }),
    "coarsen_unfused": (lambda: _coarsen(False), {
        "solve.coarsen": {"coarsen.canonical", "coarsen.level",
                          "coarsen.residual", "solve.fetch",
                          "coarsen.finalize"},
        "coarsen.residual": {"msf.flat"},
    }),
    "stream_update": (_stream_update, {
        "solve.stream.update": {"stream.update"},
        "stream.update": {"stream.union.prepare", "stream.union.solve",
                          "stream.union.publish"},
        "stream.union.solve": {"msf.flat"},
    }),
    "stream_query": (_stream_query, {
        "solve.stream.query": {"stream.query"},
    }),
    "served_batch": (_served_batch, {
        None: {"serve.decode", "serve.flush", "stream.query",
               "serve.respond", "client.send", "client.recv"},
    }),
}


@pytest.mark.parametrize("path", sorted(SPAN_PATHS))
def test_profiler_trace_holds_nested_spans(path, tmp_path):
    run, nesting = SPAN_PATHS[path]
    _, events = _profiled(tmp_path, run)
    for parent, children in nesting.items():
        for child in children:
            inner = [e for e in events if e[0] == child]
            assert inner, f"{path}: no {child} span"
            if parent is None:
                continue
            outer = [e for e in events if e[0] == parent]
            for name, lo, hi, line in inner:
                assert any(o[3] == line and o[1] <= lo and hi <= o[2]
                           for o in outer), f"{path}: {child} outside {parent}"
    if path == "served_batch":
        # the loop's spans on one thread, the fused call on another
        loop = {e[3] for e in events if e[0].startswith("serve.")}
        assert len(loop) == 1
        assert loop.isdisjoint(e[3] for e in events if e[0] == "stream.query")


def test_span_attributes_keep_the_event_name(tmp_path):
    # readers match fixed names; attributes land as the event's stats
    def closed():
        with obs.span("fixed.name", level=3, n=5):
            pass

    _, events = _profiled(tmp_path, closed)
    assert [e[0] for e in events if "fixed" in e[0]] == ["fixed.name"]


# ---------------------------------------------------------------------------
# no-observer-effect parity: the profiler never changes solver output
# ---------------------------------------------------------------------------


def _assert_reports_identical(a, b, what):
    assert float(a.weight) == float(b.weight), what
    assert np.array_equal(np.asarray(a.msf_eids), np.asarray(b.msf_eids)), what
    assert np.array_equal(np.asarray(a.parent), np.asarray(b.parent)), what


@pytest.mark.parametrize("path", ["flat", "coarsen_fused", "coarsen_unfused",
                                  "stream_update", "stream_query"])
def test_profiler_parity(path, tmp_path):
    run = SPAN_PATHS[path][0]
    base = run()
    traced, _ = _profiled(tmp_path, run)
    if isinstance(base, np.ndarray):  # query answers
        assert np.array_equal(base, traced)
    else:
        _assert_reports_identical(base, traced, f"{path} profiled")


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_and_gauge():
    obs.counter("c").inc()
    obs.counter("c").inc(41)
    obs.gauge("g").set(2.5)
    snap = obs.metrics_snapshot()
    assert snap["counters"]["c"] == 42
    assert snap["gauges"]["g"] == 2.5
    with pytest.raises(ValueError):
        obs.counter("c").inc(-1)


def test_histogram_percentiles_uniform():
    # 1..1000 ms uniformly: percentiles should match the analytic value
    # to within one log-bucket's width (the documented approximation).
    h = obs.histogram("lat")
    for ms in range(1, 1001):
        h.observe(ms / 1e3)
    for q in (50, 95, 99):
        got = h.percentile(q)
        want = q / 100.0  # q-th percentile of U(0, 1] seconds
        assert want / 2.2 <= got <= want * 2.2, (q, got, want)
    s = h.summary()
    assert s["count"] == 1000
    assert s["min"] == pytest.approx(1e-3)
    assert s["max"] == pytest.approx(1.0)
    assert s["p50"] <= s["p95"] <= s["p99"] <= s["max"]


def test_histogram_single_value_and_clamping():
    h = obs.histogram("one")
    for _ in range(10):
        h.observe(0.25)
    s = h.summary()
    # Interpolation is clamped to the observed [min, max]: a
    # single-valued stream reports that value at every quantile.
    assert s["p50"] == s["p95"] == s["p99"] == pytest.approx(0.25)


def test_histogram_rejects_bad_bounds():
    from repro.obs.metrics import Histogram

    with pytest.raises(ValueError):
        Histogram(bounds=())
    with pytest.raises(ValueError):
        Histogram(bounds=(1.0, 1.0))


def test_trace_parity_dist(dist_mesh, dist_mesh_shape):
    from repro.coarsen import CoarsenConfig
    from repro.graphs.partition import partition_edges_2d
    from repro.solve import SolveSpec, plan

    g = random_graph(512, 2048, seed=13)
    part = partition_edges_2d(g, *dist_mesh_shape)
    cfg = CoarsenConfig(cutoff=64)
    base = plan(part, SolveSpec(mode="dist", coarsen=cfg), mesh=dist_mesh).solve()
    rep = plan(
        part, SolveSpec(mode="dist", coarsen=cfg, obs="metrics"),
        mesh=dist_mesh,
    ).solve()
    _assert_reports_identical(base, rep, "dist metrics")
    snap = obs.metrics_snapshot()
    # Analytic all-reduce accounting: every level + residual round adds
    # its combine passes over the dense [n_pad] accumulator.
    assert snap["counters"]["dist.allreduce.passes"] > 0
    assert snap["counters"]["dist.allreduce.elements"] > 0
    assert "span.dist.residual" in snap["histograms"]


def test_plan_cache_counters():
    from repro.solve import SolveSpec, plan
    from repro.solve.planner import clear_plan_cache

    g = random_graph(128, 512, seed=2)
    clear_plan_cache()
    plan(g, SolveSpec(obs="metrics"))
    plan(g, SolveSpec(obs="metrics"))
    snap = obs.metrics_snapshot()["counters"]
    assert snap["plan.cache.miss"] == 1
    assert snap["plan.cache.hit"] == 1


def test_spec_rejects_unknown_obs_mode():
    from repro.solve import SolveSpec

    with pytest.raises(ValueError, match="obs"):
        SolveSpec(obs="verbose")


# ---------------------------------------------------------------------------
# SolveReport.n_components (satellite fix): canonical-root counting
# ---------------------------------------------------------------------------


def test_n_components_counts_canonical_roots():
    from repro.solve.report import SolveReport

    # Non-canonical parent: 3 -> 2 -> 1 -> 1 chain plus root 0. A naive
    # parent[i] == i count is right here, but np.unique on the raw
    # (uncanonicalized) vector would see {1, 2} labels as distinct
    # components — the regression the canonicalizing property fixes.
    parent = np.array([0, 1, 1, 2], np.int32)
    rep = SolveReport(
        mode="flat", weight=0.0, msf_eids=np.zeros(0, np.int32),
        parent=parent, n_msf_edges=0, iterations=0, levels=(),
        host_roundtrips=0, recompiles=0, raw=None,
    )
    assert rep.n_components == 2
    # Oracle: unique labels after full pointer-jumping canonicalization.
    p = parent.copy()
    while not np.array_equal(p[p], p):
        p = p[p]
    assert rep.n_components == len(np.unique(p))


def test_n_components_matches_graph_truth():
    from repro.solve import SolveSpec, plan

    g = random_graph(200, 300, seed=21)
    rep = plan(g, SolveSpec()).solve()
    assert rep.n_components == nx_free_n_components(g)
    p = np.asarray(rep.parent)
    while not np.array_equal(p[p], p):
        p = p[p]
    assert rep.n_components == len(np.unique(p))


def test_ranked_reduction_counter_counts_unpacked_flat_solves():
    """``msf.reduction.ranked`` counts one per flat solve that hooks
    through the slot ranks: unpacked and complete, not pack32, not the
    paper variant."""
    from repro.solve import SolveSpec, plan

    g = random_graph(128, 512, seed=4)
    plan(g, SolveSpec(pack=False)).solve()
    plan(g, SolveSpec(pack=False)).solve()
    plan(g, SolveSpec(pack=True)).solve()
    plan(g, SolveSpec(pack=False, variant="paper")).solve()
    assert obs.metrics_snapshot()["counters"]["msf.reduction.ranked"] == 2

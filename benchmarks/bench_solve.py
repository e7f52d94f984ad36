"""Unified-API parity gate: SolveSpec plans vs the deprecated kwarg paths.

For each graph family (rmat, grid road) this bench runs the same solve
through the new front door (``repro.solve.plan``) and through the
deprecated entry points (``msf``, ``msf_distributed``, ``StreamingMSF``
— warnings suppressed here; the shim-parity *test* suite asserts the
warning contract), asserting identical forest weight and MSF edge set,
and reporting the spec-path latency with the shim-path latency as the
derived comparison — the CI tripwire that the spec → resolve → plan
pipeline stays bit-identical to the four historical paths while both
exist.

Rows:
- ``solve_flat_*``    — flat plan vs ``msf(g)``;
- ``solve_coarsen_*`` — coarsen plan (fused levels) vs
  ``msf(g, coarsen=cfg, fused=True)``;
- ``solve_dist_*``    — dist plan on the largest available mesh vs the
  ``msf_distributed`` driver;
- ``solve_stream_*``  — stream plan replay vs a ``StreamingMSF`` replay.

``--smoke`` shrinks the graphs for the CI gate (parity is asserted in
both sizes). ``--json PATH`` writes the rows as a BENCH trajectory
point.
"""
from __future__ import annotations

import sys
import warnings

from benchmarks.common import assert_msf_parity as _assert_parity
from benchmarks.common import cost_fragment, emit, from_samples, measure, timeit
from repro.coarsen import CoarsenConfig
from repro.graphs import grid_road_graph, rmat_graph
from repro.solve import SolveSpec, plan

SMOKE_SCALE = 8
FULL_SCALE = 12
STREAM_BATCH = 256


def _deprecated(fn, *args, **kw):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return fn(*args, **kw)


def _bench_flat(name, g):
    from repro.core.msf import msf

    p = plan(g, SolveSpec())
    rep = p.solve()
    shim_r = _deprecated(msf, g)
    _assert_parity(rep, shim_r, f"solve_flat_{name}")
    m = measure(f"solve_flat_{name}", lambda: p.solve(), iters=3)
    t_shim = timeit(lambda: _deprecated(msf, g), iters=3)
    return [m.with_derived(
        f"shim_us={t_shim * 1e6:.1f};edges={g.num_directed_edges}"
        + cost_fragment(rep, m.median / 1e6)
    )]


def _bench_coarsen(name, g, cfg):
    from repro.core.msf import msf

    p = plan(g, SolveSpec(mode="coarsen", coarsen=cfg, fused=True))
    rep = p.solve()
    shim_r = _deprecated(msf, g, coarsen=cfg, fused=True)
    _assert_parity(rep, shim_r, f"solve_coarsen_{name}")
    m = measure(f"solve_coarsen_{name}", lambda: p.solve(), iters=3)
    t_shim = timeit(lambda: _deprecated(msf, g, coarsen=cfg, fused=True), iters=3)
    return [m.with_derived(
        f"shim_us={t_shim * 1e6:.1f};levels={len(rep.levels)}"
        + cost_fragment(rep, m.median / 1e6)
    )]


def _bench_dist(name, g):
    import jax

    from repro.compat import make_mesh
    from repro.core.msf_dist import msf_distributed
    from repro.graphs.partition import partition_edges_2d

    n = jax.device_count()
    shape = (2, 4) if n >= 8 else (2, 2) if n >= 4 else (1, 2) if n >= 2 else (1, 1)
    mesh = make_mesh(shape, ("data", "model"))
    part = partition_edges_2d(g, *shape)
    p = plan(part, SolveSpec(mode="dist"), mesh=mesh)
    drv = _deprecated(msf_distributed, part, mesh)
    args = (part.src_row, part.dst_col, part.w, part.eid, part.valid)
    _assert_parity(p.solve(), drv(*args), f"solve_dist_{name}")
    m = measure(f"solve_dist_{name}", lambda: p.solve(), iters=3)
    t_shim = timeit(lambda: drv(*args), iters=3)
    return [m.with_derived(
        f"shim_us={t_shim * 1e6:.1f};mesh={shape[0]}x{shape[1]}"
    )]


def _bench_stream(name, g):
    from repro.launch.serve_graph import undirected_edges
    from repro.stream import StreamingMSF

    lo, hi, w = undirected_edges(g)
    n_batches = max(1, len(lo) // STREAM_BATCH)

    def replay_spec():
        p = plan(g.n, SolveSpec(mode="stream", batch_capacity=STREAM_BATCH))
        rep = None
        for k in range(n_batches):
            sl = slice(k * STREAM_BATCH, (k + 1) * STREAM_BATCH)
            rep = p.update(lo[sl], hi[sl], w[sl])
        return rep

    def replay_shim():
        eng = _deprecated(StreamingMSF, g.n, batch_capacity=STREAM_BATCH)
        for k in range(n_batches):
            sl = slice(k * STREAM_BATCH, (k + 1) * STREAM_BATCH)
            eng.insert_batch(lo[sl], hi[sl], w[sl])
        return eng

    rep, eng = replay_spec(), replay_shim()
    assert abs(rep.weight - eng.weight) <= max(1.0, 1e-6 * abs(rep.weight)), (
        f"solve_stream_{name}", rep.weight, eng.weight,
    )
    import time as _time

    ts = []
    for _ in range(2):
        t0 = _time.perf_counter()
        replay_spec()
        ts.append(_time.perf_counter() - t0)
    m = from_samples(f"solve_stream_{name}", ts, per=n_batches)
    t_shim = timeit(replay_shim, warmup=0, iters=2)
    return [m.with_derived(
        f"shim_us={t_shim / n_batches * 1e6:.1f};batches={n_batches}"
    )]


def run_rows(smoke: bool = False):
    scale = SMOKE_SCALE if smoke else FULL_SCALE
    g_rmat = rmat_graph(scale, 4 if smoke else 8, seed=9)
    side = 32 if smoke else 128
    g_grid = grid_road_graph(side, side, seed=2)
    cfg = CoarsenConfig(rounds_per_level=2, cutoff=32 if smoke else 1024)
    out = []
    for name, g in ((f"rmat_s{scale}", g_rmat), (f"grid_{side}x{side}", g_grid)):
        out += _bench_flat(name, g)
        out += _bench_coarsen(name, g, cfg)
        out += _bench_dist(name, g)
    out += _bench_stream(f"rmat_s{scale}", g_rmat)
    return out


if __name__ == "__main__":
    argv = sys.argv[1:]
    smoke = "--smoke" in argv
    emit(run_rows(smoke=smoke), argv)
    if smoke:
        print("# solve smoke: spec/deprecated path parity OK", file=sys.stderr)

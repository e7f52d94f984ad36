"""The reported forest weight is exact in every batch mode, and the flat
driver counts its shortcut's pointer jumps.

The weight is the host float64 sum of the forest edges' float32 weights
(``core.msf.forest_weight``): for integer weights it equals the integer
sum of scipy's Kruskal forest whatever the total -- a road forest with
lengths up to 65535 totals far above 2^24, where a float32 sum drifts.
"""
from __future__ import annotations

import math

import numpy as np
import pytest
import scipy.sparse as sp
import scipy.sparse.csgraph as csg

from repro.coarsen import CoarsenConfig
from repro.core.msf import forest_weight, run_flat
from repro.graphs.generators import grid_road_graph, random_graph, rmat_graph
from repro.graphs.partition import partition_edges_2d
from repro.graphs.structures import from_edges
from repro.solve import SolveSpec, plan


def road_grid(scale: int, seed: int, extra_edge_p: float = 0.22,
              weight_max: int = 65535, float_weights: bool = False):
    """A 2^(scale/2)-sided grid road stand-in: a left-or-up spanning tree
    and every other grid edge with probability ``extra_edge_p``; integer
    lengths in 1..``weight_max`` (or floats in [1, weight_max))."""
    side = 1 << (scale // 2)
    n = side * side
    rng = np.random.default_rng(seed)
    x = np.arange(n)
    row, col = x // side, x % side
    left = np.where(row == 0, True, np.where(col == 0, False, rng.random(n) < 0.5))
    keep_left = (col > 0) & (left | (rng.random(n) < extra_edge_p))
    keep_up = (row > 0) & (~left | (rng.random(n) < extra_edge_p))
    lo = np.concatenate([x[keep_left] - 1, x[keep_up] - side])
    hi = np.concatenate([x[keep_left], x[keep_up]])
    if float_weights:
        w = rng.uniform(1.0, weight_max, len(lo))
    else:
        w = rng.integers(1, weight_max + 1, len(lo)).astype(np.float64)
    return from_edges(lo, hi, w, n)


def kruskal(g):
    """(forest eids sorted, their float32 weights, component labels) of the
    unique (w, eid) minimum spanning forest, by scipy."""
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    w, eid = np.asarray(g.w), np.asarray(g.eid)
    up = np.asarray(g.valid) & (src < dst)
    w_of = np.empty(int(eid.max()) + 1, np.float32)
    w_of[eid[up]] = w[up]
    # rank of each edge in the (w, eid) order: an exact float64 key
    rank = np.empty(up.sum(), np.float64)
    rank[np.lexsort((eid[up], w[up]))] = np.arange(1, up.sum() + 1)
    f = csg.minimum_spanning_tree(sp.csr_matrix(
        (rank, (src[up], dst[up])), shape=(g.n, g.n))).tocoo()
    order = np.lexsort((eid[up], w[up]))
    eids = np.sort(eid[up][order][f.data.astype(np.int64) - 1])
    _, labels = csg.connected_components(f, directed=False)
    return eids, w_of[eids], labels


def same_partition(labels, parent) -> bool:
    p = np.asarray(parent)
    while not np.array_equal(p[p], p):
        p = p[p]
    labels = np.asarray(labels, np.int64)[: len(p)]
    p = p[: len(labels)].astype(np.int64)
    pairs = len(np.unique(labels * (int(p.max()) + 1) + p))
    return pairs == len(np.unique(labels)) == len(np.unique(p))


GRAPHS = {
    "road-2^12": lambda: road_grid(12, seed=1),
    "road-2^16": lambda: road_grid(16, seed=2),
    "kron-2^10": lambda: rmat_graph(10, 8, seed=3),
}
MODES = {
    "flat": dict(mode="flat"),
    "coarsen-fused": dict(mode="coarsen", coarsen=CoarsenConfig(fused=True)),
    "coarsen-unfused": dict(mode="coarsen", coarsen=CoarsenConfig(fused=False)),
}


@pytest.fixture(scope="module", params=sorted(GRAPHS))
def graph_and_forest(request):
    g = GRAPHS[request.param]()
    return g, kruskal(g)


@pytest.mark.parametrize("mode", sorted(MODES))
def test_weight_is_the_exact_integer_sum(graph_and_forest, mode):
    g, (eids, w, labels) = graph_and_forest
    rep = plan(g, SolveSpec(**MODES[mode])).solve()
    assert rep.weight == int(w.astype(np.int64).sum())
    np.testing.assert_array_equal(np.sort(rep.msf_eids), eids)
    assert same_partition(labels, rep.parent)


@pytest.mark.parametrize("coarsen", [None, CoarsenConfig(fused=True, cutoff=64)],
                         ids=["dist-flat", "dist-coarsen"])
def test_dist_weight_is_the_exact_integer_sum(dist_mesh, dist_mesh_shape, coarsen):
    g = road_grid(12, seed=5)
    eids, w, labels = kruskal(g)
    part = partition_edges_2d(g, *dist_mesh_shape)
    rep = plan(part, SolveSpec(mode="dist", coarsen=coarsen), mesh=dist_mesh).solve()
    assert rep.weight == int(w.astype(np.int64).sum())
    np.testing.assert_array_equal(np.sort(rep.msf_eids), eids)
    assert same_partition(labels, rep.parent)


def test_the_2_to_16_road_total_is_beyond_float32():
    """The test graph is one where a float32 total is not exact."""
    _, w, _ = kruskal(road_grid(16, seed=2))
    exact = int(w.astype(np.int64).sum())
    assert exact > 1 << 24
    assert int(np.cumsum(w, dtype=np.float32)[-1]) != exact


@pytest.mark.parametrize("mode", sorted(MODES))
def test_float_weight_is_the_float64_sum(mode):
    g = road_grid(12, seed=7, float_weights=True)
    eids, w, labels = kruskal(g)
    rep = plan(g, SolveSpec(**MODES[mode])).solve()
    np.testing.assert_array_equal(np.sort(rep.msf_eids), eids)
    # The program sums the same float32 values in float64 in another
    # order (numpy's pairwise sum); math.fsum is correctly rounded. The
    # two differ by a few float64 ulps of the total at most: far below
    # 1e-12 relative, and far above what a float32 total would miss by.
    assert rep.weight == pytest.approx(math.fsum(w.astype(np.float64)), rel=1e-12)


def test_forest_weight_helper():
    w = np.array([65535.0, 3.0, 1.0, 7.0], np.float32)
    assert forest_weight(w, 3) == 65539.0
    assert forest_weight(np.full(1 << 12, 65535.0, np.float32)) == 65535 << 12
    assert forest_weight(np.zeros(0, np.float32)) == 0.0


def numpy_as_model(g):
    """(rounds, pointer jumps) of the complete-variant AS loop, by numpy:
    every root hooks along its minimum (w, eid) outgoing edge, mutual hooks
    keep the smaller root, then p <- p[p] until every tree is a star,
    counting the passes; stop when a round changes nothing."""
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    w, eid, valid = np.asarray(g.w), np.asarray(g.eid), np.asarray(g.valid)
    n = g.n
    i = np.arange(n)
    p = i.copy()
    rounds = jumps = 0
    while True:
        rounds += 1
        ps, pd = p[src], p[dst]
        out = np.flatnonzero((ps != pd) & valid)
        order = out[np.lexsort((eid[out], w[out], ps[out]))]
        first = np.ones(len(order), bool)
        first[1:] = ps[order][1:] != ps[order][:-1]
        win = order[first]
        p_h = p.copy()
        p_h[ps[win]] = pd[win]
        hooked = np.zeros(n, bool)
        hooked[ps[win]] = True
        t = hooked & (i < p_h) & (p_h[p_h] == i)
        p_h[t] = i[t]
        while not np.array_equal(p_h[p_h], p_h):
            p_h = p_h[p_h]
            jumps += 1
        if np.array_equal(p_h, p):
            return rounds, jumps
        p = p_h


@pytest.mark.parametrize("make", [
    lambda: road_grid(10, seed=3),
    lambda: grid_road_graph(20, 25, seed=2),
    lambda: random_graph(300, 900, seed=8),
], ids=["road-2^10", "grid-20x25", "random-300"])
@pytest.mark.parametrize("shortcut", ["complete", "csp", "os"])
def test_shortcut_jumps_match_a_numpy_count(make, shortcut):
    g = make()
    rounds, jumps = numpy_as_model(g)
    assert jumps > 0
    rep = plan(g, SolveSpec(shortcut=shortcut, capacity=1)).solve()
    assert rep.iterations == rounds
    # csp and os compress the changed map instead; at capacity 1 every
    # round with more than one hook falls back to the complete shortcut
    if shortcut == "complete":
        assert rep.shortcut_jumps == jumps
    else:
        assert 0 <= rep.shortcut_jumps <= jumps
    r = run_flat(g)
    assert int(r.shortcut_jumps) == jumps

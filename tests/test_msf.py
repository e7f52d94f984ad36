"""Algorithm 1 correctness against the scipy MSF oracle, across variants,
shortcut strategies, and graph families — plus hypothesis property tests."""
import numpy as np
import pytest
from _hypothesis_stub import given, settings, st  # skips cleanly if absent

from repro.core import msf
from repro.core.semiring import IMAX
from repro.graphs import grid_road_graph, random_graph, rmat_graph
from repro.graphs.generators import components_graph
from repro.graphs.structures import (
    from_edges,
    nx_free_msf_weight,
    nx_free_n_components,
)

GRAPHS = {
    "random": random_graph(200, 600, seed=1),
    "grid_road": grid_road_graph(12, 17, seed=2),
    "rmat": rmat_graph(8, 4, seed=3),
    "sparse_forest": random_graph(300, 150, seed=4),
    "components": components_graph(5, 40, seed=5),
}


@pytest.mark.parametrize("gname", list(GRAPHS))
@pytest.mark.parametrize(
    "variant,shortcut",
    [
        ("complete", "complete"),
        ("complete", "csp"),
        ("complete", "os"),
        ("paper", "complete"),
        ("pairwise", "complete"),
    ],
)
def test_msf_weight_matches_oracle(gname, variant, shortcut):
    g = GRAPHS[gname]
    r = msf(g, variant=variant, shortcut=shortcut, capacity=64)
    assert abs(float(r.weight) - nx_free_msf_weight(g)) < 1e-3


@pytest.mark.parametrize("gname", list(GRAPHS))
def test_msf_edges_form_spanning_forest(gname):
    """The tracked eids must form a forest with the oracle weight and the
    right component structure."""
    g = GRAPHS[gname]
    r = msf(g)
    n_f = int(r.n_msf_edges)
    eids = np.asarray(r.msf_eids)[:n_f]
    assert len(np.unique(eids)) == n_f, "duplicate MSF edges"
    # reconstruct edge weights/endpoints by eid (first direction)
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    w, eid, valid = np.asarray(g.w), np.asarray(g.eid), np.asarray(g.valid)
    lookup = {}
    for s, d, ww, e, v in zip(src, dst, w, eid, valid):
        if v and e not in lookup:
            lookup[e] = (s, d, ww)
    total = sum(lookup[e][2] for e in eids)
    assert abs(total - nx_free_msf_weight(g)) < 1e-3
    # forest check: n_msf_edges == n - n_components over non-isolated graph
    ncc = nx_free_n_components(g)
    assert n_f == g.n - ncc
    # parent vector labels match component count
    roots = np.unique(np.asarray(r.parent))
    assert len(roots) == ncc


def test_iteration_bound():
    """AS converges in O(log n) iterations (complete-shortcut variant is
    log2-bounded, paper §IV-B)."""
    g = random_graph(512, 2048, seed=7)
    r = msf(g)
    assert int(r.iterations) <= 2 * int(np.log2(512)) + 2


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(2, 60),
    m=st.integers(0, 150),
    seed=st.integers(0, 2**31 - 1),
)
def test_msf_property_random(n, m, seed):
    rng = np.random.default_rng(seed)
    u = rng.integers(0, n, m)
    v = rng.integers(0, n, m)
    w = rng.integers(1, 256, m).astype(np.float64)
    g = from_edges(u, v, w, n)
    for variant in ("complete", "paper"):
        r = msf(g, variant=variant)
        assert abs(float(r.weight) - nx_free_msf_weight(g)) < 1e-3


def test_warm_start_parent0():
    """Re-entrant msf: warm-starting from a converged labeling hooks
    nothing new; warm-starting from a partial forest reports only the
    delta weight."""
    g = random_graph(200, 600, seed=13)
    r = msf(g)
    # converged labels in: no new hooks out, same partition
    r2 = msf(g, parent0=r.parent)
    assert float(r2.weight) == 0.0
    assert int(r2.n_msf_edges) == 0
    assert np.array_equal(np.asarray(r2.parent), np.asarray(r.parent))
    # pre-merged vertex pairs: the delta weight only covers cross-pair
    # hooks, and the final partition still has the oracle component count
    import jax.numpy as jnp

    pairs = (np.arange(g.n, dtype=np.int32) // 2) * 2
    r3 = msf(g, parent0=jnp.asarray(pairs))
    assert float(r3.weight) <= nx_free_msf_weight(g)
    # free pair-merges can only coarsen the partition
    roots = np.unique(np.asarray(r3.parent))
    assert len(roots) <= nx_free_n_components(g)


def test_empty_and_singleton():
    g = from_edges(np.array([], np.int64), np.array([], np.int64),
                   np.array([], np.float64), 5)
    r = msf(g)
    assert float(r.weight) == 0.0
    assert int(r.n_msf_edges) == 0


def _kruskal_eids(g):
    """scipy's minimum spanning forest under the strict (w, eid) order:
    each undirected edge keyed by its 1-based rank in that order."""
    import scipy.sparse as sp
    import scipy.sparse.csgraph as csg

    src, dst = np.asarray(g.src), np.asarray(g.dst)
    up = np.asarray(g.valid) & (src < dst)
    lo, hi = src[up], dst[up]
    w, eid = np.asarray(g.w)[up] + 0.0, np.asarray(g.eid)[up]
    rank = np.empty(len(w), np.float64)
    rank[np.lexsort((eid, w))] = np.arange(1, len(w) + 1)
    f = csg.minimum_spanning_tree(
        sp.csr_matrix((rank, (lo, hi)), shape=(g.n, g.n))).tocoo()
    by_rank = dict(zip(rank.astype(np.int64).tolist(), eid.tolist()))
    return set(by_rank[int(r)] for r in f.data)


def _same_partition(a, b):
    a, b = np.asarray(a, np.int64), np.asarray(b, np.int64)
    pairs = len(np.unique(a * (int(b.max()) + 1) + b))
    return pairs == len(np.unique(a)) == len(np.unique(b))


@pytest.mark.parametrize("weights", ["ties", "float", "signed_zero"])
def test_flat_forest_matches_3pass_paths_and_kruskal(weights):
    """The flat plan (rank-keyed hook) picks the same forest, weight and
    labels as the paths that keep the 3-pass reduction — the paper
    variant and the fused coarsen levels — and as scipy's Kruskal."""
    from repro.coarsen import CoarsenConfig
    from repro.solve import SolveSpec, plan

    rng = np.random.default_rng(11)
    n, m = 300, 1200
    u, v = rng.integers(0, n, m), rng.integers(0, n, m)
    if weights == "ties":
        w = rng.integers(1, 4, m).astype(np.float64)
    elif weights == "float":
        w = rng.random(m)
    else:
        w = rng.choice(np.array([-0.0, 0.0, 2.5]), m)
    g = from_edges(u, v, w, n)
    flat = plan(g, SolveSpec(pack=False)).solve()
    paper = plan(g, SolveSpec(pack=False, variant="paper")).solve()
    fused = plan(g, SolveSpec(mode="coarsen", pack=False, fused=True,
                              coarsen=CoarsenConfig(cutoff=16))).solve()
    assert fused.levels, "the coarsen levels did not run"
    want = _kruskal_eids(g)
    for other in (paper, fused):
        assert set(other.msf_eids.tolist()) == want
        assert _same_partition(flat.parent, other.parent)
        assert flat.weight == pytest.approx(other.weight, rel=1e-6, abs=1e-6)
    assert set(flat.msf_eids.tolist()) == want
    assert flat.n_msf_edges == len(want) == n - nx_free_n_components(g)

"""The device Kronecker generator against ``from_edges``'s semantics."""
from __future__ import annotations

import numpy as np
import pytest

import chipbench_tiny  # noqa: F401  (puts the benchmark on sys.path)
import kron
from repro.graphs.structures import from_edges


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**40 + 3])
def test_graph_is_from_edges_of_the_raw_edges(seed):
    scale, ef = 9, 16
    u, v, w = kron.raw_edges(scale, ef << scale, seed)
    assert u.min() >= 0 and max(u.max(), v.max()) < 1 << scale
    assert w.min() >= 1 and w.max() <= 255
    want = from_edges(u, v, w.astype(np.float64), 1 << scale)
    got = kron.kron_graph(scale, ef, seed)
    for field in ("src", "dst", "w", "eid", "valid"):
        np.testing.assert_array_equal(np.asarray(getattr(got, field)),
                                      np.asarray(getattr(want, field)), field)
    assert got.n == want.n


def test_dedupe_keeps_min_weight_and_drops_loops():
    u = np.array([3, 5, 3, 2, 7, 7])
    v = np.array([5, 3, 5, 2, 1, 1])
    w = np.array([9, 4, 6, 1, 200, 255])
    lo, hi, wt = kron.dedupe(u, v, w, scale=3)
    assert lo.tolist() == [1, 3] and hi.tolist() == [7, 5]
    assert wt.tolist() == [200.0, 4.0]


def test_same_seed_same_graph_other_seed_other_graph():
    a = kron.kron_graph(8, 16, 123)
    b = kron.kron_graph(8, 16, 123)
    c = kron.kron_graph(8, 16, 124)
    np.testing.assert_array_equal(a.src, b.src)
    np.testing.assert_array_equal(a.w, b.w)
    assert a.src.shape != c.src.shape or not np.array_equal(a.src, c.src)


def test_skew_and_relabel():
    """Kronecker degrees are skewed, and the relabelling moves the hubs
    off the low ids."""
    g = kron.kron_graph(12, 16, 5)
    deg = np.bincount(np.asarray(g.src), minlength=g.n)
    assert deg.max() > 20 * deg.mean()
    assert np.argmax(deg) != 0


def test_seed_range():
    with pytest.raises(ValueError):
        kron.raw_edges(4, 16, -1)

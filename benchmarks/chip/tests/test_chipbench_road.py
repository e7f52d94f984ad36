"""The road-network stand-in (``gen_road``) and a whole run of its cell on
the CPU at a tiny size: sound solves are ``correct``, a weight one unit
off or a dropped forest edge is not, and the control fails."""
from __future__ import annotations

import numpy as np
import pytest
import scipy.sparse as sp
import scipy.sparse.csgraph as csg

from chipbench_tiny import run_tiny, tiny_registry
import control
import gen_road
from repro.graphs.structures import from_edges
from repro.solve import SolveSpec, plan
from repro.solve.planner import Plan

P, WMAX, NET = 0.22, 65535, 0


def _components(g) -> int:
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    a = sp.csr_matrix((np.ones(len(src)), (src, dst)), shape=(g.n, g.n))
    return csg.connected_components(a, directed=False)[0]


@pytest.mark.parametrize("scale, seed", [(10, 1), (12, 2**31 + 7), (16, 5)])
def test_connected_integer_lengths(scale, seed):
    g = gen_road.road_graph(scale, P, WMAX, NET, seed)
    assert g.n == 1 << scale
    assert _components(g) == 1
    w = np.asarray(g.w)
    assert w.min() >= 1 and w.max() <= WMAX and np.all(w == np.round(w))
    assert w.max() > 255  # wider than 8 bits: the unpacked path


def test_slots_per_vertex_at_scale_16():
    g = gen_road.road_graph(16, P, WMAX, NET, 3)
    assert abs(len(g.src) / g.n - 2.44) <= 0.01


@pytest.mark.parametrize("seed", [0, 2**40 + 3])
def test_layout_is_from_edges_of_its_pairs(seed):
    n, lo, hi, w = gen_road.road_pairs(12, P, WMAX, NET, seed)
    want = from_edges(lo, hi, w.astype(np.float64), n)
    got = gen_road.road_graph(12, P, WMAX, NET, seed)
    for field in ("src", "dst", "w", "eid", "valid"):
        np.testing.assert_array_equal(np.asarray(getattr(got, field)),
                                      np.asarray(getattr(want, field)), field)
    assert got.n == want.n


def test_same_seed_same_graph_other_seed_other_graph():
    """The network is fixed by ``network_seed``; the run's seed draws its
    lengths, so another seed gives the same edges with other lengths, and
    another network seed another network."""
    a = gen_road.road_graph(10, P, WMAX, NET, 123)
    b = gen_road.road_graph(10, P, WMAX, NET, 123)
    c = gen_road.road_graph(10, P, WMAX, NET, 124)
    d = gen_road.road_graph(10, P, WMAX, NET + 1, 123)
    for field in ("src", "dst", "w"):
        np.testing.assert_array_equal(getattr(a, field), getattr(b, field))
    for field in ("src", "dst", "eid"):
        np.testing.assert_array_equal(getattr(a, field), getattr(c, field))
    assert not np.array_equal(a.w[:16], c.w[:16])
    assert a.src.shape != d.src.shape or not np.array_equal(a.src, d.src)


def test_lengths_keep_the_networks_order():
    """Every seed's lengths rise along the same order of the edges, so
    only ties separate two seeds' orders."""
    _, _, _, w1 = gen_road.road_pairs(12, P, WMAX, NET, 1)
    _, _, _, w2 = gen_road.road_pairs(12, P, WMAX, NET, 2**33 + 1)
    o = np.lexsort((w2, w1))  # by the first seed's lengths, ties by the second's
    assert np.all(np.diff(w2[o]) >= 0)
    assert not np.array_equal(w1, w2)


@pytest.mark.parametrize("scale", [10, 12])
def test_every_seed_does_the_same_work(scale):
    """Every seed solves in the same rounds and pointer jumps, so a run's
    time does not move with its seed."""
    work = set()
    for seed in (1, 77, 2**31 + 5):
        rep = plan(gen_road.road_graph(scale, P, WMAX, NET, seed), SolveSpec()).solve()
        work.add((int(rep.iterations), int(rep.shortcut_jumps)))
    assert len(work) == 1, work


def test_odd_scale_is_refused():
    with pytest.raises(ValueError):
        gen_road.road_pairs(11, P, WMAX, NET, 0)


@pytest.fixture(scope="module")
def reg(tmp_path_factory):
    return tiny_registry(tmp_path_factory.mktemp("tiny"))


def test_sound_run_is_correct(reg):
    res = run_tiny(reg, "road22-flat")
    assert res["correct"] and res["failed"] == 0, res["checks"]
    assert res["attempted"] >= 1 and "solve_s" in res["metrics"]
    assert res["checks"]["wrong_forest_weight"] == {"value": 0, "limit": 0}


def _weight_one_off(rep, target):
    return rep._replace(weight=rep.weight + 1.0)


def _edge_dropped(rep, target):
    return rep._replace(msf_eids=rep.msf_eids[:-1], n_msf_edges=rep.n_msf_edges - 1)


@pytest.mark.parametrize("fault, number", [(_weight_one_off, "wrong_forest_weight"),
                                           (_edge_dropped, "wrong_forest_edges")])
def test_broken_solve_is_not_correct(reg, fault, number, monkeypatch):
    real = Plan.solve

    def broken(self, *a, **kw):
        return fault(real(self, *a, **kw), self.target)

    monkeypatch.setattr(Plan, "solve", broken)
    res = run_tiny(reg, "road22-flat")
    assert not res["correct"], res["checks"]
    assert res["checks"][number]["value"] >= 1
    assert res["failed"] == res["attempted"]


def test_control_fails_and_program_passes(reg):
    _, summary = control.readings(reg, "road22-flat", [4, 5, 6], 0.2,
                                  require_tpu=False)
    assert all(v["program_max"] == 0 for v in summary.values())
    # At 2^10 vertices few weights tie, so the tie order seldom moves an
    # edge; the control's float32 sum is off at every seed.
    assert summary["wrong_forest_weight"]["control_min"] > 0

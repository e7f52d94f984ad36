"""A whole run of a batch cell on the CPU, at a tiny size, with the timed
path broken underneath: ``correct`` has to come out false. The run skips
only the harness's look for a chip. The control (the reference with
another tie order in the program's place) has to fail too."""
from __future__ import annotations

import numpy as np
import pytest

from chipbench_tiny import run_tiny, tiny_registry
import control
from repro.solve.planner import Plan


def _unchanged(rep, target):
    """The solve hands back its starting state: every vertex its own
    root, no forest edge."""
    return rep._replace(msf_eids=np.zeros(0, np.int32), n_msf_edges=0,
                        parent=np.arange(len(rep.parent), dtype=np.int32))


def _altered(rep, target):
    """One forest edge swapped for an edge outside the forest."""
    eids = np.array(rep.msf_eids)
    outside = np.setdiff1d(np.asarray(target.eid), eids)
    eids[-1] = outside[0]
    return rep._replace(msf_eids=eids)


FAULTS = {"state_unchanged": _unchanged, "answer_altered": _altered}


@pytest.fixture(scope="module")
def reg(tmp_path_factory):
    return tiny_registry(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell", ["kron20-coarsen", "kron20-flat"])
def test_sound_run_is_correct(reg, cell):
    res = run_tiny(reg, cell)
    assert res["correct"] and res["failed"] == 0, res["checks"]
    assert res["attempted"] >= 1 and "solve_s" in res["metrics"]
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_solve_is_not_correct(reg, fault, monkeypatch):
    real = Plan.solve

    def broken(self, *a, **kw):
        return FAULTS[fault](real(self, *a, **kw), self.target)

    monkeypatch.setattr(Plan, "solve", broken)
    res = run_tiny(reg, "kron20-coarsen")
    assert not res["correct"], res["checks"]
    assert res["failed"] == res["attempted"]


def test_half_the_graph_left_out_is_not_correct(reg, monkeypatch):
    real = Plan.solve

    def half(self, *a, **kw):
        g = self.target
        valid = np.array(g.valid)
        valid[: len(valid) // 2] = False
        self.target = type(g)(src=g.src, dst=g.dst, w=g.w, eid=g.eid,
                              valid=valid, n=g.n)
        try:
            return real(self, *a, **kw)
        finally:
            self.target = g

    monkeypatch.setattr(Plan, "solve", half)
    res = run_tiny(reg, "kron20-flat")
    assert not res["correct"], res["checks"]


def test_control_fails_and_program_passes(reg):
    _, summary = control.readings(reg, "kron20-coarsen", [4, 5, 6], 0.2,
                                  require_tpu=False)
    assert all(v["program_max"] == 0 for v in summary.values())
    assert summary["wrong_forest_edges"]["control_min"] > 0

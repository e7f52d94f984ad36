"""A whole run of the stream cell on the CPU, at a tiny size, with the
timed path or the state it serves broken underneath: ``correct`` has to
come out false. The run skips only the harness's look for a chip. The
control (answers one acknowledged write behind) has to fail too."""
from __future__ import annotations

import numpy as np
import pytest

from chipbench_tiny import run_tiny, tiny_registry
import control
from repro.solve.planner import Plan
from repro.stream.service import BatchAnswer, QueryService


@pytest.fixture(scope="module")
def reg(tmp_path_factory):
    return tiny_registry(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell", ["stream20-read"])
def test_sound_run_is_correct(reg, cell):
    res = run_tiny(reg, cell)
    assert res["correct"] and res["failed"] == 0, res["checks"]
    assert res["attempted"] >= 50 and len(res["metrics"]) == 2
    assert list(res)[-1] == "checks"


def _load_unchanged(real):
    def recertify(self, u, v, w):  # the load leaves the engine as it was
        return real(self, u[:0], v[:0], w[:0])
    return recertify


def _load_half(real):
    def recertify(self, u, v, w):  # half of the graph left out
        k = max(1, len(u) // 2)
        return real(self, u[:k], v[:k], w[:k])
    return recertify


@pytest.mark.parametrize("fault", ["unchanged", "half"])
def test_broken_load_is_not_correct(reg, fault, monkeypatch):
    wrap = {"unchanged": _load_unchanged, "half": _load_half}[fault]
    monkeypatch.setattr(Plan, "recertify", wrap(Plan.recertify))
    res = run_tiny(reg, "stream20-read")
    assert not res["correct"], res["checks"]


def test_altered_query_answer_is_not_correct(reg, monkeypatch):
    real = QueryService.answer

    def altered(self, u, v):
        a = real(self, u, v)
        conn = np.array(a.connected)
        conn[0] = ~conn[0]
        comp = np.array(a.component)
        comp[0] = comp[0] + 1_000_003
        return BatchAnswer(conn, comp, a.size, a.snapshot)

    monkeypatch.setattr(QueryService, "answer", altered)
    res = run_tiny(reg, "stream20-read")
    assert not res["correct"], res["checks"]
    assert res["failed"] > 0


def test_half_the_query_batch_left_out_is_not_correct(reg, monkeypatch):
    real = QueryService.answer

    def half(self, u, v):  # the second half of the points repeats the first's
        u, v = np.asarray(u), np.asarray(v)
        k = (len(u) + 1) // 2
        a = real(self, np.concatenate([u[:k], u[:len(u) - k]]),
                 np.concatenate([v[:k], v[:len(v) - k]]))
        return a

    monkeypatch.setattr(QueryService, "answer", half)
    res = run_tiny(reg, "stream20-read")
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", ["stream20-read"])
def test_control_fails_and_program_passes(reg, cell):
    _, summary = control.readings(reg, cell, [7, 8, 9], 0.5, require_tpu=False)
    assert all(v["program_max"] == 0 for v in summary.values())
    assert max(v["control_min"] for v in summary.values()) > 0

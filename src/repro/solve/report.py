"""`SolveReport` — the one result schema every engine maps onto.

The deprecated entry points each reported a different type
(``MSFResult`` / ``DistMSFResult`` / ``CoarsenStats`` /
``DistCoarsenStats`` / ``UpdateStats``); a :class:`SolveReport` carries
the union of what callers actually consume — forest weight, the chosen
global eids, component labels, iteration count, the shortcut's pointer
jumps, the per-level coarsening
rows, the two operational counters (host round-trips, recompiles), and
the analytic ``cost`` of the plan's executable
(``repro.solve.cost.PlanCost``, computed once at ``plan.cost`` —
DESIGN.md §11) — plus the engine-native result under ``raw`` for
anything mode-specific.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Tuple

import numpy as np


class SolveReport(NamedTuple):
    """Uniform result of ``Plan.solve()`` (and ``Plan.update()``)."""

    mode: str  # engine that produced this report
    weight: float  # total forest weight: core.msf.forest_weight, float64 on the host
    msf_eids: np.ndarray  # int32 [n_msf_edges] chosen edge ids, trimmed
    parent: np.ndarray  # int32 [n] component representative per vertex
    n_msf_edges: int
    iterations: int  # hook/shortcut rounds (levels + residual)
    levels: Tuple  # per-level LevelStats rows; () when no levels ran
    host_roundtrips: int  # per-level host round-trips (0 = device-resident)
    recompiles: int  # distinct executables compiled (stream mode)
    raw: Any  # engine-native result (MSFResult / UpdateStats / ...)
    cost: Any = None  # PlanCost of the plan's executable; None off-scope
    stale: bool = False  # stream mode: snapshot may diverge from true MSF
    n_unhealed: int = 0  # stream mode: deletions not certifiably healed
    # p ← p[p] passes of the flat driver's shortcut (flat, and the coarsen
    # residual's flat solve); 0 where no flat driver ran
    shortcut_jumps: int = 0

    @property
    def n_components(self) -> int:
        """Component count from *canonical roots* — the number of
        vertices satisfying ``parent[v] == v`` after pointer-jumping the
        vector to fixpoint. Counting ``np.unique(parent)`` directly
        over-reports on non-canonical labelings (a chain ``2 → 1 → 0``
        has two distinct parent values but one component), and nothing
        in the engine contract promises canonical output."""
        return int(np.count_nonzero(_canonicalize(self.parent)
                                    == np.arange(len(self.parent))))


def _canonicalize(parent) -> np.ndarray:
    """Pointer-jump a parent vector to its root fixpoint (host-side)."""
    p = np.asarray(parent)
    while True:
        gp = p[p]
        if np.array_equal(gp, p):
            return p
        p = gp


def _trim_eids(msf_eids, n_msf_edges) -> np.ndarray:
    return np.asarray(msf_eids)[: int(n_msf_edges)].astype(np.int32)


def report_from_msf_result(
    mode: str,
    r,
    *,
    levels: Tuple = (),
    host_roundtrips: int = 0,
    recompiles: int = 0,
    shortcut_jumps: int = 0,
) -> SolveReport:
    """Adapt an ``MSFResult``/``DistMSFResult``-shaped record; its
    ``weight`` is the host float64 sum of its recorded edge weights."""
    return SolveReport(
        mode=mode,
        weight=r.weight,
        msf_eids=_trim_eids(r.msf_eids, r.n_msf_edges),
        parent=np.asarray(r.parent),
        n_msf_edges=int(r.n_msf_edges),
        iterations=int(r.iterations),
        levels=tuple(levels),
        host_roundtrips=int(host_roundtrips),
        recompiles=int(recompiles),
        raw=r,
        shortcut_jumps=int(shortcut_jumps),
    )

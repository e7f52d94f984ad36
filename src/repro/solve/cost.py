"""Analytic cost of a compiled plan — ``SolveReport.cost``.

At ``plan.build`` time the planner asks this module for a
:class:`PlanCost`: the static flop/byte/collective-byte counts of the
executable the engine will actually run, obtained by abstract-lowering
the jitted driver (no real arrays — ``ShapeDtypeStruct`` stand-ins with
the resolved statics) and running :mod:`repro.analysis.hlo_analyzer`
over the compiled HLO text. Bench rows then carry measured-vs-roofline
fractions, and a regression flagged by the sentinel is attributable to
"got slower" vs "does more work" (the counts changed).

Scope follows the executables the analyzer can see whole:

- **flat** — the ``_msf_jit`` while-loop driver. Its convergence loop is
  dynamic, so ``dynamic_loops > 0`` and the counts are *per iteration*
  (the paper's own unit, Figs 3/4); multiply by ``report.iterations``.
- **coarsen** — the level-0 executable (``fused_level`` under
  ``fused=True``, ``contract_level_und`` otherwise), the shape-dominant
  level of the pipeline. When the target is already at/below the cutoff
  the whole solve is the flat residual and the flat cost is reported.
- **dist / stream** — ``None``: the shard_map program would need a
  second full compile (the lowered executable does not share jax's call
  cache), and stream engines recompile per batch shape.

Analyses are memoized process-wide on (backend, statics, shapes) —
engines rebuilt with the same resolved spec and padded shapes (plan
cache misses after ``clear_plan_cache()``, same-shape sweeps) pay the
lower+compile once. Everything is best-effort: any failure yields
``cost=None`` rather than a failed plan.
"""
from __future__ import annotations

import threading
from typing import NamedTuple, Optional

import numpy as np

_lock = threading.Lock()
_memo: dict = {}


class PlanCost(NamedTuple):
    """Static cost of the plan's dominant executable (per device)."""

    flops: float  # dot_flops + ew_flops
    dot_flops: float
    ew_flops: float
    bytes: float  # HBM traffic under the producer-consumer model
    collective_bytes: float  # inter-device volume (0 off-mesh)
    dynamic_loops: int  # > 0: counts are per-iteration of those loops
    analyzed: str  # which executable the counts describe

    def as_dict(self) -> dict:
        d = self._asdict()
        d["dynamic_loops"] = int(self.dynamic_loops)
        return d


def predicted_time_s(
    cost: Optional[PlanCost], *, iterations: int = 1
) -> Optional[float]:
    """Analytic roofline time of a plan's executable on the chip named by
    ``analysis.roofline.TARGET_DEVICE_KIND``, in seconds — a model, never
    a measurement.

    Per-iteration costs (``dynamic_loops > 0``) are multiplied by the
    ``iterations`` hint. This is the autotuner's pre-measurement pruning
    metric (DESIGN.md §12): only the *ordering* matters, and only at
    order-of-magnitude granularity — the tuner's generous keep-ratio
    absorbs the model error. ``None`` in, ``None`` out.
    """
    if cost is None:
        return None
    from repro.analysis.roofline import TARGET_DEVICE_KIND, peaks

    hw = peaks(TARGET_DEVICE_KIND)
    mult = max(int(iterations), 1) if cost.dynamic_loops else 1
    return mult * max(
        cost.flops / hw["peak_flops_bf16"],
        cost.bytes / hw["hbm_bw"],
    )


def _from_analysis(c: dict, analyzed: str) -> PlanCost:
    return PlanCost(
        flops=float(c["flops"]),
        dot_flops=float(c["dot_flops"]),
        ew_flops=float(c["ew_flops"]),
        bytes=float(c["bytes"]),
        collective_bytes=float(c["collective_bytes"]),
        dynamic_loops=int(c["dynamic_loops"]),
        analyzed=analyzed,
    )


def _abstract(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype))


# ---------------------------------------------------------------------------
# per-mode lowerings
# ---------------------------------------------------------------------------

def _lower_flat(n: int, e: int, rs):
    from repro.core.msf import _msf_jit
    from repro.graphs.structures import Graph

    s = rs.spec
    g = Graph(
        src=_abstract((e,), np.int32),
        dst=_abstract((e,), np.int32),
        w=_abstract((e,), np.float32),
        eid=_abstract((e,), np.int32),
        valid=_abstract((e,), np.bool_),
        n=n,
    )
    lowered = _msf_jit.lower(
        g,
        variant=s.variant,
        shortcut=rs.shortcut,
        capacity=s.capacity,
        max_iters=s.max_iters,
        unroll_guard=s.unroll_guard,
        pack=bool(rs.pack),
        segmin=rs.segmin_flat,
    )
    return lowered, "flat"


def _lower_coarsen(target, rs):
    from repro.coarsen.engine import (
        _canonical_host,
        _eid_capacity,
        fused_level,
    )
    from repro.coarsen.contract import contract_level_und
    from repro.solve.spec import resolve_dedupe, resolve_level_segmins
    from repro.stream.service import next_pow2

    cfg = rs.coarsen
    n0 = int(target.n)
    lo, hi, w, eid, valid, m0 = _canonical_host(target)
    if n0 <= cfg.cutoff or m0 == 0:
        # no levels run — the whole solve is the flat residual
        return _lower_flat(n0, int(np.asarray(target.src).shape[0]), rs)

    use_pack = bool(rs.pack)
    segmin_hook, segmin_dedupe = resolve_level_segmins(cfg.segmin, use_pack)
    pad = len(lo)
    n_pad = next_pow2(n0, floor=8)
    eid_cap = _eid_capacity(eid, m0)
    args = (
        _abstract((pad,), np.int32),  # lo
        _abstract((pad,), np.int32),  # hi
        _abstract((pad,), np.float32),  # w
        _abstract((pad,), np.int32),  # eid
        _abstract((pad,), np.bool_),  # valid
    )
    if cfg.fused:
        lowered = fused_level.lower(
            *args,
            _abstract((n0,), np.int32),  # label_map
            n=n_pad, eid_capacity=eid_cap, rounds=cfg.rounds_per_level,
            pack=use_pack, segmin=segmin_hook, segmin_dedupe=segmin_dedupe,
            dedupe_host=resolve_dedupe(cfg.dedupe) == "host",
        )
        return lowered, "coarsen.level0.fused"
    lowered = contract_level_und.lower(
        *args,
        n=n_pad, eid_capacity=eid_cap, rounds=cfg.rounds_per_level,
        pack=use_pack, segmin=segmin_hook,
    )
    return lowered, "coarsen.level0"


def lower_plan(mode: str, target, rs):
    """``(jax.stages.Lowered, name)`` of the dominant executable a flat
    or coarsen plan over ``target`` runs under the resolved spec ``rs``:
    the ``_msf_jit`` driver (flat), or the level-0 executable (coarsen;
    the flat driver when no level runs). Lowered from shapes alone, with
    the same statics the engine passes, so its compile is the one the
    solve itself needs (and hits a warm persistent compile cache). Raises
    on anything it cannot lower — callers that must not fail wrap it."""
    if mode == "flat":
        return _lower_flat(int(target.n), int(np.asarray(target.src).shape[0]), rs)
    if mode == "coarsen":
        return _lower_coarsen(target, rs)
    raise ValueError(f"no dominant-executable lowering for mode {mode!r}")


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def _memo_key(mode: str, target, rs):
    s = rs.spec
    common = (mode, rs.backend, rs.shortcut, s.capacity, s.max_iters,
              s.variant, bool(rs.pack), s.segmin)
    if mode == "flat":
        return common + (int(target.n), int(np.asarray(target.src).shape[0]))
    if mode == "coarsen":
        return common + (int(target.n), int(np.asarray(target.src).shape[0]),
                         rs.coarsen)
    return None


def plan_cost(mode: str, target, rs) -> Optional[PlanCost]:
    """Best-effort :class:`PlanCost` for a freshly built engine; ``None``
    when out of scope (dist/stream) or on any analysis failure."""
    try:
        if mode not in ("flat", "coarsen") or target is None:
            return None
        if getattr(target, "src", None) is None:  # int n / Partition2D
            return None
        key = _memo_key(mode, target, rs)
        with _lock:
            if key in _memo:
                return _memo[key]
        lowered, analyzed = lower_plan(mode, target, rs)
        from repro.analysis.hlo_analyzer import analyze

        cost = _from_analysis(analyze(lowered.compile().as_text()), analyzed)
        with _lock:
            _memo[key] = cost
        return cost
    except Exception:
        return None

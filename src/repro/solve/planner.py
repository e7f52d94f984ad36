"""``plan(target, spec)`` — compile a :class:`SolveSpec` into a ``Plan``.

The plan compiler resolves the spec against the target (concrete backend
choices), looks the (resolved spec, static shapes, jax backend, mesh)
key up in a bounded per-process cache, and wraps the cached engine in a
cheap :class:`Plan` handle with the uniform surface:

    p = plan(graph, SolveSpec(mode="coarsen", coarsen=cfg))
    report = p.solve()          # -> SolveReport, every mode
    p.update(u, v, w)           # stream mode only
    p.query(u, v)               # stream mode only

Engines are **target-free**: the cache stores compiled machinery
(jitted drivers, level pipelines), never the target's arrays, so two
graphs of the same padded shape share executables — the repeated-solve
path never re-traces. Stream plans are stateful (they own a forest) and
are deliberately *not* cached: every ``plan()`` call builds a fresh
engine, while the underlying jitted union solve still shares the global
jit cache.

``register_engine(mode, builder)`` is the extension point the next
engines (sharded-parent level-0 schedule, all_to_all dedupe) plug into
instead of growing another kwarg on a deprecated entry point.
"""
from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict
from typing import Any, Callable, NamedTuple

import numpy as np

from repro import obs
from repro.solve.report import SolveReport
from repro.solve.spec import MODES, ResolvedSpec, SolveSpec

PLAN_CACHE_MAXSIZE = 64

_lock = threading.Lock()
_cache: "OrderedDict[Any, Any]" = OrderedDict()  # key -> engine (LRU)


class _EngineDef(NamedTuple):
    mode: str
    builder: Callable  # (target, resolved, mesh) -> engine
    cacheable: bool


_engines: dict[str, _EngineDef] = {}


def register_engine(mode: str, builder: Callable, *, cacheable: bool = False):
    """Register a solver engine for ``mode``.

    ``builder(target, resolved, mesh)`` must return an object with
    ``solve(target, *args, **kw) -> SolveReport`` (plus ``update`` /
    ``query`` for streaming-style engines). Set ``cacheable=True`` only
    if the engine is target-free and safe to share across plans of the
    same (resolved spec, shapes, backend, mesh) key. Registering a mode
    also makes it a legal ``SolveSpec.mode`` value.
    """
    from repro.solve import spec as _spec_mod

    _engines[mode] = _EngineDef(mode, builder, cacheable)
    if mode not in MODES:
        _spec_mod.EXTRA_MODES.add(mode)
    return builder


def registered_modes() -> tuple:
    return tuple(_engines)


# ---------------------------------------------------------------------------
# plan cache
# ---------------------------------------------------------------------------

def _shape_key(target) -> tuple:
    """Static-shape fingerprint of a plan target (never its data)."""
    if target is None:
        return ("none",)
    if isinstance(target, (int, np.integer)):
        return ("n", int(target))
    shard = getattr(target, "shard_size", None)
    if shard is not None:  # Partition2D
        return (
            "part2d", target.rows, target.cols, target.e_max,
            target.n, target.n_pad, shard,
        )
    src = getattr(target, "src", None)
    if src is not None:  # Graph
        return ("graph", target.n, int(src.shape[0]))
    raise TypeError(f"cannot plan against target of type {type(target).__name__}")


def _cache_get(key):
    with _lock:
        eng = _cache.get(key)
        if eng is not None:
            _cache.move_to_end(key)
        return eng


def _cache_put(key, engine):
    with _lock:
        _cache[key] = engine
        _cache.move_to_end(key)
        while len(_cache) > PLAN_CACHE_MAXSIZE:
            _cache.popitem(last=False)


def plan_cache_info() -> tuple:
    """(current entries, max entries) of the per-process plan cache."""
    with _lock:
        return len(_cache), PLAN_CACHE_MAXSIZE


def clear_plan_cache() -> None:
    with _lock:
        _cache.clear()


# ---------------------------------------------------------------------------
# the compiler
# ---------------------------------------------------------------------------

def plan(target, spec: SolveSpec | None = None, *, mesh=None, **overrides) -> "Plan":
    """Compile ``spec`` against ``target`` into a reusable :class:`Plan`.

    ``target``: a ``Graph`` (flat / coarsen), a ``Partition2D`` of the
    original graph plus ``mesh=`` (dist), or an ``int`` vertex count or
    ``Graph`` (stream — only ``n`` is read). ``spec`` defaults to
    ``SolveSpec()``; keyword ``overrides`` are folded into it
    (``plan(g, mode="coarsen")`` is shorthand for
    ``plan(g, SolveSpec(mode="coarsen"))``).
    """
    from repro.solve import engines as _  # noqa: F401 — registers built-ins

    if spec is None:
        spec = SolveSpec(**overrides)
    elif overrides:
        spec = dataclasses.replace(spec, **overrides)
    edef = _engines.get(spec.mode)
    if edef is None:
        raise ValueError(
            f"no engine registered for mode {spec.mode!r} "
            f"(registered: {registered_modes()})"
        )
    if spec.mode == "dist" and mesh is None:
        raise ValueError("mode='dist' needs a mesh= (jax Mesh over the 2D grid)")
    with obs.enabled(spec.obs):
        with obs.span("plan.resolve", mode=spec.mode):
            # mesh only keys the tuning-DB lookup (dist entries are
            # bucketed per mesh shape); heuristic resolution ignores it.
            resolved = spec.resolve(target, mesh=mesh)
        engine = None
        key = None
        if edef.cacheable:
            # The key carries the *resolved* spec (concrete pack/segmin/
            # dedupe choices), not just the user spec: two same-shape
            # targets whose data resolves differently (e.g. integral vs
            # float weights under pack=None) must not share an engine.
            key = (resolved, _shape_key(target), mesh)
            engine = _cache_get(key)
            if obs.metrics_active():
                obs.counter(
                    "plan.cache.hit" if engine is not None
                    else "plan.cache.miss"
                ).inc()
        if engine is None:
            # The compile span: builders construct/trace the jitted
            # drivers (dist mode traces the whole shard_map program here).
            with obs.span("plan.build", mode=spec.mode):
                engine = edef.builder(target, resolved, mesh)
            # Analytic cost of the executable this engine runs (flat /
            # coarsen scope; best-effort): it lowers and compiles that
            # executable. Stored on the engine so cache hits reuse the
            # analysis with the compiled machinery.
            from repro.solve.cost import plan_cost

            with obs.span("plan.cost", mode=spec.mode):
                engine._plan_cost = plan_cost(spec.mode, target, resolved)
            if key is not None:
                _cache_put(key, engine)
    return Plan(spec=spec, resolved=resolved, target=target, mesh=mesh, engine=engine)


class Plan:
    """A compiled solve: spec + resolved backends + a (possibly shared)
    engine, bound to one target. Handles are cheap; the engine inside is
    what the plan cache reuses across same-shape targets."""

    def __init__(self, *, spec, resolved, target, mesh, engine):
        self.spec: SolveSpec = spec
        self.resolved: ResolvedSpec = resolved
        self.target = target
        self.mesh = mesh
        self._engine = engine

    @property
    def mode(self) -> str:
        return self.spec.mode

    @property
    def driver(self):
        """The engine-native callable (dist mode: the jitted block driver
        or the ``DistCoarsenMSF`` instance) — what the deprecated
        ``msf_distributed`` shim hands back for bit-identical call
        patterns. ``None`` for engines without one."""
        return getattr(self._engine, "driver", None)

    @property
    def engine(self):
        """The engine-native stateful object, for introspection beyond
        the report schema (stream mode: the ``StreamEngine`` —
        ``forest_edges()``, ``union_edge_capacity``, ...). Public so
        callers never reach through plan internals; the uniform surface
        is still ``solve()``/``update()``/``query()``."""
        return getattr(self._engine, "engine", self._engine)

    @property
    def service(self):
        """Stream mode: the engine's shared
        :class:`~repro.stream.service.QueryService` — reads from the
        published snapshot store, safe to call from any thread while the
        single writer applies ``update()``/``delete()``. The serving
        tier (``repro.serve.MSFServer``) batches through this seam."""
        svc = getattr(self._stream(), "service", None)
        if svc is None:
            raise ValueError(
                f"service is a stream-mode surface; this plan's mode "
                f"is {self.mode!r}"
            )
        return svc

    @property
    def cost(self):
        """Analytic :class:`~repro.solve.cost.PlanCost` of this plan's
        executable, computed once at build (``None`` when out of the
        analyzer's scope — dist/stream — or on analysis failure)."""
        return getattr(self._engine, "_plan_cost", None)

    def _attach_cost(self, rep):
        if isinstance(rep, SolveReport) and rep.cost is None:
            c = self.cost
            if c is not None:
                rep = rep._replace(cost=c)
        return rep

    def _observed(self, what: str, call):
        """Run one engine call under a ``solve.<mode>[.<what>]`` span and
        this spec's ``obs`` scope, then attach the plan's analytic cost."""
        name = f"solve.{self.spec.mode}" + (f".{what}" if what else "")
        with obs.enabled(self.spec.obs), obs.span(name):
            rep = call()
        return self._attach_cost(rep)

    def solve(self, *args, **kw) -> SolveReport:
        """Run the full solve for this plan's target. Dist plans accept
        the five block arrays positionally to override the target's own
        (the deprecated driver call pattern); flat plans accept
        ``parent0=`` for warm starts."""
        return self._observed(
            "", lambda: self._engine.solve(self.target, *args, **kw)
        )

    # -- stream-mode surfaces -------------------------------------------

    def _stream(self):
        if not hasattr(self._engine, "update"):
            raise ValueError(
                f"update()/query() are stream-mode surfaces; this plan's "
                f"mode is {self.mode!r}"
            )
        return self._engine

    def update(self, u, v, w) -> SolveReport:
        """Stream mode: apply one batch of edge insertions."""
        eng = self._stream()
        return self._observed("update", lambda: eng.update(u, v, w))

    def delete(self, u, v) -> SolveReport:
        """Stream mode: delete a batch of edges (exact replacement-edge
        search by default; tombstones under ``exact_deletes=False``)."""
        eng = self._stream()
        return self._observed("delete", lambda: eng.delete(u, v))

    def recertify(self, u, v, w) -> SolveReport:
        """Stream mode: rebuild forest + reservoir exactly from a
        caller-supplied surviving edge multiset — the recovery path when
        ``SolveReport.n_unhealed > 0`` after reservoir exhaustion."""
        eng = self._stream()
        if not hasattr(eng, "recertify"):
            raise ValueError(
                f"recertify() is a stream-mode surface; this plan's "
                f"mode is {self.mode!r}"
            )
        return self._observed("recertify", lambda: eng.recertify(u, v, w))

    def query(self, u, v):
        """Stream mode: batched connectivity queries against the latest
        published snapshot; returns a bool array."""
        eng = self._stream()
        return self._observed("query", lambda: eng.query(u, v))

    def compact(self) -> SolveReport:
        """Stream mode: drop tombstones and rebuild the forest."""
        eng = self._stream()
        return self._observed("compact", lambda: eng.compact())

    def __repr__(self):
        return (
            f"Plan(mode={self.mode!r}, target={_shape_key(self.target)}, "
            f"pack={self.resolved.pack}, dedupe={self.resolved.dedupe!r})"
        )

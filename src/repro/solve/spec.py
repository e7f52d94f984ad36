"""Declarative solver specification — the single front door (DESIGN.md §9).

``SolveSpec`` is a frozen, hashable description of *which* MSF engine to
run (``mode``: flat / coarsen / dist / stream) and *how* (backend knobs:
pack / segmin / dedupe / fused / shortcut / variant, plus the mode's own
parameters). Validation that used to live in scattered ``raise`` sites
(``core.msf.msf``, ``coarsen.engine``, ``coarsen.dist``,
``stream.engine``) happens once, in ``__post_init__`` (static rules) and
:meth:`SolveSpec.resolve` (data-dependent rules).

This module is also the single home of every **backend auto-detect
rule** the engines used to duplicate:

- :func:`auto_pack` / :func:`weights_packable` — the pack32 regime test
  (integral weights in [0, 255], 24-bit indices);
- :func:`resolve_dedupe` — ``dedupe="auto"`` → device on TPU, host
  elsewhere;
- :func:`resolve_flat_segmin` / :func:`resolve_level_segmins` — segment-
  min backend selection for flat (unsorted-segment) reductions and for
  the coarsening level kernels (hook + dedupe sites), delegating the
  kernel-choice callables to ``repro.kernels.ops``.

Engines call these helpers; the public API calls
:meth:`SolveSpec.resolve`, which orchestrates all of them and returns a
concrete :class:`ResolvedSpec`. No engine re-implements a rule.

Import discipline: this module sits *below* the engines (they import
it), so its module-level imports stop at leaf layers
(``core.semiring``); ``coarsen.config`` and ``kernels.ops`` are pulled
lazily inside functions (importing ``repro.coarsen.config`` runs the
``repro.coarsen`` package init, whose engine imports this module back).
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import numpy as np

from repro.core.semiring import PACK_IDX_MASK

MODES = ("flat", "coarsen", "dist", "stream")
#: Observability levels of the ``obs`` knob (DESIGN.md §10): "off" = spans
#: only reach a recording profiler, "metrics" = span-duration histograms
#: + counters in the process-global registry too. Either mode runs the
#: same executables; a ``jax.profiler`` session is what records a trace.
OBS_MODES = ("off", "metrics")
#: Tuning-database consultation levels of the ``tuning`` knob
#: (DESIGN.md §12): "off" = the hand-written heuristics below, "db" =
#: consult the active ``tuning-db/v1`` database first (exact key, then
#: nearest shape bucket) and fall back to the heuristics when it is
#: missing/invalid/non-matching, "measure" = tune the target on first
#: resolve and cache the winner in the in-process database. The knob is
#: part of the spec (and therefore of every resolved plan-cache key), so
#: parity suites can pin behavior with ``tuning="off"``.
TUNING_MODES = ("off", "db", "measure")
#: Modes added by ``repro.solve.register_engine`` beyond the built-ins.
#: Mode-specific validation below only applies to the built-in modes; a
#: registered engine owns its own validation.
EXTRA_MODES: set = set()
VARIANTS = ("complete", "paper", "pairwise")
#: Shortcut strategies per driver family. ``None`` in a spec means "the
#: mode's default": "complete" for the single-device drivers, "csp" for
#: the distributed Fig-2 solve.
FLAT_SHORTCUTS = (None, "complete", "csp", "os")
DIST_SHORTCUTS = (None, "csp", "os", "baseline")


# ---------------------------------------------------------------------------
# backend auto-detect rules (the engines' former duplicated copies)
# ---------------------------------------------------------------------------

def weights_packable(w) -> bool:
    """The pack32 weight regime: integral values in [0, 255] (paper §VII).

    The streaming engine applies this per insert batch (its packability
    is a running conjunction); :func:`auto_pack` applies it to a whole
    edge array at once.
    """
    w = np.asarray(w)
    if w.size == 0:
        return True
    return bool(np.all(w == np.floor(w)) and w.min() >= 0 and w.max() <= 255)


def auto_pack(w, eid, valid, e_capacity: int) -> bool:
    """pack32 applies when weights are integral in [0, 255] and both the
    global eids and the per-level position indices fit 24 bits strictly."""
    if e_capacity >= PACK_IDX_MASK:
        return False
    w = np.asarray(w)
    eid = np.asarray(eid)
    valid = np.asarray(valid)
    wv = w[valid]
    if wv.size == 0:
        return True
    if not weights_packable(wv):
        return False
    return int(eid[valid].max()) < PACK_IDX_MASK


def resolve_dedupe(dedupe: str, backend: str | None = None) -> str:
    """``dedupe="auto"`` → the in-jit device pipeline on TPU, the numpy
    lexsort twin elsewhere (XLA's CPU sort loses ~5× to numpy's)."""
    if dedupe != "auto":
        return dedupe
    backend = backend or jax.default_backend()
    return "device" if backend == "tpu" else "host"


def resolve_flat_segmin(segmin: str | None, pack: bool):
    """Packed segment-min callable for a *flat* reduction site (the MSF
    hook loops, the residual solve — unsorted segment ids).

    "sorted" is dedupe-only (the contiguous-range kernel silently loses
    out-of-order contributions) and degrades to "auto" here; with
    ``pack=False`` there is no packed reduction and the request is
    ignored. Returns a callable for ``core.msf._msf_jit``'s ``segmin``
    static, or ``None``.
    """
    if not pack:
        return None
    from repro.kernels.ops import flat_segmin_backend, make_packed_segmin

    return make_packed_segmin(flat_segmin_backend(segmin) or "auto")


def resolve_level_segmins(segmin: str | None, use_pack: bool):
    """(hook segmin, dedupe segmin) callables for the coarsening level
    kernels.

    The hook reduction (``coarsen.contract``) sees *unsorted* segment ids
    (roots of the current parent vector), so "sorted" degrades to "auto"
    there. The dedupe's ids are the boundary prefix-sum over sorted pair
    keys — its resolution delegates to
    ``kernels.ops.dedupe_segmin_backend`` (shared with the distributed
    fused level).
    """
    if not use_pack:
        return None, None
    from repro.kernels.ops import (
        dedupe_segmin_backend,
        flat_segmin_backend,
        make_packed_segmin,
    )

    hook = None
    if segmin not in (None, "jnp"):
        hook = make_packed_segmin(flat_segmin_backend(segmin))
    return hook, dedupe_segmin_backend(segmin)


# ---------------------------------------------------------------------------
# the spec
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SolveSpec:
    """Frozen, hashable description of one MSF solve configuration.

    ``mode`` selects the engine; the backend knobs (``pack``, ``segmin``,
    ``dedupe``, ``fused``, ``shortcut``, ``variant``) mean the same thing
    in every mode; the trailing blocks parameterize one mode each and are
    ignored by the others. ``None`` for a knob means "auto": concrete
    values are chosen by :meth:`resolve` against the target's data.
    """

    mode: str = "flat"
    # algorithm knobs (flat driver + coarsen/stream residual solves)
    variant: str = "complete"
    shortcut: str | None = None  # None = mode default (complete / csp)
    capacity: int = 1 << 16  # CSP/OS changed-map capacity
    max_iters: int | None = None
    unroll_guard: bool = True
    # backend knobs
    pack: bool | None = None  # pack32 inner loops; None = auto-detect
    segmin: str | None = None  # packed segment-min backend request
    dedupe: str = "auto"  # coarsen dedupe: "auto" | "device" | "host"
    fused: bool | None = None  # one-jit device-resident levels
    # coarsening levels ("coarsen" mode; optional prelude for dist/stream)
    coarsen: CoarsenConfig | None = None
    # stream mode
    batch_capacity: int = 1024
    adaptive_capacity: bool = False
    min_capacity: int = 16
    compact_trigger: float = 0.25
    coarsen_threshold: int = 1 << 15
    reservoir_capacity: int = 4096
    reservoir_per_component: int = 256
    exact_deletes: bool = True
    # dist mode
    row_axis: str = "data"
    col_axis: str = "model"
    # observability: "off" | "metrics" (DESIGN.md §10). Scoped around
    # every Plan.solve()/update()/query() of this spec.
    obs: str = "off"
    # tuning-database consultation: "off" | "db" | "measure"
    # (DESIGN.md §12, ``repro.solve.tune``).
    tuning: str = "off"

    def __post_init__(self):
        from repro.coarsen.config import (
            DEDUPE_BACKENDS,
            SEGMIN_BACKENDS,
            CoarsenConfig,
        )

        if self.mode not in MODES and self.mode not in EXTRA_MODES:
            raise ValueError(f"unknown mode {self.mode!r} (expected one of {MODES})")
        # obs is infrastructure, not engine policy — validated for
        # registered modes too (the plan layer applies it uniformly).
        if self.obs not in OBS_MODES:
            raise ValueError(
                f"unknown obs mode {self.obs!r} (expected one of {OBS_MODES})"
            )
        # tuning is resolve-layer infrastructure, validated for
        # registered modes too (the lookup is keyed by mode string).
        if self.tuning not in TUNING_MODES:
            raise ValueError(
                f"unknown tuning mode {self.tuning!r} "
                f"(expected one of {TUNING_MODES})"
            )
        if self.coarsen is True:  # convenience: True → defaults
            object.__setattr__(self, "coarsen", CoarsenConfig())
        if self.coarsen is not None and not isinstance(self.coarsen, CoarsenConfig):
            raise ValueError(
                f"coarsen must be a CoarsenConfig, True, or None; "
                f"got {self.coarsen!r}"
            )
        if self.mode not in MODES:
            return  # registered engines own their mode-specific rules
        if self.variant not in VARIANTS:
            raise ValueError(
                f"unknown variant {self.variant!r} (expected one of {VARIANTS})"
            )
        allowed = DIST_SHORTCUTS if self.mode == "dist" else FLAT_SHORTCUTS
        if self.shortcut not in allowed:
            raise ValueError(
                f"unknown {self.mode} shortcut {self.shortcut!r} "
                f"(expected one of {allowed})"
            )
        if self.segmin not in SEGMIN_BACKENDS:
            raise ValueError(
                f"unknown segmin backend {self.segmin!r} "
                f"(expected one of {SEGMIN_BACKENDS})"
            )
        if self.dedupe not in DEDUPE_BACKENDS:
            raise ValueError(f"unknown dedupe backend {self.dedupe!r}")
        if self.mode == "flat":
            if self.coarsen is not None:
                raise ValueError(
                    "coarsen levels need mode='coarsen' (or 'dist'/'stream' "
                    "with a coarsen prelude), not mode='flat'"
                )
            if self.fused:
                raise ValueError(
                    "fused=True requires coarsen= (it fuses the levels)"
                )
            if self.segmin == "sorted":
                raise ValueError(
                    "segmin='sorted' needs sorted segment ids — only the "
                    "coarsen dedupe provides them; the flat hook loop's ids "
                    "are unsorted (use 'pallas'/'jnp'/'auto' here)"
                )
            if self.pack is False and self.segmin not in (None, "auto"):
                raise ValueError(
                    "segmin= only applies to the pack=True inner loop"
                )
        if self.mode == "stream":
            if self.batch_capacity < 1:
                raise ValueError("batch_capacity must be >= 1")
            if self.min_capacity < 1:
                raise ValueError("min_capacity must be >= 1")
            if self.coarsen_threshold < 0:
                raise ValueError("coarsen_threshold must be >= 0")
            if self.reservoir_capacity < 0:
                raise ValueError("reservoir_capacity must be >= 0")
            if self.reservoir_per_component < 1:
                raise ValueError("reservoir_per_component must be >= 1")
        if self.capacity < 1:
            raise ValueError("capacity must be >= 1")

    # ------------------------------------------------------------------

    def resolve(
        self, target=None, *, backend: str | None = None, mesh=None
    ) -> "ResolvedSpec":
        """Turn auto knobs into concrete backend choices for ``target``.

        ``target`` is whatever :func:`repro.solve.plan` compiles against:
        a ``Graph`` (flat/coarsen/stream), a ``Partition2D`` (dist), an
        ``int`` vertex count (stream), or ``None`` (static resolution
        only). Every data-dependent validation and auto-detection lives
        here — engines receive concrete values. With ``tuning != "off"``
        the persisted tuning database is consulted first
        (``repro.solve.tune``, DESIGN.md §12): a compatible winner fills
        the knobs the user left on auto, and everything below resolves
        the *effective* spec; on any DB failure the heuristics run
        untouched. ``mesh`` only keys the tuning lookup (dist plans).
        """
        from repro.coarsen.config import CoarsenConfig

        backend = backend or jax.default_backend()
        eff = self
        if self.tuning != "off":
            from repro.solve.tune import resolve_overrides

            tuned = resolve_overrides(self, target, backend, mesh)
            if tuned is not None:
                eff = tuned
        pack = eff.pack
        if pack is None:
            if self.mode == "stream":
                # Stream keeps None — its engine tracks packability per
                # batch (a running conjunction over the insert stream),
                # degrading automatically near the pack32 index bound; a
                # Graph target only contributes its n here.
                pass
            else:
                arrays = _pack_probe_arrays(target)
                # No data to probe: the conservative float path.
                pack = auto_pack(*arrays) if arrays is not None else False
        if self.mode == "stream" and pack is True and target is not None:
            n = _stream_n(target)
            union = (n - 1) + eff.batch_capacity
            if union >= PACK_IDX_MASK:
                raise ValueError(
                    f"pack=True needs union eids < 2^24 - 1; (n - 1) + "
                    f"batch_capacity = {union} overflows the pack32 index "
                    f"field"
                )
        shortcut = eff.shortcut or ("csp" if self.mode == "dist" else "complete")
        coarsen = eff.coarsen
        if coarsen is None and self.mode in ("coarsen",):
            coarsen = CoarsenConfig()
        if coarsen is not None:
            # Spec-level segmin/fused override the embedded config — the
            # precedence the deprecated kwarg paths had — and dedupe joins
            # them (the old paths had no dedupe kwarg). spec.pack is
            # deliberately NOT folded in: historically the pack kwarg
            # steered only the residual/union solve while the levels kept
            # config.pack (usually None = per-level auto-detect), and
            # forcing an explicit pack onto the level kernels would run
            # pack32 on data the levels never validated.
            merged = {}
            if eff.segmin is not None:
                merged["segmin"] = eff.segmin
            if eff.dedupe != "auto":
                merged["dedupe"] = eff.dedupe
            if eff.fused is not None:
                merged["fused"] = eff.fused
            if merged:
                coarsen = dataclasses.replace(coarsen, **merged)
        # spec=eff, not self: engines read knobs through rs.spec, and the
        # plan-cache key must reflect the knobs actually in effect (eff
        # keeps self.tuning, so "db" and "off" never share a key even
        # when the database is empty).
        return ResolvedSpec(
            spec=eff,
            backend=backend,
            pack=pack,
            shortcut=shortcut,
            segmin_flat=resolve_flat_segmin(eff.segmin, bool(pack)),
            dedupe=resolve_dedupe(eff.dedupe, backend),
            coarsen=coarsen,
        )


class ResolvedSpec(NamedTuple):
    """Concrete backend choices for one (spec, target, jax backend)."""

    spec: SolveSpec
    backend: str  # jax backend the choices were made for
    pack: bool | None  # None only in stream mode (tracked per batch)
    shortcut: str
    segmin_flat: Any  # packed-segmin callable for flat hook loops, or None
    dedupe: str  # "device" | "host"
    coarsen: CoarsenConfig | None  # effective config, spec knobs folded in


def _pack_probe_arrays(target):
    """(w, eid, valid, e_capacity) host views for :func:`auto_pack`, or
    ``None`` when the target carries no edge data (int n / None)."""
    if target is None or isinstance(target, (int, np.integer)):
        return None
    w = getattr(target, "w", None)
    eid = getattr(target, "eid", None)
    valid = getattr(target, "valid", None)
    if w is None or eid is None or valid is None:
        return None
    w = np.asarray(w).reshape(-1)
    eid = np.asarray(eid).reshape(-1)
    valid = np.asarray(valid).reshape(-1)
    return w, eid, valid, int(eid.shape[0])


def _stream_n(target) -> int:
    if isinstance(target, (int, np.integer)):
        return int(target)
    n = getattr(target, "n", None)
    if n is None:
        raise ValueError(
            "stream mode needs a vertex count: pass an int n or a Graph"
        )
    return int(n)

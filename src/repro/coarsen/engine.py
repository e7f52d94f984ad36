"""Coarsening engine: alternate contract and filter levels, then hand the
residual graph to the flat AS solver (DESIGN.md §7).

Each level runs K hook+shortcut rounds (``contract.contract_level``), a
device-side rank/relabel, and the sort-dedupe edge filter
(``filter.filter_level``). Both n and m shrink geometrically, so the
dense O(n) vector work and the O(m) multilinear sweeps of the flat
solver only ever touch the *current* level's padded arrays. When the
supervertex count drops below ``cutoff`` (or edges run out, or a level
stops making progress), the residual graph goes to ``core.msf``.

Shapes are re-padded to powers of two between levels (host-driven, like
the streaming engine), so compiled executables are bounded by
log2(E) × levels rather than one per input.

Invariants (DESIGN.md §7.4):
- every hooked edge is an MSF edge of the *original* graph (cut property
  under the distinct (w, eid) total order), recorded by global eid;
- filtering is exact: a dropped parallel edge closes a cycle on which it
  is not the (w, eid)-minimum (cycle property);
- ``label_map`` composes the per-level relabelings, so original-vertex
  component labels are a single gather at the end.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.coarsen.config import CoarsenConfig
from repro.coarsen.contract import contract_level, contract_level_und
from repro.coarsen.filter import (
    filter_level,
    filter_level_callback,
    filter_level_host,
)
from repro.core.msf import MSFResult, flat_msf as _flat_msf
from repro.graphs.partition import Partition2D, partition_edges_2d
from repro.graphs.structures import Graph, graph_from_canonical
from repro.solve.spec import auto_pack, resolve_dedupe, resolve_level_segmins
from repro.stream.service import next_pow2

_IMAX = np.int32(np.iinfo(np.int32).max)


class LevelStats(NamedTuple):
    n: int  # vertices entering the level
    m: int  # undirected edges entering the level
    n_next: int  # supervertices after contraction
    m_next: int  # unique live pairs after filtering
    hooked: int  # MSF edges recorded this level


class CoarsenStats(NamedTuple):
    levels: Tuple[LevelStats, ...]
    residual_n: int
    residual_m: int


class CoarsenPrelude(NamedTuple):
    """Everything the contraction levels decided, residual not yet solved."""

    msf_eids: np.ndarray  # global eids of level-hooked MSF edges
    msf_w: np.ndarray  # float32: their weights, in the same order
    label_map: np.ndarray  # int32 [n0]: original vertex → residual vertex id
    residual: Graph  # canonical symmetric residual graph
    stats: CoarsenStats
    # hook+shortcut rounds the levels actually ran (levels × rounds_per_level)
    # — threaded so finalizers report true iteration counts instead of
    # re-deriving them from a config they may not see (merge_distributed
    # used to hard-code 1 round per level and under-report).
    level_iters: int = 0


def _next_pow2(k: int) -> int:
    return next_pow2(k, floor=8)  # edge buffers tolerate a smaller floor


def _eid_capacity(eid: np.ndarray, m0: int) -> int:
    """Static pow2 bound on the global eids carried by the levels — sizes
    the eid→position hook-payload table of ``contract_level_und``."""
    if m0 == 0:
        return 8
    return _next_pow2(int(np.asarray(eid[:m0]).max()) + 1)


def _canonical_host(graph: Graph):
    """Host copies of the undirected (lo < hi) edge set, pow2-padded."""
    src = np.asarray(graph.src)
    dst = np.asarray(graph.dst)
    w = np.asarray(graph.w)
    eid = np.asarray(graph.eid)
    valid = np.asarray(graph.valid)
    sel = valid & (src < dst)
    m0 = int(sel.sum())
    pad = _next_pow2(m0)
    lo = np.zeros(pad, np.int32)
    hi = np.zeros(pad, np.int32)
    ww = np.full(pad, np.inf, np.float32)
    ee = np.full(pad, _IMAX, np.int32)
    vv = np.zeros(pad, bool)
    lo[:m0], hi[:m0] = src[sel], dst[sel]
    ww[:m0], ee[:m0] = w[sel], eid[sel]
    vv[:m0] = True
    return lo, hi, ww, ee, vv, m0


class FusedLevel(NamedTuple):
    """One coarsening level's outputs, all device-resident, edge arrays at
    the (static) input capacity with live entries front-packed."""

    lo: jax.Array  # int32 [E] — supervertex pairs, lo < hi
    hi: jax.Array  # int32 [E]
    w: jax.Array  # float32 [E]; +inf beyond m_new
    eid: jax.Array  # int32 [E] — original global eids; IMAX beyond m_new
    valid: jax.Array  # bool [E]
    m_new: jax.Array  # int32 scalar: unique live pairs
    new_ids: jax.Array  # int32 [n]: vertex → supervertex rank
    n_next: jax.Array  # int32 scalar: supervertex count (incl. padding roots)
    msf_eids: jax.Array  # int32 [n]: global eids hooked (front-packed)
    msf_w: jax.Array  # float32 [n]: their weights, at the same positions
    n_msf_edges: jax.Array  # int32 scalar
    label_map: jax.Array  # int32 [n0]: original vertex → supervertex id


@partial(
    jax.jit,
    static_argnames=(
        "n", "eid_capacity", "rounds", "pack", "segmin", "segmin_dedupe",
        "dedupe_host",
    ),
)
def fused_level(
    lo: jax.Array,
    hi: jax.Array,
    w: jax.Array,
    eid: jax.Array,
    valid: jax.Array,
    label_map: jax.Array,
    *,
    n: int,
    eid_capacity: int,
    rounds: int = 2,
    pack: bool = False,
    segmin=None,
    segmin_dedupe=None,
    dedupe_host: bool = False,
) -> FusedLevel:
    """One whole coarsening level under a single jit (DESIGN.md §7.6).

    contract (K hook+shortcut rounds) → rank_relabel → sort → sorted-
    segment dedupe → compaction, with zero host round-trips inside the
    level. Compaction is device-side and comes out of the dedupe's
    prefix-sum: segment ids are ranks of the sorted pair keys (a cumsum
    over boundary flags), invalid entries sort last, so scattering each
    segment's winner to its rank front-packs the live edges — the
    engine's between-level re-pad is then a device slice, not a host
    gather. Dead tail slots are sanitized to the sort sentinels
    (w = +inf, eid = IMAX) so the next level's dedupe ordering stays
    exact under the (w, eid) total order.

    Inputs are the *undirected* canonical arrays at a static pow2
    capacity; ``label_map`` is the [n0] original-vertex composition,
    threaded through so it too stays device-resident. One executable per
    (n, edge-capacity, n0) shape triple.

    ``dedupe_host=True`` swaps the dedupe stage for the zero-copy host
    callback (:func:`filter_level_callback`) — the CPU backend of
    ``dedupe="auto"``, where XLA's sort loses ~5× to numpy's; on TPU the
    engine keeps the device pipeline (sort + sorted-segment Pallas
    kernel) so the level never leaves the accelerator.
    """
    res = contract_level_und(
        lo, hi, w, eid, valid,
        n=n, eid_capacity=eid_capacity, rounds=rounds, pack=pack, segmin=segmin,
    )
    if dedupe_host:
        fr = filter_level_callback(
            lo, hi, w, eid, valid, res.new_ids, n=n
        )
    else:
        fr = filter_level(
            lo, hi, w, eid, valid, res.new_ids, n=n, pack=pack,
            segmin=segmin_dedupe,
        )
    return FusedLevel(
        lo=fr.lo,  # filter sanitizes dead slots to the sort identities
        hi=fr.hi,
        w=fr.w,
        eid=fr.eid,
        valid=fr.valid,
        m_new=fr.m_new,
        new_ids=res.new_ids,
        n_next=res.n_next,
        msf_eids=res.msf_eids,
        msf_w=res.msf_w,
        n_msf_edges=res.n_msf_edges,
        label_map=res.new_ids[label_map],
    )


def _run_levels_fused(
    graph: Graph, cfg: CoarsenConfig, use_pack: bool, canon, eid_cap: int
) -> CoarsenPrelude:
    """Level loop over :func:`fused_level`: edge arrays and ``label_map``
    stay on device across levels; only per-level scalars (n_next, m_new)
    and the hooked eids cross to the host for loop control/bookkeeping.
    ``canon`` is the device copy of :func:`_canonical_host`'s arrays."""
    segmin_hook, segmin_dedupe = resolve_level_segmins(cfg.segmin, use_pack)
    dedupe = resolve_dedupe(cfg.dedupe)
    n0 = graph.n
    lo, hi, w, eid, valid, m_cur = canon
    label_map = jnp.arange(n0, dtype=jnp.int32)

    eids_acc: list[np.ndarray] = []
    w_acc: list[np.ndarray] = []
    stats: list[LevelStats] = []
    n_cur = n0

    while len(stats) < cfg.max_levels and n_cur > cfg.cutoff and m_cur > 0:
        n_pad = next_pow2(n_cur, floor=8)
        # The span ends after the level's scalars and hooked eids are on
        # the host: the first read waits for the level's executable.
        with obs.span("coarsen.level", level=len(stats), n=n_cur, m=m_cur):
            res = fused_level(
                lo, hi, w, eid, valid, label_map,
                n=n_pad, eid_capacity=eid_cap, rounds=cfg.rounds_per_level,
                pack=use_pack, segmin=segmin_hook,
                segmin_dedupe=segmin_dedupe, dedupe_host=dedupe == "host",
            )
            n_next = int(res.n_next) - (n_pad - n_cur)  # drop padding roots
            if n_next == n_cur:  # every component already complete
                break
            n_f = int(res.n_msf_edges)
            eids_acc.append(np.asarray(res.msf_eids[:n_f]))
            w_acc.append(np.asarray(res.msf_w[:n_f]))
            m_next = int(res.m_new)
        pad = _next_pow2(m_next)
        lo, hi, w, eid, valid = (
            res.lo[:pad], res.hi[:pad], res.w[:pad], res.eid[:pad],
            res.valid[:pad],
        )
        label_map = res.label_map
        stats.append(LevelStats(n=n_cur, m=m_cur, n_next=n_next,
                                m_next=m_next, hooked=n_f))
        n_cur, m_cur = n_next, m_next

    residual = graph_from_canonical(
        lo, hi, w, eid, valid, next_pow2(n_cur, floor=8)
    )
    with obs.span("solve.fetch"):
        label_map = np.asarray(label_map)
    return CoarsenPrelude(
        msf_eids=_concat(eids_acc, np.int32),
        msf_w=_concat(w_acc, np.float32),
        label_map=label_map,
        residual=residual,
        stats=CoarsenStats(levels=tuple(stats), residual_n=n_cur,
                           residual_m=m_cur),
        level_iters=len(stats) * cfg.rounds_per_level,
    )


def run_levels(graph: Graph, config: CoarsenConfig | None = None) -> CoarsenPrelude:
    """Contract-and-filter until the cutoff; return the residual + prelude."""
    cfg = config or CoarsenConfig()
    n0 = graph.n
    with obs.span("coarsen.canonical"):
        lo, hi, w, eid, valid, m_cur = _canonical_host(graph)
        use_pack = (
            auto_pack(np.asarray(graph.w), np.asarray(graph.eid),
                      np.asarray(graph.valid), 2 * len(lo))
            if cfg.pack is None
            else cfg.pack
        )
        eid_cap = _eid_capacity(eid, m_cur)
        if cfg.fused:  # the levels keep their edge arrays on the device
            lo, hi, w, eid, valid = (
                jnp.asarray(a) for a in (lo, hi, w, eid, valid)
            )
    if cfg.fused:
        return _run_levels_fused(
            graph, cfg, use_pack, (lo, hi, w, eid, valid, m_cur), eid_cap
        )
    segmin_fn, segmin_dedupe_fn = resolve_level_segmins(cfg.segmin, use_pack)
    dedupe = resolve_dedupe(cfg.dedupe)

    label_map = np.arange(n0, dtype=np.int32)
    eids_acc: list[np.ndarray] = []
    w_acc: list[np.ndarray] = []
    stats: list[LevelStats] = []
    n_cur = n0

    while len(stats) < cfg.max_levels and n_cur > cfg.cutoff and m_cur > 0:
        # Vertex dim is jit-static: pad to pow2 so executables are keyed
        # by (pow2 n, pow2 E) buckets and reused across levels/graphs
        # instead of one compile per exact supervertex count. Padding
        # vertices are isolated → they stay roots; their ranks trail the
        # real ones (padding ids sit above every real id, and the rank
        # prefix-sum only counts roots at smaller ids), so real
        # supervertex ids remain contiguous in [0, R).
        n_pad = next_pow2(n_cur, floor=8)
        with obs.span("coarsen.level", level=len(stats), n=n_cur, m=m_cur):
            res = contract_level_und(
                lo, hi, w, eid, valid,
                n=n_pad, eid_capacity=eid_cap,
                rounds=cfg.rounds_per_level,
                pack=use_pack, segmin=segmin_fn,
            )
            n_next = int(res.n_next) - (n_pad - n_cur)  # drop padding roots
            if n_next == n_cur:  # every component already complete
                break
            n_f = int(res.n_msf_edges)
            eids_acc.append(np.asarray(res.msf_eids[:n_f]))
            w_acc.append(np.asarray(res.msf_w[:n_f]))
            if dedupe == "host":
                l2, h2, w2_, e2_ = filter_level_host(
                    lo, hi, w, eid, valid, res.new_ids, n_cur
                )
                m_next = len(l2)
                pad = _next_pow2(m_next)
                lo = np.zeros(pad, np.int32)
                hi = np.zeros(pad, np.int32)
                w = np.full(pad, np.inf, np.float32)
                eid = np.full(pad, _IMAX, np.int32)
                lo[:m_next], hi[:m_next] = l2, h2
                w[:m_next], eid[:m_next] = w2_, e2_
            else:
                fr = filter_level(
                    lo, hi, w, eid, valid, res.new_ids,
                    n=n_pad, pack=use_pack, segmin=segmin_dedupe_fn,
                )
                m_next = int(fr.m_new)
                pad = _next_pow2(m_next)
                lo = np.asarray(fr.lo[:pad])
                hi = np.asarray(fr.hi[:pad])
                w = np.asarray(fr.w[:pad])
                eid = np.asarray(fr.eid[:pad])
            label_map = np.asarray(res.new_ids)[label_map]
            stats.append(LevelStats(n=n_cur, m=m_cur, n_next=n_next,
                                    m_next=m_next, hooked=n_f))
            valid = np.arange(pad) < m_next  # filter is front-packed
            n_cur, m_cur = n_next, m_next

    # Residual n is pow2-padded too (padding vertices are isolated
    # singleton components, never referenced by label_map) — the flat
    # solve and the 2D partition then also reuse executables across
    # similar graphs instead of compiling per exact supervertex count.
    residual = graph_from_canonical(
        lo, hi, w, eid, valid, next_pow2(n_cur, floor=8)
    )
    return CoarsenPrelude(
        msf_eids=_concat(eids_acc, np.int32),
        msf_w=_concat(w_acc, np.float32),
        label_map=label_map,
        residual=residual,
        stats=CoarsenStats(levels=tuple(stats), residual_n=n_cur,
                           residual_m=m_cur),
        level_iters=len(stats) * cfg.rounds_per_level,
    )


def _concat(parts: list, dtype) -> np.ndarray:
    return np.concatenate(parts) if parts else np.zeros(0, dtype)


def forest_result(parent, eids, w, n0: int, iterations: int,
                  shortcut_jumps: int = 0) -> MSFResult:
    """A host ``MSFResult`` of ``n0`` vertices from the forest's edge ids
    and their weights (in the same order), padded as the flat driver pads."""
    msf_eids = np.full(n0, _IMAX, np.int32)
    msf_eids[: len(eids)] = eids
    msf_w = np.zeros(n0, np.float32)
    msf_w[: len(w)] = w
    return MSFResult(
        parent=parent,
        msf_eids=msf_eids,
        msf_w=msf_w,
        n_msf_edges=np.int32(len(eids)),
        iterations=np.int32(iterations),
        shortcut_jumps=np.int32(shortcut_jumps),
    )


def _finalize(
    prelude: CoarsenPrelude,
    residual_parent: np.ndarray,
    residual_eids: np.ndarray,
    residual_w: np.ndarray,
    residual_iters: int,
    residual_jumps: int,
    n0: int,
) -> MSFResult:
    """Merge level picks with the residual solve into one MSFResult in
    original-graph vertex/edge ids. ``shortcut_jumps`` is the residual
    flat solve's: the levels do not count theirs."""
    from repro.coarsen.relabel import canonical_minvertex_labels

    comp = residual_parent[prelude.label_map]  # [n0] residual-space labels
    return forest_result(
        canonical_minvertex_labels(comp, len(residual_parent)),
        np.concatenate([prelude.msf_eids, residual_eids]),
        np.concatenate([prelude.msf_w, residual_w]),
        n0,
        prelude.level_iters + residual_iters,
        residual_jumps,
    )


class CoarsenMSF:
    """Reusable engine front-end: holds a config, records per-run stats.

    ``msf_kw`` (variant/shortcut/capacity/pack/segmin/...) is forwarded
    to the residual ``core.msf`` call; ``config`` controls the levels.
    The result is expressed in input-graph ids: ``msf_eids`` are global
    eids, and ``parent`` labels components by their minimum original
    vertex.
    """

    def __init__(self, config: CoarsenConfig | None = None, **msf_kw):
        self.config = config or CoarsenConfig()
        # segmin only parameterizes the pack=True inner loop of core.msf;
        # for a float residual it would be ignored there, so keep it for
        # the levels (via config) but only forward alongside pack=True.
        # (The residual call goes through ``core.msf.flat_msf``, whose
        # backend resolution — including the "sorted"-degrades rule for
        # unsorted hook segments — lives in ``repro.solve.spec``.)
        if not msf_kw.get("pack"):
            msf_kw.pop("segmin", None)
        self.msf_kw = msf_kw
        self.last_stats: CoarsenStats | None = None

    def __call__(self, graph: Graph) -> MSFResult:
        prelude = run_levels(graph, self.config)
        with obs.span("coarsen.residual", n=prelude.residual.n,
                      m=prelude.stats.residual_m):
            r = _flat_msf(prelude.residual, **self.msf_kw)
        self.last_stats = prelude.stats
        with obs.span("solve.fetch"):
            n_f = int(r.n_msf_edges)
            parts = (
                np.asarray(r.parent),
                np.asarray(r.msf_eids)[:n_f],
                np.asarray(r.msf_w)[:n_f],
                int(r.iterations),
                int(r.shortcut_jumps),
            )
        with obs.span("coarsen.finalize"):
            return _finalize(prelude, *parts, graph.n)


def coarsen_msf(
    graph: Graph,
    *,
    config: CoarsenConfig | None = None,
    segmin: str | None = None,
    fused: bool | None = None,
    **msf_kw,
) -> MSFResult:
    """One-shot form of :class:`CoarsenMSF`; ``segmin`` (when given)
    applies to the level kernels — overriding ``config.segmin`` — and,
    with ``pack=True``, the residual; ``fused`` (when given) overrides
    ``config.fused``. Callers that need the per-level
    :class:`CoarsenStats` should hold a :class:`CoarsenMSF` instance
    (its ``last_stats`` is per-instance, not shared global state)."""
    cfg = config or CoarsenConfig()
    if segmin is not None:
        cfg = dataclasses.replace(cfg, segmin=segmin)
    if fused is not None:
        cfg = dataclasses.replace(cfg, fused=fused)
    return CoarsenMSF(cfg, segmin=segmin, **msf_kw)(graph)


# ---------------------------------------------------------------------------
# Partition2D-aware pre-contraction for the distributed engine
# ---------------------------------------------------------------------------

def precontract_partition(
    graph: Graph,
    rows: int,
    cols: int,
    *,
    config: CoarsenConfig | None = None,
) -> Tuple[Partition2D, CoarsenPrelude]:
    """Coarsen on the host first, then 2D-partition only the residual.

    The paper's Fig-2 schedule pays all_gathers proportional to n and
    local work proportional to the device's edge block — both shrink with
    the contracted residual, so the distributed solve runs on a graph
    whose n/m the levels already cut geometrically. Use
    :func:`merge_distributed` to fold the ``msf_distributed`` result back
    into original-graph ids.

    This is the **host-prelude** pipeline (every level round-trips edge
    arrays off-device); the production distributed path is
    ``msf_distributed(part_of_original_graph, mesh, coarsen=config)``,
    which runs the same levels inside ``shard_map`` with zero per-level
    host re-partitions (``repro.coarsen.dist``, DESIGN.md §8) and keeps
    this pipeline as its measured baseline.
    """
    prelude = run_levels(graph, config)
    part = partition_edges_2d(prelude.residual, rows, cols)
    return part, prelude


def merge_distributed(prelude: CoarsenPrelude, dist_result) -> MSFResult:
    """Combine a ``DistMSFResult`` over the residual with the prelude.

    ``iterations`` adds the rounds the levels actually ran
    (``prelude.level_iters``) to the distributed solve's count — it used
    to hard-code one round per level and under-report whenever
    ``rounds_per_level > 1``.
    """
    n_f = int(dist_result.n_msf_edges)
    return _finalize(
        prelude,
        np.asarray(dist_result.parent),
        np.asarray(dist_result.msf_eids)[:n_f],
        np.asarray(dist_result.msf_w)[:n_f],
        int(dist_result.iterations),
        0,  # the distributed driver counts no pointer jumps
        len(prelude.label_map),
    )

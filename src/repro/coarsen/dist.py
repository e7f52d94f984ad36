"""Distributed fused coarsening levels under ``shard_map`` (DESIGN.md §8).

The PR-2 distributed hook (`precontract_partition`) coarsens on the host
and only then 2D-partitions the residual: every level round-trips the
edge arrays off-device — the exact cost `fused_level` removed for the
single-device path. This module runs the same contract → relabel →
filter level **inside the mesh**, on the `Partition2D` [R, C, Emax] edge
blocks, so nothing but control scalars and the hooked eids ever leaves
the devices:

- edges are re-keyed once from block-local offsets to **global** vertex
  ids (`graphs.partition.block_global_ids`) — after the first relabel the
  (row_of, col_of) block alignment is gone, so the Fig-2 row/col-block
  gathers stop applying and each round instead reduces local per-root
  partials into a dense [n] accumulator combined across the mesh by the
  existing MINWEIGHT semiring (`make_und_reduce` with an
  all-reduce(min) ``combine`` — DESIGN.md §2's masked passes);
- the supervertex rank vector (`rank_relabel` of the replicated parent)
  is materialized once per level and each device re-keys its block
  locally, then sort-dedupes it in place (`filter_level_impl` on the
  local [Emax] block — the sorted-segment Pallas segmin on TPU).
  Cross-device parallels survive local dedupe; that is exact (they are
  non-minimal on a cycle, and the hook combine never selects them while
  the lighter copy lives), and the per-block m still shrinks
  geometrically, so between-level capacity cuts are device-side slices
  of the blocks' (unsharded) edge dim — zero host re-partitions;
- after the levels stop (cutoff / no progress / max_levels), the
  **residual solve stays in-mesh too**: hook+shortcut rounds over the
  same globally-keyed blocks in one `lax.while_loop` until no root
  hooks. The parent vector is replicated per level (n has shrunk
  geometrically by then), so shortcutting is local pointer-jumping —
  the CSP/OS machinery of `core.msf_dist` addresses the big-n regime
  this path contracts away.

``dedupe="host"`` keeps a per-level host fallback for CPU CI: contraction
still runs in-mesh, but the blocks hop to the host for the numpy
lexsort dedupe (`filter_level_host` per block) — L round-trips, counted
in ``DistCoarsenStats.host_roundtrips`` (0 for the in-mesh path).

Entry point: ``core.msf_dist.msf_distributed(part, mesh, coarsen=cfg)``
returns a :class:`DistCoarsenMSF` driver with the same call signature as
the flat distributed driver; results are an ``MSFResult`` in
original-graph vertex/edge ids (directly comparable to
``msf(graph, coarsen=cfg, fused=True)``).
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.compat import shard_map
from repro.coarsen.contract import contract_rounds, make_und_reduce
from repro.coarsen.config import CoarsenConfig
from repro.coarsen.engine import LevelStats, _concat, _next_pow2, forest_result
from repro.coarsen.filter import filter_level_host, filter_level_impl
from repro.coarsen.relabel import canonical_minvertex_labels
from repro.core.msf import MSFResult, hook_and_tiebreak, record_edges
from repro.core.semiring import IMAX
from repro.core.shortcut import complete_shortcut
from repro.graphs.partition import Partition2D, block_global_ids
from repro.solve.spec import auto_pack, resolve_dedupe, resolve_level_segmins

_IMAX_NP = np.int32(np.iinfo(np.int32).max)


def _account_allreduce(rounds: int, n_pad: int, pack: bool) -> None:
    """Analytic all-reduce volume of ``rounds`` cross-device contract
    rounds over a dense [n_pad] accumulator: the pack path combines two
    dense passes per round (packed minkey + payload), the float path
    three (minw, mineid, payload) — exactly the ``combine`` call sites of
    :func:`make_und_reduce`. Host-side schedule accounting, not a device
    measurement: the counters mirror what the compiled program does."""
    if not obs.metrics_active():
        return
    passes = (2 if pack else 3) * rounds
    obs.counter("dist.allreduce.passes").inc(passes)
    obs.counter("dist.allreduce.elements").inc(passes * n_pad)


class DistCoarsenStats(NamedTuple):
    """Per-run surface of the distributed fused level pipeline.

    ``m`` counts are *block entries*: directed copies at level 0 (each
    undirected edge enters twice, wherever the 2D partition put its two
    directions), per-block-unique canonical pairs afterwards — a pair
    duplicated across devices counts once per device (local dedupe only).
    """

    levels: Tuple[LevelStats, ...]
    residual_n: int
    residual_m: int  # block entries handed to the in-mesh residual solve
    residual_iters: int  # hook+shortcut rounds the residual solve ran
    host_roundtrips: int  # per-level block round-trips (0 = in-mesh dedupe)


def _mesh_min(x, row_axis, col_axis):
    """All-reduce(min) over the whole mesh — one masked MINWEIGHT pass."""
    return lax.pmin(lax.pmin(x, col_axis), row_axis)


def _flat(a):
    return a.reshape(a.shape[-1:])


@lru_cache(maxsize=None)
def _level_driver(
    mesh, row_axis, col_axis, n, eid_capacity, rounds, pack,
    segmin_hook, segmin_dedupe, with_filter,
):
    """Jitted shard_map'ed level: K cross-device contract rounds +
    rank/relabel (replicated) + local per-block re-key/sort-dedupe.

    Cached per static signature so repeat levels of the same (n, capacity)
    shape reuse one executable, exactly like the single-device
    ``fused_level`` (jax.jit handles the per-edge-capacity retraces).
    """

    def fn(lo, hi, w, eid, valid, label_map):
        shp = lo.shape
        lo1, hi1, w1 = _flat(lo), _flat(hi), _flat(w)
        eid1, valid1 = _flat(eid), _flat(valid)
        reduce_fn = make_und_reduce(
            lo1, hi1, w1, eid1, valid1,
            n=n, eid_capacity=eid_capacity, pack=pack, segmin=segmin_hook,
            combine=partial(_mesh_min, row_axis=row_axis, col_axis=col_axis),
        )
        res = contract_rounds(reduce_fn, n, rounds)
        if with_filter:
            fr = filter_level_impl(
                lo1, hi1, w1, eid1, valid1, res.new_ids,
                n=n, pack=pack, segmin=segmin_dedupe,
            )
            m_local = fr.m_new
            out = (fr.lo, fr.hi, fr.w, fr.eid, fr.valid)
        else:  # dedupe="host": blocks pass through untouched
            m_local = jnp.sum(valid1.astype(jnp.int32))
            out = (lo1, hi1, w1, eid1, valid1)
        m_max = lax.pmax(lax.pmax(m_local, col_axis), row_axis)
        m_total = lax.psum(lax.psum(m_local, col_axis), row_axis)
        return (
            tuple(a.reshape(shp) for a in out)
            + (res.new_ids[label_map], res.new_ids, res.n_next, res.msf_w,
               res.msf_eids, res.n_msf_edges, m_max, m_total)
        )

    specs_e = P(row_axis, col_axis, None)
    mapped = shard_map(
        fn,
        mesh=mesh,
        in_specs=(specs_e,) * 5 + (P(),),
        out_specs=(specs_e,) * 5 + (P(),) * 8,
        check_vma=False,
    )
    return jax.jit(mapped)


@lru_cache(maxsize=None)
def _residual_driver(
    mesh, row_axis, col_axis, n, eid_capacity, pack, segmin_hook, limit,
):
    """In-mesh residual solve: hook+shortcut rounds over the globally-keyed
    blocks until no root hooks (or ``limit``), one ``lax.while_loop``."""

    def fn(lo, hi, w, eid, valid):
        lo1, hi1, w1 = _flat(lo), _flat(hi), _flat(w)
        eid1, valid1 = _flat(eid), _flat(valid)
        reduce_fn = make_und_reduce(
            lo1, hi1, w1, eid1, valid1,
            n=n, eid_capacity=eid_capacity, pack=pack, segmin=segmin_hook,
            combine=partial(_mesh_min, row_axis=row_axis, col_axis=col_axis),
        )

        def body(state):
            p, msf_eids, msf_w, n_f, it, _ = state
            r = reduce_fn(p)
            p_h, keep, _ = hook_and_tiebreak(p, r.w, r.eid, r.payload[0])
            msf_eids, msf_w, n_f = record_edges(msf_eids, msf_w, n_f, keep,
                                                r.eid, r.w)
            p_next = complete_shortcut(p_h)
            done = ~jnp.any(keep)
            return p_next, msf_eids, msf_w, n_f, it + 1, done

        def cond(state):
            return jnp.logical_and(~state[5], state[4] < limit)

        init = (
            jnp.arange(n, dtype=jnp.int32),
            jnp.full((n,), IMAX, jnp.int32),
            jnp.zeros((n,), jnp.float32),
            jnp.int32(0),
            jnp.int32(0),
            jnp.bool_(False),
        )
        p, msf_eids, msf_w, n_f, it, _ = lax.while_loop(cond, body, init)
        return p, msf_w, msf_eids, n_f, it

    specs_e = P(row_axis, col_axis, None)
    mapped = shard_map(
        fn,
        mesh=mesh,
        in_specs=(specs_e,) * 5,
        out_specs=(P(),) * 5,
        check_vma=False,
    )
    return jax.jit(mapped)


def _host_filter_blocks(lo, hi, w, eid, valid, new_ids, n_pad):
    """dedupe="host" level tail: numpy lexsort dedupe per block, repacked
    to a shared pow2 capacity (the explicit CPU-CI round-trip path)."""
    rows, cols = lo.shape[0], lo.shape[1]
    parts = [
        filter_level_host(
            lo[r, s], hi[r, s], w[r, s], eid[r, s], valid[r, s],
            new_ids, n_pad,
        )
        for r in range(rows)
        for s in range(cols)
    ]
    m_max = max(len(p[0]) for p in parts)
    cap = _next_pow2(m_max)
    lo2 = np.zeros((rows, cols, cap), np.int32)
    hi2 = np.zeros((rows, cols, cap), np.int32)
    w2 = np.full((rows, cols, cap), np.inf, np.float32)
    eid2 = np.full((rows, cols, cap), _IMAX_NP, np.int32)
    valid2 = np.zeros((rows, cols, cap), bool)
    m_total = 0
    for k, (l_, h_, w_, e_) in enumerate(parts):
        r, s, m = k // cols, k % cols, len(l_)
        lo2[r, s, :m], hi2[r, s, :m] = l_, h_
        w2[r, s, :m], eid2[r, s, :m] = w_, e_
        valid2[r, s, :m] = True
        m_total += m
    return lo2, hi2, w2, eid2, valid2, m_total


class DistCoarsenMSF:
    """Distributed fused coarsen-and-solve driver over a 2D partition.

    Built by ``msf_distributed(part, mesh, coarsen=config)``; call with
    the partition's block arrays (same signature as the flat distributed
    driver). Returns an :class:`repro.core.msf.MSFResult` in
    original-graph ids; per-run :class:`DistCoarsenStats` land on
    ``last_stats``.

    Config knobs follow the single-device engine: ``dedupe`` "auto"
    resolves to the in-mesh device pipeline on TPU and the per-level host
    fallback elsewhere ("device"/"host" force either); ``pack`` None
    auto-detects the pack32 regime; ``segmin`` picks the packed
    segment-min backends (the dedupe site takes the sorted-segment Pallas
    kernel). ``max_iters`` bounds the residual solve's rounds.
    """

    def __init__(
        self,
        part: Partition2D,
        mesh,
        config: CoarsenConfig | None = None,
        *,
        row_axis: str = "data",
        col_axis: str = "model",
        max_iters: int | None = None,
    ):
        self.part = part
        self.mesh = mesh
        self.config = config or CoarsenConfig()
        self.row_axis = row_axis
        self.col_axis = col_axis
        self.max_iters = max_iters
        self.last_stats: DistCoarsenStats | None = None
        self._prep = None  # last (input refs) → re-keyed blocks + statics

    def _prepare(self, src_row, dst_col, w, eid, valid):
        """Re-key blocks to global ids and derive eid_cap / pack — all
        deterministic functions of the inputs, memoized on the exact input
        arrays (the common case: the driver is called repeatedly with the
        partition's own arrays, e.g. benchmark loops) so repeat calls skip
        the O(E) host scans and the re-keyed upload."""
        refs = (src_row, dst_col, w, eid, valid)
        if self._prep is not None and all(
            a is b for a, b in zip(self._prep[0], refs)
        ):
            return self._prep[1]
        src_g, dst_g = block_global_ids(
            np.asarray(src_row), np.asarray(dst_col), self.part.shard_size
        )
        w_np = np.asarray(w, np.float32)
        eid_np = np.asarray(eid, np.int32)
        valid_np = np.asarray(valid, bool)
        eids_live = eid_np[valid_np]
        eid_cap = (
            _next_pow2(int(eids_live.max()) + 1) if eids_live.size else 8
        )
        use_pack = (
            auto_pack(w_np, eid_np, valid_np, eid_cap)
            if self.config.pack is None
            else self.config.pack
        )
        prep = (src_g, dst_g, w_np, eid_np, valid_np, eid_cap, use_pack)
        self._prep = (refs, prep)
        return prep

    def __call__(self, src_row, dst_col, w, eid, valid) -> MSFResult:
        part, cfg = self.part, self.config
        n0 = part.n
        src_g, dst_g, w_np, eid_np, valid_np, eid_cap, use_pack = (
            self._prepare(src_row, dst_col, w, eid, valid)
        )
        segmin_hook, segmin_dedupe = resolve_level_segmins(cfg.segmin, use_pack)
        in_mesh = resolve_dedupe(cfg.dedupe) != "host"

        lo, hi, w_b, eid_b, valid_b = src_g, dst_g, w_np, eid_np, valid_np
        if in_mesh:
            lo, hi = jnp.asarray(lo), jnp.asarray(hi)
            w_b, eid_b = jnp.asarray(w_b), jnp.asarray(eid_b)
            valid_b = jnp.asarray(valid_b)
            label_map = jnp.arange(n0, dtype=jnp.int32)
        else:
            label_map = np.arange(n0, dtype=np.int32)

        mesh_key = (self.mesh, self.row_axis, self.col_axis)
        n_cur = n0
        m_cur = int(valid_np.sum())
        eids_acc: list[np.ndarray] = []
        w_acc: list[np.ndarray] = []
        stats: list[LevelStats] = []
        roundtrips = 0

        while len(stats) < cfg.max_levels and n_cur > cfg.cutoff and m_cur > 0:
            n_pad = _next_pow2(n_cur)
            drv = _level_driver(
                *mesh_key, n_pad, eid_cap, cfg.rounds_per_level, use_pack,
                segmin_hook, segmin_dedupe, in_mesh,
            )
            # The span ends after the level's scalars and hooked eids are
            # on the host: the first read waits for the level's program.
            with obs.span("dist.level", level=len(stats), n=n_cur, m=m_cur):
                out = drv(lo, hi, w_b, eid_b, valid_b, label_map)
                _account_allreduce(cfg.rounds_per_level, n_pad, use_pack)
                n_next = int(out[7]) - (n_pad - n_cur)  # drop padding roots
                if n_next == n_cur:  # every component already complete
                    break
                n_f = int(out[10])
                eids_acc.append(np.asarray(out[9][:n_f]))
                w_acc.append(np.asarray(out[8][:n_f]))
            if in_mesh:
                m_max, m_total = int(out[11]), int(out[12])
                cap = _next_pow2(m_max)
                lo, hi = out[0][..., :cap], out[1][..., :cap]
                w_b, eid_b = out[2][..., :cap], out[3][..., :cap]
                valid_b = out[4][..., :cap]
                label_map = out[5]
            else:
                new_ids = np.asarray(out[6])
                lo, hi, w_b, eid_b, valid_b, m_total = _host_filter_blocks(
                    np.asarray(lo), np.asarray(hi), np.asarray(w_b),
                    np.asarray(eid_b), np.asarray(valid_b), new_ids, n_pad,
                )
                label_map = new_ids[label_map]
                roundtrips += 1
            stats.append(LevelStats(n=n_cur, m=m_cur, n_next=n_next,
                                    m_next=m_total, hooked=n_f))
            n_cur, m_cur = n_next, m_total

        n_res_pad = _next_pow2(n_cur)
        limit = int(
            self.max_iters
            if self.max_iters is not None
            else 2 * int(n_res_pad).bit_length() + 8
        )
        rdrv = _residual_driver(
            *mesh_key, n_res_pad, eid_cap, use_pack, segmin_hook, limit
        )
        with obs.span("dist.residual", n=n_cur, m=m_cur):
            p_res, r_w, r_eids, r_nf, r_it = rdrv(
                lo, hi, w_b, eid_b, valid_b
            )
            # Residual rounds run the same per-round combine schedule.
            _account_allreduce(int(r_it), n_res_pad, use_pack)

        r_nf = int(r_nf)
        eids_acc.append(np.asarray(r_eids)[:r_nf])
        w_acc.append(np.asarray(r_w)[:r_nf])
        comp = np.asarray(p_res)[np.asarray(label_map)]
        self.last_stats = DistCoarsenStats(
            levels=tuple(stats),
            residual_n=n_cur,
            residual_m=m_cur,
            residual_iters=int(r_it),
            host_roundtrips=roundtrips,
        )
        return forest_result(
            canonical_minvertex_labels(comp, n_res_pad),
            _concat(eids_acc, np.int32),
            _concat(w_acc, np.float32),
            n0,
            len(stats) * cfg.rounds_per_level + int(r_it),
        )

"""Algebraic Awerbuch-Shiloach minimum spanning forest (paper Algorithm 1).

Two algorithm variants:

- ``variant="complete"`` (production default, paper §IV-B): complete
  shortcutting keeps every tree a star at the top of each iteration, so the
  starcheck disappears and hooking can fuse the line-10 projection into the
  multilinear kernel (segment ids = p[src] are root ids). Unpacked, its
  rounds hook through one rank per slot in the (w, eid) order, ranked
  once per solve (DESIGN.md §2): one segment-min per round.
- ``variant="paper"`` (faithful Algorithm 1): starcheck, per-vertex
  multilinear kernel (line 9), separate projection to roots (line 10), one
  shortcut round per iteration (line 15).

Plus the *pairwise* formulation (paper §IV-A "Pairwise") used as the Fig-8
baseline: first materialize m_ij = (a_ij, p_j) (the nnz extra writes), then
reduce f(p_i, m_ij) — algebraically identical, strictly more data movement.

Termination uses FastSV's grandparent-convergence condition (paper §V): stop
when hooking makes no progress, checked on the parent vector after complete
shortcutting.

Outputs: the MSF edge set (global eids) with each edge's weight beside it,
the parent vector (connected-component labels), the iteration count and
the shortcut's pointer jumps. The total weight is summed on the host in
float64 from the recorded edge weights (:func:`forest_weight`), so it is
exact for integer weights whatever the total (DESIGN.md §2).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import shortcut as sc
from repro.core.multilinear import (
    min_outgoing_coo,
    min_outgoing_coo_packed,
    min_outgoing_ranked,
    project_to_roots,
    rank_slots,
)
from repro.core.semiring import INF, IMAX
from repro.graphs.structures import Graph


def forest_weight(w, count=None) -> float:
    """Total weight of a forest: the float64 sum, on the host, of its
    edges' float32 weights ``w[:count]``. Exact for integer weights while
    the total is below 2^53; for float weights the float64 sum of the
    float32 values. Every engine's forest weight comes from here."""
    w = np.asarray(w)
    if count is not None:
        w = w[: int(count)]
    return float(w.sum(dtype=np.float64))


# ``weight`` of every record that carries ``msf_w`` and ``n_msf_edges``.
FOREST_WEIGHT = property(
    lambda r: forest_weight(r.msf_w, r.n_msf_edges),
    doc="Total forest weight: :func:`forest_weight` of the recorded edges.",
)


class MSFResult(NamedTuple):
    parent: jax.Array  # int32 [n]: component representative per vertex
    msf_eids: jax.Array  # int32 [n]: global eids of MSF edges, IMAX padded
    msf_w: jax.Array  # float32 [n]: each MSF edge's weight beside its eid, 0 padded
    n_msf_edges: jax.Array  # int32 scalar
    iterations: jax.Array  # int32 scalar
    shortcut_jumps: jax.Array  # int32 scalar: p ← p[p] passes of the flat driver

    weight = FOREST_WEIGHT


def starcheck(p: jax.Array) -> jax.Array:
    """AS starcheck (paper §II-C): s_i = does vertex i belong to a star."""
    n = p.shape[0]
    i = jnp.arange(n, dtype=p.dtype)
    gp = p[p]
    s = jnp.ones(n, bool)
    nonstar = gp != p
    # Vertex i informs its grandparent the tree is not a star.
    tgt = jnp.where(nonstar, gp, n)  # out-of-bounds dropped
    s = s.at[tgt].set(False, mode="drop")
    s = s & ~nonstar
    # Remaining vertices query their parent.
    return s & s[p]


def hook_and_tiebreak(p, r_w, r_eid, r_parent):
    """Lines 11-13: hook star roots with their min outgoing edge, then break
    the 2-cycles hooking introduces (larger root keeps the hook).

    Public because the coarsening engine (``repro.coarsen.contract``) runs
    the same hook rounds outside the full MSF driver loop."""
    n = p.shape[0]
    i = jnp.arange(n, dtype=p.dtype)
    hooked = r_w < INF  # only roots receive a valid r entry
    p_h = jnp.where(hooked, r_parent, p)
    # Tie break: i was a (hooked) root, i < p_i, and p_{p_i} == i.
    t = hooked & (i < p_h) & (p_h[p_h] == i)
    p_new = jnp.where(t, i, p_h)
    keep = hooked & ~t  # roots whose hook survives contribute their edge
    return p_new, keep, t


def record_edges(msf_eids, msf_w, n_f, keep, r_eid, r_w):
    """Append the surviving hook edges' eids, and their weights at the
    same positions, to the MSF buffers."""
    n = keep.shape[0]
    pos = n_f + jnp.cumsum(keep.astype(jnp.int32)) - 1
    tgt = jnp.where(keep, pos, n)  # drop non-winners
    msf_eids = msf_eids.at[tgt].set(r_eid, mode="drop")
    msf_w = msf_w.at[tgt].set(r_w, mode="drop")
    return msf_eids, msf_w, n_f + jnp.sum(keep.astype(jnp.int32))


def _ranked(variant: str, pack: bool) -> bool:
    """Whether the driver hooks through the slot ranks: the unpacked
    complete variant (the paper and pairwise baselines keep the 3-pass
    reduction, the packed path its single packed key)."""
    return variant == "complete" and not pack


def _make_msf_body(graph: Graph, variant, shortcut_fn, pack, segmin, ranks=None):
    """One hook+shortcut round as ``body(state) -> state`` over the
    7-tuple ``(p, msf_eids, msf_w, n_f, it, jumps, done)``: the body of
    :func:`_msf_jit`'s while_loop. ``ranks`` are the graph's
    :func:`~repro.core.multilinear.rank_slots` tables, which the unpacked
    complete variant hooks through."""
    n = graph.n
    src, dst, w, eid, valid = graph.src, graph.dst, graph.w, graph.eid, graph.valid

    def body_complete(state):
        p, msf_eids, msf_w, n_f, it, jumps, _ = state
        p_prev = p
        with jax.named_scope("hook"):
            r = min_outgoing(p)
            p_h, keep, _ = hook_and_tiebreak(p, r.w, r.eid, r.payload[0])
            msf_eids, msf_w, n_f = record_edges(msf_eids, msf_w, n_f, keep,
                                                r.eid, r.w)
        with jax.named_scope("shortcut"):
            p_next, k = shortcut_fn(p_h, p_prev)
        done = jnp.all(p_next == p_prev)
        return p_next, msf_eids, msf_w, n_f, it + 1, jumps + k, done

    def min_outgoing(p):
        if variant == "pairwise":
            # Paper §IV-A pairwise baseline: materialize m = (a_ij, p_j)
            # into an nnz-sized buffer (the extra writes), then reduce with
            # f(p_i, m_ij). Algebraically identical to the fused kernel.
            # ``optimization_barrier`` forces the materialization XLA would
            # otherwise fuse away — CTF's pairwise path writes the updated
            # adjacency tensor to memory, which is exactly the cost the
            # paper's all-at-once kernel removes.
            m_w, m_pd, m_eid = jax.lax.optimization_barrier(
                (
                    jnp.where(valid, w, INF),  # materialized weight field
                    jnp.where(valid, p[dst], IMAX),  # materialized parents
                    jnp.where(valid, eid, IMAX),
                )
            )
            ps = p[src]
            outgoing = (ps != m_pd) & valid
            from repro.core.semiring import segment_argmin

            return segment_argmin(m_w, m_eid, (m_pd,), ps, n, valid=outgoing)
        if pack:
            return min_outgoing_coo_packed(
                p, src, dst, w, eid, valid, n, segmin=segmin
            )
        return min_outgoing_ranked(p, src, dst, w, eid, valid, n, ranks)

    def body_paper(state):
        p, msf_eids, msf_w, n_f, it, jumps, _ = state
        p_prev = p
        s = starcheck(p)
        q = min_outgoing_coo(p, src, dst, w, eid, valid, n, segment="vertex", star=s)
        r = project_to_roots(q, p, n)
        p_h, keep, _ = hook_and_tiebreak(p, r.w, r.eid, r.payload[0])
        msf_eids, msf_w, n_f = record_edges(msf_eids, msf_w, n_f, keep, r.eid, r.w)
        s2 = starcheck(p_h)
        p_next = sc.shortcut_once(p_h, s2)  # one pass of p ← p[p]
        done = jnp.all(p_next == p_prev)
        return p_next, msf_eids, msf_w, n_f, it + 1, jumps + 1, done

    return body_paper if variant == "paper" else body_complete


def _msf_init(graph: Graph, parent0):
    if parent0 is None:
        p0, k0 = jnp.arange(graph.n, dtype=jnp.int32), jnp.int32(0)
    else:
        # Canonicalize: the hooking kernels rely on the every-tree-a-star
        # invariant at the top of each iteration.
        p0, k0 = sc.count_shortcut_subiters(parent0.astype(jnp.int32))
    return (
        p0,
        jnp.full((graph.n,), IMAX, jnp.int32),
        jnp.zeros((graph.n,), jnp.float32),
        jnp.int32(0),
        jnp.int32(0),
        k0,
        jnp.bool_(False),
    )


def _msf_limit(n: int, max_iters) -> int:
    return int(max_iters if max_iters is not None else 2 * int(n).bit_length() + 8)


@partial(
    jax.jit,
    static_argnames=(
        "variant",
        "shortcut",
        "capacity",
        "max_iters",
        "unroll_guard",
        "pack",
        "segmin",
    ),
)
def _msf_jit(
    graph: Graph,
    *,
    parent0: jax.Array | None = None,
    variant: str = "complete",
    shortcut: str = "complete",
    capacity: int = 1 << 16,
    max_iters: int | None = None,
    unroll_guard: bool = True,
    pack: bool = False,
    segmin=None,
) -> MSFResult:
    """Jitted MSF driver — see :func:`msf` for the public entry point."""
    limit = jnp.int32(_msf_limit(graph.n, max_iters))
    shortcut_fn = sc.make_shortcut_fn(shortcut, capacity) if variant != "paper" else None
    # Ranked once per solve, before the loop: every solve pays for its own.
    ranks = rank_slots(graph.w, graph.eid, graph.valid) if _ranked(variant, pack) else None
    body = _make_msf_body(graph, variant, shortcut_fn, pack, segmin, ranks)

    def cond(state):
        it, done = state[4], state[6]
        guard = it < limit if unroll_guard else True
        return jnp.logical_and(~done, guard)

    init = _msf_init(graph, parent0)
    p, msf_eids, msf_w, n_f, it, jumps, _ = jax.lax.while_loop(cond, body, init)
    # canonical labels (complete variant: no-op, no jump)
    p, k = sc.count_shortcut_subiters(p)
    return MSFResult(parent=p, msf_eids=msf_eids, msf_w=msf_w, n_msf_edges=n_f,
                     iterations=it, shortcut_jumps=jumps + k)


def run_flat(graph: Graph, **kw) -> MSFResult:
    """The jitted flat driver, for callers holding a *resolved* segmin
    callable (the ``repro.solve`` flat engine, :func:`flat_msf`, the
    stream union solve), under an ``msf.flat`` span.

    Every caller's first host read of the result is its edge count; it is
    made here, inside the span, so the span ends when the solve has run
    on the device (the value stays with the array, nothing is copied
    twice). The pointer-jump count is read beside it, into the counter
    ``msf.shortcut.jumps``."""
    from repro import obs

    if _ranked(kw.get("variant", "complete"), kw.get("pack", False)):
        obs.counter("msf.reduction.ranked").inc()
    with obs.span("msf.flat"):
        r = _msf_jit(graph, **kw)
        int(r.n_msf_edges)
        obs.counter("msf.shortcut.jumps").inc(int(r.shortcut_jumps))
    return r


def flat_msf(graph: Graph, *, pack: bool = False, segmin: str | None = None,
             **kw) -> MSFResult:
    """Internal flat AS solve — the non-deprecated twin of the old
    ``msf()`` kwarg path, used by the ``repro.solve`` engines and the
    coarsen stack's residual solve.

    ``segmin`` is the *string* backend request; resolution (including
    the "sorted"-degrades-to-"auto" rule for unsorted hook segments)
    lives in ``repro.solve.spec.resolve_flat_segmin``. No validation —
    public callers go through ``SolveSpec``, which validates once.
    """
    from repro.solve.spec import resolve_flat_segmin  # lazy: layer cycle

    return run_flat(graph, pack=pack, segmin=resolve_flat_segmin(segmin, pack), **kw)


def msf(
    graph: Graph,
    *,
    coarsen=None,
    segmin: str | None = None,
    fused: bool | None = None,
    **kw,
) -> MSFResult:
    """Deprecated: compute the MSF of ``graph`` (kwarg-dispatch form).

    .. deprecated::
        Use the declarative API instead::

            from repro.solve import SolveSpec, plan
            plan(graph, SolveSpec()).solve()                    # flat
            plan(graph, SolveSpec(mode="coarsen",               # levels
                                  coarsen=cfg, fused=True)).solve()

        This shim builds the equivalent ``SolveSpec``, routes through
        ``repro.solve.plan``, and returns the engine-native
        ``MSFResult`` — bit-identical to the historical behavior (the
        4-way property suite pins it). It will be removed once the
        deprecation window closes; see DESIGN.md §9.

    variant: "complete" | "paper" | "pairwise"
    shortcut (complete variant only): "complete" | "csp" | "os"
    parent0: optional warm-start parent vector — the re-entrant form for
      callers that maintain their own component labels (e.g. an incremental
      connectivity refresh). Hooking starts from these components instead
      of singletons, so the returned ``weight``/``msf_eids`` cover only the
      edges hooked *during this call*. Any forest labeling works — it is
      canonicalized to stars first.
    pack: use the pack32 single-reduction inner loop (integer weights in
      [0, 255], eids < 2^24 − 1 — the paper's evaluation regime).
    segmin: packed segment-min backend for ``pack=True`` — "jnp",
      "pallas", or "auto" / None.
    coarsen: None for the flat solver, or a
      ``repro.coarsen.CoarsenConfig`` (or ``True`` for defaults) to run
      Borůvka contract-and-filter levels first (DESIGN.md §7).
      Incompatible with ``parent0``.
    fused: with ``coarsen=``, one-jit device-resident levels
      (DESIGN.md §7.6); overrides ``CoarsenConfig.fused``.
    """
    import warnings

    warnings.warn(
        "msf(...) is deprecated; build a repro.solve.SolveSpec and call "
        "plan(graph, spec).solve() instead",
        DeprecationWarning,
        stacklevel=2,
    )
    from repro import solve  # lazy: core must not import the plan layer eagerly

    parent0 = kw.pop("parent0", None)
    use_coarsen = coarsen is not None and coarsen is not False
    if use_coarsen:
        if parent0 is not None:
            raise ValueError("coarsen= cannot be combined with parent0=")
        spec = solve.SolveSpec(
            mode="coarsen",
            coarsen=True if coarsen is True else coarsen,
            segmin=segmin,
            fused=fused,
            pack=kw.pop("pack", None),
            **kw,
        )
        return solve.plan(graph, spec).solve().raw
    spec = solve.SolveSpec(
        mode="flat",
        segmin=segmin,
        fused=True if fused else None,  # surfaces the old ValueError
        pack=kw.pop("pack", False),
        **kw,
    )
    return solve.plan(graph, spec).solve(parent0=parent0).raw


def msf_weight(graph: Graph, **kw) -> float:
    """Deprecated alongside :func:`msf` (it delegates to it)."""
    return float(msf(graph, **kw).weight)

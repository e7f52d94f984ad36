"""Distributed MSF engine — the paper's Fig-2 schedule on a JAX mesh.

Per outer iteration (all inside one ``shard_map``-ped ``while_loop``):

1. **Multilinear kernel** (paper §IV-A): gather the parent-vector row block
   (all_gather over the column axis) and column block (all_gather over the
   row axis) — the redistribute+broadcast stage; apply
   f(p_i, a_ij, p_j) all-at-once over the local 2D edge block; local
   segment-argmin into a dense accumulator; MINWEIGHT ⊕-combine across the
   mesh (masked all-reduce(min) passes).
2. **Hook + tie-break** entirely from the replicated reduction result: with
   the complete-shortcutting invariant every tree is a star, so a root's
   post-hook parent is known from r alone — zero extra communication.
3. **Shortcut**:
   - ``baseline``: one full all_gather of p per sub-iteration, pointer jump
     locally, repeat (the paper's unoptimized remote-read loop);
   - ``csp``: the changed map (hooked roots → new parents) is already
     device-local; compress it by pointer doubling (local reads only) and
     apply in one pass — Algorithm 2 with the gather folded into the
     kernel's ⊕-combine. This is the communication the paper's Fig 3/4
     measure: n words × sub-iterations vs none.
   - ``os``: csp when |changed| ≤ capacity else baseline.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.core.msf import FOREST_WEIGHT, record_edges
from repro.core.multilinear import min_outgoing_2d, min_outgoing_2d_packed
from repro.core.semiring import INF, IMAX
from repro.graphs.partition import Partition2D


class DistMSFResult(NamedTuple):
    parent: jax.Array  # [n_pad] sharded
    msf_eids: jax.Array  # [n_pad] replicated, IMAX padded
    msf_w: jax.Array  # [n_pad] replicated: each MSF edge's weight, 0 padded
    n_msf_edges: jax.Array
    iterations: jax.Array

    weight = FOREST_WEIGHT


def _csp_apply(keep, r_parent, p_local, n_pad, capacity):
    """Build the changed map from the replicated hook results, compress it
    locally (pointer doubling over at most ceil(log2 chain) rounds), apply
    to the local parent shard in one pass."""
    i = jnp.arange(n_pad, dtype=jnp.int32)
    key = jnp.where(keep, i, IMAX)
    ids = -lax.top_k(-key, capacity)[0]  # smallest `capacity` changed ids
    safe = jnp.clip(ids, 0, n_pad - 1)
    vals = jnp.where(ids == IMAX, IMAX, r_parent[safe])

    def lookup(x):
        j = jnp.clip(jnp.searchsorted(ids, x), 0, capacity - 1)
        hit = (ids[j] == x) & (x != IMAX)
        return jnp.where(hit, vals[j], x), hit

    def cond(v):
        _, hit = lookup(v)
        return jnp.any(hit)

    def body(v):
        nxt, _ = lookup(v)
        return nxt

    vals = lax.while_loop(cond, body, vals)
    out, _ = lookup(p_local)
    return out


def _flat_axes(row_axis, col_axis):
    return (
        tuple(row_axis) if isinstance(row_axis, tuple) else (row_axis,)
    ) + (col_axis,)


def _baseline_shortcut(p_local, row_axis, col_axis):
    """Per-sub-iteration full gather + jump (the paper's baseline)."""
    axes = _flat_axes(row_axis, col_axis)

    def body(state):
        p_loc, _ = state
        p_full = lax.all_gather(p_loc, axes, tiled=True)
        p_new = p_full[p_loc]
        moved = jnp.any(p_new != p_loc).astype(jnp.int32)
        cont = lax.pmax(moved, axes)
        return p_new, cont

    def cond(state):
        return state[1] > 0

    p_final, _ = lax.while_loop(cond, body, (p_local, jnp.int32(1)))
    return p_final


def msf_distributed(
    part: Partition2D,
    mesh,
    *,
    row_axis: str = "data",
    col_axis: str = "model",
    shortcut: str = "csp",
    capacity: int = 1 << 16,
    max_iters: int | None = None,
    pack: bool = False,
    coarsen=None,
):
    """Deprecated: build the distributed MSF driver (kwarg-dispatch form).

    .. deprecated::
        This entry point has a **dual return type** — a jitted block
        driver function without ``coarsen=``, a
        ``repro.coarsen.dist.DistCoarsenMSF`` instance with it — which is
        exactly the kind of kwarg-keyed dispatch ``repro.solve``
        replaces. Use::

            from repro.solve import SolveSpec, plan
            p = plan(part, SolveSpec(mode="dist"), mesh=mesh)       # flat
            p = plan(part, SolveSpec(mode="dist", coarsen=cfg),     # fused
                     mesh=mesh)                                     # levels
            report = p.solve()          # uniform SolveReport, either way

        Removal path: this shim now routes **both** branches through
        ``repro.solve.plan`` (so repeated builds share the plan cache)
        and returns the plan's engine-native driver for call-pattern
        compatibility; when the deprecation window closes the shim and
        its dual return type disappear, and ``plan(...).solve()`` —
        whose report is uniform across both branches — is the only
        surface. See DESIGN.md §9.

    Shapes: edges [R, C, Emax] sharded over (row_axis, col_axis); parent
    vector [n_pad] sharded over the flattened mesh. ``coarsen``: ``None``
    for the flat Fig-2 solve, or a ``CoarsenConfig`` (``True`` for
    defaults) to run contract-and-filter levels inside the mesh first
    (DESIGN.md §8); ``shortcut``/``capacity`` only apply to the flat
    solve, ``pack`` is governed by the config under ``coarsen=``.
    """
    import warnings

    warnings.warn(
        "msf_distributed(...) is deprecated; use repro.solve.plan(part, "
        "SolveSpec(mode='dist', ...), mesh=mesh) instead",
        DeprecationWarning,
        stacklevel=2,
    )
    from repro import solve  # lazy: core must not import the plan layer eagerly

    use_coarsen = coarsen is not None and coarsen is not False
    spec = solve.SolveSpec(
        mode="dist",
        coarsen=(True if coarsen is True else coarsen) if use_coarsen else None,
        shortcut=None if use_coarsen else shortcut,
        capacity=capacity,
        max_iters=max_iters,
        pack=None if use_coarsen else pack,  # coarsen: config governs pack
        row_axis=row_axis,
        col_axis=col_axis,
    )
    return solve.plan(part, spec, mesh=mesh).driver


def build_dist_driver(
    part: Partition2D,
    mesh,
    *,
    row_axis: str = "data",
    col_axis: str = "model",
    shortcut: str = "csp",
    capacity: int = 1 << 16,
    max_iters: int | None = None,
    pack: bool = False,
):
    """Internal: the flat Fig-2 distributed driver builder.

    Returns a jitted function (src_row, dst_col, w, eid, valid) →
    ``DistMSFResult``. Only reads the partition's *static* fields
    (``n_pad``, ``cols``, ``shard_size``), so one driver serves every
    same-shape partition — which is what the ``repro.solve`` plan cache
    keys on. Public callers go through ``plan(part, SolveSpec
    (mode="dist"), mesh=...)``; the in-mesh coarsening variant lives in
    ``repro.coarsen.dist.DistCoarsenMSF``.
    """
    n_pad = part.n_pad
    capacity = min(capacity, n_pad)
    limit = jnp.int32(
        max_iters if max_iters is not None else 2 * int(n_pad).bit_length() + 8
    )

    def step(src_row, dst_col, w, eid, valid, p_local, state):
        msf_eids, msf_w, n_f, it = state
        kernel = min_outgoing_2d_packed if pack else min_outgoing_2d
        r = kernel(
            p_local,
            src_row,
            dst_col,
            w,
            eid,
            valid,
            n_pad,
            row_axis=row_axis,
            col_axis=col_axis,
        )
        r_w, r_eid, r_parent = r.w, r.eid, r.payload[0]
        hooked = r_w < INF
        i = jnp.arange(n_pad, dtype=jnp.int32)
        # Post-hook parent of any *root* j is r_parent[j] if hooked else j —
        # derivable from the replicated reduction alone (stars invariant).
        tgt = jnp.clip(r_parent, 0, n_pad - 1)
        tgt_parent = jnp.where(hooked[tgt], r_parent[tgt], tgt)
        t = hooked & (i < r_parent) & (tgt_parent == i)
        keep = hooked & ~t
        # Record MSF edges (replicated bookkeeping).
        msf_eids, msf_w, n_f = record_edges(msf_eids, msf_w, n_f, keep, r_eid, r_w)

        # Apply hooks to the local shard, then shortcut.
        shard_ix = _shard_index(row_axis, col_axis, part.cols)
        base = shard_ix * part.shard_size
        loc = base + jnp.arange(part.shard_size, dtype=jnp.int32)
        keep_loc = keep[loc]
        p_hooked = jnp.where(keep_loc, r_parent[loc], p_local)

        if shortcut == "baseline":
            p_next = _baseline_shortcut(p_hooked, row_axis, col_axis)
        elif shortcut in ("csp", "os"):
            # CSP is only exact when the changed set fits the prefetch
            # buffer; on overflow fall back to the baseline remote-read loop
            # (this *is* the paper's OS policy — CSP differs only in that the
            # paper sizes the gather dynamically, which XLA cannot).
            n_changed = jnp.sum(keep.astype(jnp.int32))

            def do_csp(pl):
                return _csp_apply(keep, r_parent, pl, n_pad, capacity)

            def do_base(pl):
                return _baseline_shortcut(pl, row_axis, col_axis)

            p_next = lax.cond(n_changed <= capacity, do_csp, do_base, p_hooked)
        else:
            raise ValueError(f"unknown distributed shortcut {shortcut!r}")

        done = ~jnp.any(keep)
        return p_next, (msf_eids, msf_w, n_f, it + 1), done

    def run(src_row, dst_col, w, eid, valid, p0_local):
        src_row = src_row.reshape(src_row.shape[-1:])
        dst_col = dst_col.reshape(dst_col.shape[-1:])
        w = w.reshape(w.shape[-1:])
        eid = eid.reshape(eid.shape[-1:])
        valid = valid.reshape(valid.shape[-1:])

        init_state = (
            jnp.full((n_pad,), IMAX, jnp.int32),
            jnp.zeros((n_pad,), jnp.float32),
            jnp.int32(0),
            jnp.int32(0),
        )

        def body_fn(carry):
            p_loc, state, _ = carry
            p_next, state, done = step(src_row, dst_col, w, eid, valid, p_loc, state)
            return p_next, state, done

        def cond_fn(carry):
            _, state, done = carry
            return jnp.logical_and(~done, state[3] < limit)

        carry0 = (p0_local, init_state, jnp.bool_(False))
        p_loc, state, _ = lax.while_loop(cond_fn, body_fn, carry0)
        msf_eids, msf_w, n_f, it = state
        return p_loc, msf_eids, msf_w, n_f, it

    specs_edges = P(row_axis, col_axis, None)
    flat_axes = (
        tuple(row_axis) if isinstance(row_axis, tuple) else (row_axis,)
    ) + (col_axis,)
    mapped = shard_map(
        run,
        mesh=mesh,
        in_specs=(specs_edges,) * 5 + (P(flat_axes),),
        out_specs=(P(flat_axes), P(), P(), P(), P()),
        check_vma=False,
    )

    @jax.jit
    def driver(src_row, dst_col, w, eid, valid):
        p0 = jnp.arange(n_pad, dtype=jnp.int32)
        p, msf_eids, msf_w, n_f, it = mapped(src_row, dst_col, w, eid, valid, p0)
        return DistMSFResult(
            parent=p, msf_eids=msf_eids, msf_w=msf_w, n_msf_edges=n_f, iterations=it
        )

    return driver


def _axis_index_flat(axes):
    """axis_index generalized to a tuple of mesh axes (row-major)."""
    if isinstance(axes, str):
        return lax.axis_index(axes)
    idx = lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * lax.psum(1, a) + lax.axis_index(a)
    return idx


def _shard_index(row_axis, col_axis, cols: int):
    """Flat shard index r*C + s of the executing device."""
    r = _axis_index_flat(row_axis)
    s = _axis_index_flat(col_axis)
    return (r * cols + s).astype(jnp.int32)

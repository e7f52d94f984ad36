"""The paper's multilinear kernel (§III-A, §IV-A).

Computes ``w_i ← ⊕_j f(x_i, a_ij, y_j)`` *all-at-once*: vertex updates use
information from an edge and BOTH adjacent vertex values simultaneously,
without materializing an updated adjacency matrix (the pairwise
formulation's extra ``nnz`` writes — paper §IV-A, Fig 8).

Three execution paths:

- ``multilinear_coo``   — sparse edge-list path (production, single shard)
- ``multilinear_dense`` — dense-matrix path (reference; Pallas oracle)
- ``multilinear_2d``    — the paper's distributed schedule (Fig 2): edges on
  a 2D (row, col) device grid, vertex vectors 1D; broadcast x along rows and
  y along columns (``all_gather``), local all-at-once compute, ⊕-reduce over
  columns (masked ``all-reduce(min)``). Call inside ``shard_map``.

The MSF instantiation is ``f(p_i, a_ij, p_j) = (a_ij, p_j) if p_i ≠ p_j
else (∞, 0)`` over the MINWEIGHT monoid; the generic entry points also take
arbitrary ``f``/monoid for reuse by the GNN substrate (DESIGN.md §4).
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from repro.core.semiring import (
    EdgeMin,
    INF,
    IMAX,
    allreduce_argmin,
    axis_argmin,
    segment_argmin,
)


# ---------------------------------------------------------------------------
# MSF instantiation: minimum outgoing edge per (star root) segment
# ---------------------------------------------------------------------------

def min_outgoing_coo(
    p: jax.Array,
    src: jax.Array,
    dst: jax.Array,
    w: jax.Array,
    eid: jax.Array,
    valid: jax.Array,
    n: int,
    *,
    segment: str = "root",
    star: jax.Array | None = None,
) -> EdgeMin:
    """All-at-once kernel for Algorithm 1 line 9(+10).

    f(p_i, a_ij, p_j) = (a_ij, p_j) if p_i != p_j else identity, reduced by
    ``segment``:
      - "root":   segment ids = p[src]  (fuses line 9 with the line-10
                  projection r_{p_i} ← q_i — valid when every tree is a
                  star, the complete-shortcutting invariant)
      - "vertex": segment ids = src     (the paper's literal line 9; use
                  with a separate ``project_to_roots`` for line 10)

    Returns EdgeMin over [n] with payload (p_dst,).
    """
    ps = p[src]
    pd = p[dst]
    outgoing = (ps != pd) & valid
    if star is not None:
        outgoing = outgoing & star[src]
    seg = ps if segment == "root" else src
    return segment_argmin(w, eid, (pd,), seg, n, valid=outgoing)


def project_to_roots(q: EdgeMin, p: jax.Array, n: int) -> EdgeMin:
    """Line 10: r_{p_i} ← MINWEIGHT_j { q_j : p_j = i } (vertex-indexed q)."""
    return segment_argmin(q.w, q.eid, q.payload, p, n, valid=q.w < INF)


@jax.named_scope("segmin")
def min_outgoing_coo_packed(
    p: jax.Array,
    src: jax.Array,
    dst: jax.Array,
    w: jax.Array,
    eid: jax.Array,
    valid: jax.Array,
    n: int,
    *,
    segmin=None,
) -> EdgeMin:
    """pack32 fast path of :func:`min_outgoing_coo` (root-segment form).

    Valid in the paper's integer-weight regime: ``w`` integral in
    [0, 255] and ``eid < 2^24 - 1`` (strict — pack32(255, 2^24-1) would
    collide with the 0xFFFFFFFF identity). The (w, eid) MINWEIGHT key
    packs into one uint32, so the per-iteration reduction is a SINGLE
    segment-min on the packed key plus one masked payload pass — and
    ``segmin`` lets callers swap in the Pallas flat kernel
    (``kernels.ops.make_packed_segmin``) for that dominant reduction.
    """
    from repro.core.semiring import PACK_IDENTITY, pack32, unpack32

    ps = p[src]
    pd = p[dst]
    outgoing = (ps != pd) & valid
    # Mask weights BEFORE the uint32 cast: padding carries +inf, whose
    # float→uint conversion is implementation-defined.
    w_int = jnp.where(outgoing, w, 0.0).astype(jnp.uint32)
    key = jnp.where(outgoing, pack32(w_int, eid), PACK_IDENTITY)
    if segmin is None:
        minkey = jax.ops.segment_min(key, ps, num_segments=n)
    else:
        minkey = segmin(key, ps, n)
    w_out, eid_out = unpack32(minkey)
    winner = outgoing & (key == minkey[ps])
    pay = jax.ops.segment_min(jnp.where(winner, pd, IMAX), ps, num_segments=n)
    empty = minkey == PACK_IDENTITY
    return EdgeMin(
        w=jnp.where(empty, INF, w_out.astype(jnp.float32)),
        eid=jnp.where(empty, IMAX, eid_out),
        payload=(pay,),
    )


def min_outgoing_dense(
    p: jax.Array, a: jax.Array, star: jax.Array | None = None
) -> EdgeMin:
    """Dense-adjacency version (a[i, j] = w or +inf). Used as the oracle for
    the Pallas multilinear kernel and for small-graph validation."""
    n = a.shape[0]
    neq = p[:, None] != p[None, :]
    if star is not None:
        neq = neq & star[:, None]
    w = jnp.where(neq, a, INF)
    eid = jnp.where(w < INF, jnp.arange(n, dtype=jnp.int32)[None, :], IMAX)
    pd = jnp.where(w < INF, p[None, :].astype(jnp.int32), IMAX)
    return axis_argmin(w, eid, (pd,), axis=1)


# ---------------------------------------------------------------------------
# Generic multilinear (GNN substrate reuse)
# ---------------------------------------------------------------------------

def multilinear_coo(
    x: jax.Array,
    y: jax.Array,
    src: jax.Array,
    dst: jax.Array,
    a: jax.Array | None,
    f: Callable,
    *,
    num_segments: int,
    reduce: str = "sum",
) -> jax.Array:
    """w_i = ⊕_{(i,j) ∈ E} f(x_i, a_ij, y_j) with ⊕ in {sum, min, max}.

    ``x``/``y`` may be [n] or [n, d]; ``f`` is applied vectorized over the
    edge dimension.
    """
    xi = x[src]
    yj = y[dst]
    vals = f(xi, a, yj) if a is not None else f(xi, None, yj)
    op = {
        "sum": jax.ops.segment_sum,
        "min": jax.ops.segment_min,
        "max": jax.ops.segment_max,
    }[reduce]
    return op(vals, src, num_segments=num_segments)


def spmm_sum_2d(
    x_local: jax.Array,  # [n/P, h] — 1D-sharded node features
    src_row: jax.Array,  # [E_loc] local src offsets into the row block
    dst_col: jax.Array,  # [E_loc] local dst offsets into the column block
    valid: jax.Array,
    *,
    row_axis: str,
    col_axis: str,
    shard_size: int,
    col_block_size: int,
) -> jax.Array:
    """GNN aggregation (⊕ = sum) on the paper's Fig-2 schedule.

    The same 2D edge partition + row/col vector gathers as the MSF kernel,
    with segment-sum instead of MINWEIGHT: gather the row block of x
    (all_gather over cols, n/R words), aggregate the local edge block by
    destination, ⊕-reduce partials over rows (psum, n/C words), then each
    device slices its own 1D shard out of its column block — zero
    additional resharding. Communication per layer ≈ n/R + n/C words vs the
    1D baseline's full-n feature all-gather (§Perf Cell 4).
    """
    x_row = jax.lax.all_gather(x_local, col_axis, tiled=True)  # [n/R, h]
    msgs = jnp.where(valid[:, None], x_row[src_row], 0.0)
    y_partial = jax.ops.segment_sum(msgs, dst_col, num_segments=col_block_size)
    y_col = jax.lax.psum(y_partial, row_axis)  # [n/C, h]
    r = jax.lax.axis_index(row_axis)
    return jax.lax.dynamic_slice(
        y_col, (r * shard_size, 0), (shard_size, x_local.shape[1])
    )


# ---------------------------------------------------------------------------
# Distributed schedule (paper Fig 2) — call inside shard_map
# ---------------------------------------------------------------------------

def gather_row_col_vectors(
    p_local: jax.Array, row_axis: str | tuple, col_axis: str | tuple
):
    """Redistribute + broadcast step of the paper's kernel.

    The global parent vector is 1D-sharded over (row, col) devices in
    row-major order: device (r, s) owns shard index r*C + s. Gathering over
    ``col_axis`` therefore concatenates the shards of row block r →
    x^(r) ("broadcast x over processes (r, t)"); gathering over
    ``row_axis`` yields the *strided* column block y^(s).

    Returns (x_row_block [n/R], y_col_block [n/C]) as locally dense arrays.
    """
    x_row = jax.lax.all_gather(p_local, col_axis, tiled=True)
    y_col = jax.lax.all_gather(p_local, row_axis, tiled=True)
    return x_row, y_col


def min_outgoing_2d_packed(
    p_local: jax.Array,
    src_row: jax.Array,
    dst_col: jax.Array,
    w: jax.Array,
    eid: jax.Array,
    valid: jax.Array,
    n: int,
    *,
    row_axis,
    col_axis,
) -> EdgeMin:
    """pack32 fast path of the distributed kernel (§Perf variant).

    Valid when weights fit 8 bits (the paper's integer 1..255 regime) and
    undirected edge ids fit 24 bits: the (w, eid) MINWEIGHT key packs into
    one uint32, so the cross-device ⊕-combine needs TWO all-reduce(min)
    passes (packed key + masked payload) instead of three — a 33% cut in
    the dominant collective, with bit-identical winners.
    """
    from repro.core.semiring import pack32, unpack32

    x_row, y_col = gather_row_col_vectors(p_local, row_axis, col_axis)
    ps = x_row[src_row]
    pd = y_col[dst_col]
    outgoing = (ps != pd) & valid
    key = jnp.where(outgoing, pack32(w.astype(jnp.uint32), eid), jnp.uint32(0xFFFFFFFF))
    # segment-min on the packed key (single pass), local then global
    minkey = jax.ops.segment_min(key, ps, num_segments=n)
    minkey = jax.lax.pmin(jax.lax.pmin(minkey, col_axis), row_axis)
    w_out, eid_out = unpack32(minkey)
    # masked payload combine: only the devices holding the winning edge
    # contribute their p_dst
    winner = outgoing & (key == minkey[ps])
    pay = jax.ops.segment_min(jnp.where(winner, pd, IMAX), ps, num_segments=n)
    pay = jax.lax.pmin(jax.lax.pmin(pay, col_axis), row_axis)
    empty = minkey == jnp.uint32(0xFFFFFFFF)
    return EdgeMin(
        w=jnp.where(empty, INF, w_out.astype(jnp.float32)),
        eid=jnp.where(empty, IMAX, eid_out),
        payload=(pay,),
    )


def min_outgoing_2d(
    p_local: jax.Array,
    src_row: jax.Array,  # local edge src, as offset into the row block
    dst_col: jax.Array,  # local edge dst, as offset into the column block
    w: jax.Array,
    eid: jax.Array,
    valid: jax.Array,
    n: int,
    *,
    row_axis,
    col_axis,
    seg_global: jax.Array | None = None,
) -> EdgeMin:
    """The paper's distributed multilinear kernel, fused with the root
    projection: each device owns an edge block A^(r,s); after the row/col
    vector gathers it computes local per-root minima into a dense [n]
    accumulator, then ⊕-combines over the column axis *and* the row axis so
    every device holds r (the paper reduces over columns only because its
    output is row-distributed; our parent updates need r replicated, which
    costs one extra all-reduce round over rows — noted in EXPERIMENTS.md).

    ``seg_global``: optional precomputed global segment ids (defaults to
    p[src] looked up in the gathered row block → root ids).
    """
    x_row, y_col = gather_row_col_vectors(p_local, row_axis, col_axis)
    ps = x_row[src_row]
    pd = y_col[dst_col]
    outgoing = (ps != pd) & valid
    seg = ps if seg_global is None else seg_global
    local = segment_argmin(w, eid, (pd,), seg, n, valid=outgoing)
    combined = allreduce_argmin(local, col_axis)
    return allreduce_argmin(combined, row_axis)

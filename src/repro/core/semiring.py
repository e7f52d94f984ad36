"""Algebraic structures for the MSF formulation (paper §II-A, §III).

The paper's ``(EDGE, MINWEIGHT)`` monoid has elements
``EDGE = (weight, parent)`` and combine = "keep the pair with the least
weight" (CRCW min-write in the PRAM model, a custom MPI reduction in CTF).

TPU adaptation (DESIGN.md §2): we avoid 64-bit packed atomics and instead
implement deterministic *argmin-with-payload* as a small fixed number of
32-bit masked min-reductions, exploiting that effective weights
``(w, eid)`` are lexicographically distinct:

  pass 1:  minw  = min_seg w
  pass 2:  mineid = min_seg (eid   | masked to w == minw)
  pass 3+: payload = min_seg (payload | masked to eid == mineid)

This works for segment reductions (``jax.ops.segment_min``), for dense
axis reductions, and — crucially — for *cross-device* combines, where each
pass is one ``all-reduce(min)`` (see ``repro.core.multilinear``).

A ``pack32`` fast path covers the paper's own evaluation regime (integer
weights 1..255): key = w << 24 | idx for idx < 2^24 — a single reduction,
and the layout the Pallas kernels use.
"""
from __future__ import annotations

from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp

INF = jnp.float32(jnp.inf)
IMAX = jnp.int32(jnp.iinfo(jnp.int32).max)


class EdgeMin(NamedTuple):
    """Per-segment result of a MINWEIGHT reduction."""

    w: jax.Array  # float32 [n]; +inf where the segment is empty
    eid: jax.Array  # int32 [n]; IMAX where empty
    payload: Tuple[jax.Array, ...]  # int32 [n] each; IMAX where empty


@jax.named_scope("segmin")
def segment_argmin(
    w: jax.Array,
    eid: jax.Array,
    payloads: Sequence[jax.Array],
    segment_ids: jax.Array,
    num_segments: int,
    valid: jax.Array | None = None,
) -> EdgeMin:
    """MINWEIGHT reduction by segment, with deterministic (w, eid) tie-break.

    All inputs are edge-indexed [E]. Invalid entries contribute the monoid
    identity (inf, IMAX, ...).
    """
    if valid is not None:
        w = jnp.where(valid, w, INF)
    minw = jax.ops.segment_min(w, segment_ids, num_segments=num_segments)
    on_min = w == minw[segment_ids]  # inf==inf at empty segments is harmless
    if valid is not None:
        on_min = on_min & valid
    eid_m = jnp.where(on_min, eid, IMAX)
    mineid = jax.ops.segment_min(eid_m, segment_ids, num_segments=num_segments)
    winner = on_min & (eid == mineid[segment_ids])
    outs = []
    for p in payloads:
        pm = jnp.where(winner, p, IMAX)
        outs.append(jax.ops.segment_min(pm, segment_ids, num_segments=num_segments))
    return EdgeMin(w=minw, eid=mineid, payload=tuple(outs))


def axis_argmin(
    w: jax.Array,
    eid: jax.Array,
    payloads: Sequence[jax.Array],
    axis: int,
) -> EdgeMin:
    """MINWEIGHT reduction along a dense array axis (used by the dense
    multilinear reference and the Pallas oracle)."""
    minw = jnp.min(w, axis=axis)
    on_min = w == jnp.expand_dims(minw, axis)
    eid_m = jnp.where(on_min, eid, IMAX)
    mineid = jnp.min(eid_m, axis=axis)
    winner = on_min & (eid == jnp.expand_dims(mineid, axis))
    outs = tuple(
        jnp.min(jnp.where(winner, p, IMAX), axis=axis) for p in payloads
    )
    return EdgeMin(w=minw, eid=mineid, payload=outs)


def combine_edgemin(a: EdgeMin, b: EdgeMin) -> EdgeMin:
    """Binary MINWEIGHT combine of two EdgeMin fields (elementwise)."""
    w = jnp.minimum(a.w, b.w)
    a_on = a.w == w
    b_on = b.w == w
    eid = jnp.minimum(jnp.where(a_on, a.eid, IMAX), jnp.where(b_on, b.eid, IMAX))
    a_win = a_on & (a.eid == eid)
    b_win = b_on & (b.eid == eid)
    payload = tuple(
        jnp.minimum(jnp.where(a_win, pa, IMAX), jnp.where(b_win, pb, IMAX))
        for pa, pb in zip(a.payload, b.payload)
    )
    return EdgeMin(w=w, eid=eid, payload=payload)


def allreduce_argmin(em: EdgeMin, axis_name) -> EdgeMin:
    """Cross-device MINWEIGHT combine inside ``shard_map``.

    This is the paper's ⊕-reduction over processor-grid columns (§IV-A),
    expressed as 2+len(payload) masked all-reduce(min)s over ``axis_name``.
    """
    minw = jax.lax.pmin(em.w, axis_name)
    on_min = em.w == minw
    mineid = jax.lax.pmin(jnp.where(on_min, em.eid, IMAX), axis_name)
    winner = on_min & (em.eid == mineid)
    payload = tuple(
        jax.lax.pmin(jnp.where(winner, p, IMAX), axis_name) for p in em.payload
    )
    return EdgeMin(w=minw, eid=mineid, payload=payload)


# ---------------------------------------------------------------------------
# pack32 fast path (paper's integer-weight regime: w in [1, 255], idx < 2^24)
# ---------------------------------------------------------------------------

PACK_IDX_BITS = 24
PACK_IDX_MASK = (1 << PACK_IDX_BITS) - 1
PACK_MAX_W = (1 << (32 - PACK_IDX_BITS)) - 1  # 255 weight levels (paper's regime)
PACK_IDENTITY = jnp.uint32(0xFFFFFFFF)


def pack32(w_int: jax.Array, idx: jax.Array) -> jax.Array:
    """Pack (small int weight, index) into one uint32 min-reducible key."""
    return (w_int.astype(jnp.uint32) << PACK_IDX_BITS) | (
        idx.astype(jnp.uint32) & PACK_IDX_MASK
    )


def unpack32(key: jax.Array) -> Tuple[jax.Array, jax.Array]:
    return (key >> PACK_IDX_BITS).astype(jnp.int32), (
        key & PACK_IDX_MASK
    ).astype(jnp.int32)


def packable(n: int, max_w: int) -> bool:
    return n <= PACK_IDX_MASK + 1 and max_w <= PACK_MAX_W


# Tropical semiring helpers (used by the Bellman-Ford showcase, paper §II-B).
def tropical_spmv(d: jax.Array, src, dst, w, num_segments: int) -> jax.Array:
    """One Bellman-Ford relaxation: d'_j = min(d_j, min_i d_i + w_ij)."""
    cand = d[src] + w
    relaxed = jax.ops.segment_min(cand, dst, num_segments=num_segments)
    return jnp.minimum(d, relaxed)

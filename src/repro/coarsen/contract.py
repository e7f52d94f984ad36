"""Borůvka-style contraction rounds via the AS multilinear kernel (§7.1).

One *level* = K hook+shortcut rounds of the existing multilinear MSF
machinery (``min_outgoing_coo`` → ``hook_and_tiebreak`` →
``complete_shortcut``) starting from singleton stars, followed by the
rank/relabel pass. Each round merges every component with its minimum
outgoing (w, eid)-lex edge — the classic Borůvka step expressed with the
paper's kernels — so K rounds shrink the vertex count by ≥ 2^K wherever
edges remain, and every hooked edge is an MSF edge (cut property under
the distinct (w, eid) total order).

The recorded eids are the graph's *global* edge ids, threaded unchanged
through relabeling and filtering by the engine.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import shortcut as sc
from repro.core.msf import FOREST_WEIGHT, hook_and_tiebreak, record_edges
from repro.core.multilinear import min_outgoing_coo, min_outgoing_coo_packed
from repro.core.semiring import IMAX
from repro.coarsen.relabel import rank_relabel


class ContractResult(NamedTuple):
    parent: jax.Array  # int32 [n]: star-canonical labels after K rounds
    new_ids: jax.Array  # int32 [n]: vertex → supervertex rank in [0, n_next)
    n_next: jax.Array  # int32 scalar: supervertex count
    msf_eids: jax.Array  # int32 [n]: global eids chosen this level (front-packed)
    msf_w: jax.Array  # float32 [n]: their weights, at the same positions
    n_msf_edges: jax.Array  # int32 scalar

    weight = FOREST_WEIGHT  # host float64 sum of the level's hooked weights


def contract_rounds(reduce_fn, n: int, rounds: int) -> ContractResult:
    """Shared K-round hook+shortcut driver; ``reduce_fn(p)`` yields the
    per-root MINWEIGHT EdgeMin for the current parent vector.

    Public: the distributed fused level (``repro.coarsen.dist``) runs the
    same rounds inside ``shard_map`` with a cross-device reduce_fn — all
    the per-round bookkeeping (hook, tie-break, eid recording, shortcut,
    rank/relabel) operates on replicated dense vectors and is shared."""
    with jax.named_scope("contract"):
        p = jnp.arange(n, dtype=jnp.int32)
        msf_eids = jnp.full((n,), IMAX, jnp.int32)
        msf_w = jnp.zeros((n,), jnp.float32)
        n_f = jnp.int32(0)
        for _ in range(rounds):
            r = reduce_fn(p)
            p_h, keep, _ = hook_and_tiebreak(p, r.w, r.eid, r.payload[0])
            msf_eids, msf_w, n_f = record_edges(msf_eids, msf_w, n_f, keep,
                                                r.eid, r.w)
            p = sc.complete_shortcut(p_h)
    with jax.named_scope("relabel"):
        new_ids, n_next = rank_relabel(p)
    return ContractResult(
        parent=p,
        new_ids=new_ids,
        n_next=n_next,
        msf_eids=msf_eids,
        msf_w=msf_w,
        n_msf_edges=n_f,
    )


@partial(jax.jit, static_argnames=("n", "rounds", "pack", "segmin"))
def contract_level(
    src: jax.Array,
    dst: jax.Array,
    w: jax.Array,
    eid: jax.Array,
    valid: jax.Array,
    *,
    n: int,
    rounds: int = 2,
    pack: bool = False,
    segmin=None,
) -> ContractResult:
    """Run K hook+shortcut rounds and rank-relabel the surviving roots.

    ``rounds`` is static and small (the engine's ``rounds_per_level``), so
    the loop unrolls — each round is exactly the complete-variant MSF body
    and preserves the every-tree-a-star invariant at its top.
    """
    if pack:
        def reduce_fn(p):
            return min_outgoing_coo_packed(
                p, src, dst, w, eid, valid, n, segmin=segmin
            )
    else:
        def reduce_fn(p):
            return min_outgoing_coo(p, src, dst, w, eid, valid, n, segment="root")
    return contract_rounds(reduce_fn, n, rounds)


@partial(
    jax.jit,
    static_argnames=("n", "eid_capacity", "rounds", "pack", "segmin"),
)
def contract_level_und(
    lo: jax.Array,
    hi: jax.Array,
    w: jax.Array,
    eid: jax.Array,
    valid: jax.Array,
    *,
    n: int,
    eid_capacity: int,
    rounds: int = 2,
    pack: bool = False,
    segmin=None,
) -> ContractResult:
    """:func:`contract_level` over the *undirected* canonical arrays.

    Two structural savings over feeding the symmetric 2E concatenation:

    - the ``outgoing`` mask is symmetric (p[lo] ≠ p[hi]), so ONE masked
      MINWEIGHT key array serves both directions; the per-root partials
      are two segment-mins (segments p[lo], then p[hi]) ⊕-combined
      elementwise — no 2E intermediates ever materialize;
    - the hook payload (the winner's other-endpoint parent) is recovered
      by *gathering the winning edge back through an eid→position table*
      (one [eid_capacity] scatter per level, reused across rounds)
      instead of a second masked segment reduction per direction.

    Identical results to :func:`contract_level` on the concatenated form:
    the monoid is commutative and the (w, eid) order total, so the
    per-root minimum is direction-agnostic, and the payload is a pure
    function of the winning edge. ``eid_capacity`` is a static bound with
    eid < eid_capacity for every valid edge (the engine passes the padded
    original edge capacity).
    """
    reduce_fn = make_und_reduce(
        lo, hi, w, eid, valid,
        n=n, eid_capacity=eid_capacity, pack=pack, segmin=segmin,
    )
    return contract_rounds(reduce_fn, n, rounds)


def make_und_reduce(
    lo: jax.Array,
    hi: jax.Array,
    w: jax.Array,
    eid: jax.Array,
    valid: jax.Array,
    *,
    n: int,
    eid_capacity: int,
    pack: bool = False,
    segmin=None,
    combine=None,
):
    """Build ``reduce_fn(p) → EdgeMin`` over the undirected canonical arrays.

    ``combine`` is applied to every dense [n] partial *before* winner
    selection: identity (``None``) for the single-shard engine, the
    cross-device all-reduce(min) over the mesh axes for the distributed
    fused level — the MINWEIGHT ⊕-combine of DESIGN.md §2, where each pass
    is one masked min-reduction. With a combine the arrays may be one
    device's *shard* of the edge set: the per-root minimum is the global
    one after the combine, winner masks only fire on shards that hold the
    winning edge, and the payload lookup is masked by locality (the
    eid→position table marks absent eids with −1) so remote shards
    contribute the identity.
    """
    from repro.core.semiring import EdgeMin, INF, PACK_IDENTITY, pack32, unpack32

    if combine is None:
        combine = lambda x: x  # noqa: E731 — identity for the local engine
    e = lo.shape[0]
    pos_of_eid = jnp.full((eid_capacity,), -1, jnp.int32).at[
        jnp.where(valid, eid, eid_capacity)
    ].set(jnp.arange(e, dtype=jnp.int32), mode="drop")
    i_n = jnp.arange(n, dtype=jnp.int32)

    def payload_from_eid(p, mineid, empty):
        pos = pos_of_eid[jnp.clip(mineid, 0, eid_capacity - 1)]
        local = (pos >= 0) & ~empty  # this shard holds the winning edge
        safe = jnp.clip(pos, 0, max(e - 1, 0))
        plo, phi = p[lo[safe]], p[hi[safe]]
        pd = jnp.where(plo == i_n, phi, plo)
        return combine(jnp.where(local, pd, IMAX))

    if pack:
        def reduce_fn(p):
            plo, phi = p[lo], p[hi]
            out = (plo != phi) & valid
            # Mask weights BEFORE the uint32 cast (padding carries +inf).
            w_int = jnp.where(out, w, 0.0).astype(jnp.uint32)
            key = jnp.where(out, pack32(w_int, eid), PACK_IDENTITY)
            with jax.named_scope("segmin"):
                if segmin is None:
                    m1 = jax.ops.segment_min(key, plo, num_segments=n)
                    m2 = jax.ops.segment_min(key, phi, num_segments=n)
                else:
                    m1 = segmin(key, plo, n)
                    m2 = segmin(key, phi, n)
                minkey = combine(jnp.minimum(m1, m2))
            w_out, eid_out = unpack32(minkey)
            empty = minkey == PACK_IDENTITY
            return EdgeMin(
                w=jnp.where(empty, INF, w_out.astype(jnp.float32)),
                eid=jnp.where(empty, IMAX, eid_out),
                payload=(payload_from_eid(p, eid_out, empty),),
            )
    else:
        def reduce_fn(p):
            plo, phi = p[lo], p[hi]
            out = (plo != phi) & valid
            wm = jnp.where(out, w, INF)
            with jax.named_scope("segmin"):
                minw = combine(jnp.minimum(
                    jax.ops.segment_min(wm, plo, num_segments=n),
                    jax.ops.segment_min(wm, phi, num_segments=n),
                ))
                on1 = out & (wm == minw[plo])
                on2 = out & (wm == minw[phi])
                mineid = combine(jnp.minimum(
                    jax.ops.segment_min(
                        jnp.where(on1, eid, IMAX), plo, num_segments=n
                    ),
                    jax.ops.segment_min(
                        jnp.where(on2, eid, IMAX), phi, num_segments=n
                    ),
                ))
            empty = minw == INF
            return EdgeMin(
                w=minw,
                eid=mineid,
                payload=(payload_from_eid(p, mineid, empty),),
            )
    return reduce_fn

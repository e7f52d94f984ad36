"""Edge filtering between contraction levels (DESIGN.md §7.3).

Relabels the edge list into supervertex space, drops self-loops (edges
internal to a contracted component) and deduplicates parallel edges
keeping the minimum-(w, eid)-lex representative. Dropping the heavier
parallels is *exact* under the distinct (w, eid) total order: parallel
supervertex edges close a cycle through the two contracted components,
and the cycle property excludes every non-minimal one from the MSF.

All-device, single jitted call with static shapes:

1. canonical pair keys — packed uint32 ``lo << 16 | hi`` when n ≤ 2^16,
   the (lo, hi) pair beyond (int64 keys are unavailable without
   jax_enable_x64) — sorted on the pair key alone;
2. sort → duplicate pairs become adjacent; segment ids by boundary-flag
   prefix-sum (≤ E segments, independent of n′² — invalid entries sort
   last into one dead segment, so live segments are already
   front-compacted);
3. per-segment MINWEIGHT via the pack32 segment-min in the
   integer-weight regime, the 3-pass masked float (w, eid) reduction
   (``semiring.segment_argmin``) otherwise. The segment ids here are
   *sorted* (a prefix-sum over sort-order boundary flags), so the
   matching Pallas backend is ``kernels.segment_min_sorted`` — O(E)
   compares via scalar-prefetched per-row-block offsets, vs the flat
   kernel's O(E²) rescan at ``num_segments = E``
   (``segmin=None``/"jnp" keeps this step at O(E) via segment_min);
4. gather the winners' (lo, hi, w, global eid).

Original global eids ride through untouched — the level output is still
expressed in input-graph edge ids.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.semiring import (
    IMAX,
    INF,
    PACK_IDENTITY,
    pack32,
    segment_argmin,
    unpack32,
)
from repro.coarsen.relabel import relabel_edges

#: largest vertex count for the packed uint32 pair-key sort path
PAIR_PACK_LIMIT = 1 << 16


class FilterResult(NamedTuple):
    """Deduped canonical edges, indexed by segment (front-packed: entries
    [0, m_new) are the live unique pairs, the rest carry valid=False)."""

    lo: jax.Array  # int32 [E]
    hi: jax.Array  # int32 [E]
    w: jax.Array  # float32 [E]
    eid: jax.Array  # int32 [E] — original global eids
    valid: jax.Array  # bool [E]
    m_new: jax.Array  # int32 scalar: number of unique live pairs


@partial(jax.jit, static_argnames=("n", "pack", "segmin"))
def filter_level(
    und_lo: jax.Array,
    und_hi: jax.Array,
    w: jax.Array,
    eid: jax.Array,
    valid: jax.Array,
    new_ids: jax.Array,
    *,
    n: int,
    pack: bool = False,
    segmin=None,
) -> FilterResult:
    """Jitted wrapper around :func:`filter_level_impl` (same contract)."""
    return filter_level_impl(
        und_lo, und_hi, w, eid, valid, new_ids, n=n, pack=pack, segmin=segmin
    )


@jax.named_scope("filter")
def filter_level_impl(
    und_lo: jax.Array,
    und_hi: jax.Array,
    w: jax.Array,
    eid: jax.Array,
    valid: jax.Array,
    new_ids: jax.Array,
    *,
    n: int,
    pack: bool = False,
    segmin=None,
) -> FilterResult:
    """Relabel into supervertex space, drop self-loops, dedupe parallels.

    Unjitted trace body — the distributed fused level calls this directly
    *inside* ``shard_map`` on its local [Emax] edge block (each device
    sort-dedupes its own block; cross-device parallels survive, which is
    exact — they are non-minimal on a cycle and the hook reduction's
    cross-device combine never picks them while the lighter copy lives).
    Standalone callers use the jitted :func:`filter_level`.

    Takes the *undirected* canonical arrays (one entry per edge, not the
    symmetric directed form) — both directions relabel to the same
    canonical pair, so sorting the directed form would double the
    dominant argsort for no information. ``n`` is the previous level's
    (static) vertex count — the bound on relabeled ids used for sort
    sentinels. ``pack`` requires integral weights in [0, 255] and global
    eids < 2^24 − 1 (the (w, eid) pair is packed jointly, so the sort
    only orders the pair key and the segment-min settles the winner).

    Output entries beyond ``m_new`` are sanitized to the identity
    (lo = hi = 0, w = +inf, eid = IMAX, valid = False) so the arrays can
    feed the next level — or a device residual — without a host pass.
    """
    e = und_lo.shape[0]
    if e == 0:
        # Fully contracted level: nothing to sort — the boundary flag
        # construction below would otherwise build a length-1 array
        # against zero-length sort keys. Return the empty residual.
        z_i = jnp.zeros((0,), jnp.int32)
        return FilterResult(
            lo=z_i,
            hi=z_i,
            w=jnp.zeros((0,), w.dtype),
            eid=z_i,
            valid=jnp.zeros((0,), bool),
            m_new=jnp.int32(0),
        )
    ns, nd = relabel_edges(new_ids, und_lo, und_hi)
    lo = jnp.minimum(ns, nd)
    hi = jnp.maximum(ns, nd)
    real = valid & (lo != hi)

    if pack:
        # Pack (w, eid) into one min-reducible value: the sort then only
        # has to make duplicate pairs adjacent (single pair key — the
        # dominant cost at CPU sort speeds), and the segment-min picks
        # the (w, eid)-lex representative without position bookkeeping.
        w_int = jnp.where(real, w, 0.0).astype(jnp.uint32)
        wkey = jnp.where(real, pack32(w_int, eid), PACK_IDENTITY)
        if n <= PAIR_PACK_LIMIT:
            # Two-operand variadic sort: the pair key orders, the packed
            # value rides along — no order permutation to materialize and
            # the winning pair decodes straight from the key.
            key = (lo.astype(jnp.uint32) << 16) | hi.astype(jnp.uint32)
            key = jnp.where(real, key, jnp.uint32(0xFFFFFFFF))
            key_s, wkey_s = jax.lax.sort((key, wkey), num_keys=1)
            boundary = jnp.concatenate(
                [jnp.ones((1,), bool), key_s[1:] != key_s[:-1]]
            )
            seg = jnp.cumsum(boundary.astype(jnp.int32)) - 1  # [0, E) ranks
            with jax.named_scope("segmin"):
                if segmin is None:
                    minkey = jax.ops.segment_min(wkey_s, seg, num_segments=e)
                else:
                    minkey = segmin(wkey_s, seg, e)
            seg_live = minkey != PACK_IDENTITY
            w_min, eid_min = unpack32(minkey)
            # Every member of a segment carries the identical pair key, so
            # a duplicate-index scatter is deterministic and recovers it.
            keyseg = jnp.zeros((e,), jnp.uint32).at[seg].set(key_s)
            lo_out = (keyseg >> 16).astype(jnp.int32)
            hi_out = (keyseg & jnp.uint32(0xFFFF)).astype(jnp.int32)
        else:
            lo_k = jnp.where(real, lo, jnp.int32(n))
            hi_k = jnp.where(real, hi, jnp.int32(n))
            lo_s, hi_s, wkey_s = jax.lax.sort((lo_k, hi_k, wkey), num_keys=2)
            boundary = jnp.concatenate(
                [
                    jnp.ones((1,), bool),
                    (lo_s[1:] != lo_s[:-1]) | (hi_s[1:] != hi_s[:-1]),
                ]
            )
            seg = jnp.cumsum(boundary.astype(jnp.int32)) - 1
            with jax.named_scope("segmin"):
                if segmin is None:
                    minkey = jax.ops.segment_min(wkey_s, seg, num_segments=e)
                else:
                    minkey = segmin(wkey_s, seg, e)
            seg_live = minkey != PACK_IDENTITY
            w_min, eid_min = unpack32(minkey)
            lo_out = jnp.zeros((e,), jnp.int32).at[seg].set(lo_s)
            hi_out = jnp.zeros((e,), jnp.int32).at[seg].set(hi_s)
        return FilterResult(
            lo=jnp.where(seg_live, lo_out, 0),
            hi=jnp.where(seg_live, hi_out, 0),
            w=jnp.where(seg_live, w_min.astype(w.dtype), INF),
            eid=jnp.where(seg_live, eid_min, IMAX),
            valid=seg_live,
            m_new=jnp.sum(seg_live.astype(jnp.int32)),
        )

    # Float path: the sort only makes duplicate pairs adjacent, carrying
    # the permutation; the MINWEIGHT reduction then picks each pair's
    # (w, eid)-lex minimum by value (eids are distinct). Keeping (w, eid)
    # out of the sort keys matters on TPU, where XLA's sort compiles in
    # time that grows with its operand count (minutes for the old
    # four-key lexsort).
    pos = jnp.arange(e, dtype=jnp.int32)
    if n <= PAIR_PACK_LIMIT:
        key = (lo.astype(jnp.uint32) << 16) | hi.astype(jnp.uint32)
        key = jnp.where(real, key, jnp.uint32(0xFFFFFFFF))
        key_s, order = jax.lax.sort((key, pos), num_keys=1)
        boundary = jnp.concatenate(
            [jnp.ones((1,), bool), key_s[1:] != key_s[:-1]]
        )
    else:
        lo_k = jnp.where(real, lo, jnp.int32(n))
        hi_k = jnp.where(real, hi, jnp.int32(n))
        lo_ks, hi_ks, order = jax.lax.sort((lo_k, hi_k, pos), num_keys=2)
        boundary = jnp.concatenate(
            [
                jnp.ones((1,), bool),
                (lo_ks[1:] != lo_ks[:-1]) | (hi_ks[1:] != hi_ks[:-1]),
            ]
        )
    lo_s, hi_s = lo[order], hi[order]
    w_s, eid_s = w[order], eid[order]
    real_s = real[order]
    seg = jnp.cumsum(boundary.astype(jnp.int32)) - 1  # [0, E) ranks

    em = segment_argmin(w_s, eid_s, (pos,), seg, e, valid=real_s)
    winner = em.payload[0]
    seg_live = em.w < INF

    sel = jnp.clip(winner, 0, e - 1)
    return FilterResult(
        lo=jnp.where(seg_live, lo_s[sel], 0),
        hi=jnp.where(seg_live, hi_s[sel], 0),
        w=jnp.where(seg_live, w_s[sel], INF),
        eid=jnp.where(seg_live, eid_s[sel], IMAX),
        valid=seg_live,
        m_new=jnp.sum(seg_live.astype(jnp.int32)),
    )


def filter_level_callback(
    und_lo: jax.Array,
    und_hi: jax.Array,
    w: jax.Array,
    eid: jax.Array,
    valid: jax.Array,
    new_ids: jax.Array,
    *,
    n: int,
) -> FilterResult:
    """:func:`filter_level` twin that routes the dedupe through the host
    (``jax.pure_callback`` around :func:`filter_level_host`), with the
    same static-capacity padded outputs.

    This is the CPU materialization of the *fused* level's dedupe stage:
    on CPU backends device and host share memory, so the callback is a
    plain function call (no transfer), and numpy's radix/lexsort beats
    XLA's CPU sort ~5×. The trace stays a single jitted executable; on
    TPU the engine picks :func:`filter_level` instead (the sort and the
    sorted-segment Pallas kernel stay on device — a host hop there would
    cost a PCIe round-trip per level, the very thing fusion removes).
    """
    e = und_lo.shape[0]
    if e == 0:
        z_i = jnp.zeros((0,), jnp.int32)
        return FilterResult(
            lo=z_i,
            hi=z_i,
            w=jnp.zeros((0,), w.dtype),
            eid=z_i,
            valid=jnp.zeros((0,), bool),
            m_new=jnp.int32(0),
        )

    def _host(lo_h, hi_h, w_h, eid_h, valid_h, new_ids_h):
        import numpy as np

        l2, h2, w2, e2 = filter_level_host(
            lo_h, hi_h, w_h, eid_h, valid_h, new_ids_h, n
        )
        m = len(l2)
        out_lo = np.zeros(e, np.int32)
        out_hi = np.zeros(e, np.int32)
        out_w = np.full(e, np.inf, np.float32)
        out_eid = np.full(e, np.iinfo(np.int32).max, np.int32)
        out_lo[:m], out_hi[:m] = l2, h2
        out_w[:m], out_eid[:m] = w2, e2
        return out_lo, out_hi, out_w, out_eid, np.int32(m)

    s = jax.ShapeDtypeStruct
    lo2, hi2, w2, eid2, m_new = jax.pure_callback(
        _host,
        (
            s((e,), jnp.int32),
            s((e,), jnp.int32),
            s((e,), jnp.float32),
            s((e,), jnp.int32),
            s((), jnp.int32),
        ),
        und_lo, und_hi, w, eid, valid, new_ids,
    )
    return FilterResult(
        lo=lo2,
        hi=hi2,
        w=w2.astype(w.dtype),
        eid=eid2,
        valid=jnp.arange(e) < m_new,
        m_new=m_new,
    )


def filter_level_host(lo, hi, w, eid, valid, new_ids, n: int):
    """Host (numpy) twin of :func:`filter_level` — same policy, returns
    compact unpadded arrays (lo, hi, w, eid).

    The engine is host-driven between levels anyway, and numpy's lexsort
    beats XLA's CPU sort by an order of magnitude, so this is the CPU
    backend of the ``dedupe="auto"`` switch (the jitted pipeline is the
    TPU path, where the sort and the pack32 segment-min stay on device).
    """
    import numpy as np

    from repro.graphs.structures import canonical_edges, edge_keys

    new_ids = np.asarray(new_ids)
    ns, nd = new_ids[np.asarray(lo)], new_ids[np.asarray(hi)]
    l, h, keep = canonical_edges(ns, nd)
    real = np.asarray(valid) & keep
    l, h = l[real], h[real]
    w, eid = np.asarray(w)[real], np.asarray(eid)[real]
    key = edge_keys(l, h, n)  # shared collision-free pair key
    order = np.lexsort((eid, w, key))  # per pair: min (w, eid) first
    key_s = key[order]
    first = np.ones(len(key_s), bool)
    first[1:] = key_s[1:] != key_s[:-1]
    idx = order[first]
    return l[idx], h[idx], w[idx], eid[idx]

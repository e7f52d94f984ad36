"""Graph containers used throughout the framework.

The canonical representation is a *symmetric* COO edge list: every
undirected edge {u, v} appears twice, as (u, v) and (v, u), sharing one
global edge id ``eid``.  Distinct effective weights (required by
Awerbuch-Shiloach, paper §II) are guaranteed lexicographically by the
pair ``(w, eid)`` — see ``repro.core.semiring``.

Arrays may be padded to a static size; ``valid`` marks real edges.
``Graph`` is registered as a JAX pytree with ``n`` (vertex count) static,
so it can be passed straight through ``jax.jit`` boundaries.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import numpy as np


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Graph:
    """Symmetric COO graph. ``src/dst/eid`` int32 [E], ``w`` float32 [E]."""

    src: jax.Array
    dst: jax.Array
    w: jax.Array
    eid: jax.Array
    valid: jax.Array  # bool [E]; False for padding entries
    n: int = dataclasses.field(metadata=dict(static=True))

    @property
    def num_directed_edges(self) -> int:
        return int(self.src.shape[0])

    def pad_to(self, e_pad: int) -> "Graph":
        e = self.src.shape[0]
        if e_pad < e:
            raise ValueError(f"pad_to({e_pad}) smaller than E={e}")
        pad = e_pad - e

        def _pad(a, fill):
            return np.concatenate([np.asarray(a), np.full((pad,), fill, np.asarray(a).dtype)])

        return Graph(
            src=_pad(self.src, 0),
            dst=_pad(self.dst, 0),
            w=_pad(self.w, np.float32(np.inf)),
            eid=_pad(self.eid, np.iinfo(np.int32).max),
            valid=_pad(self.valid, False),
            n=self.n,
        )


def canonical_edges(u, v):
    """Canonical undirected endpoint order: (lo, hi, keep) with lo < hi.

    ``keep`` masks out self-loops. Works on numpy and jax arrays alike
    (elementwise min/max/compare only).
    """
    xp = np
    if not isinstance(u, np.ndarray):  # jax inputs: stay on device
        import jax.numpy as jnp

        xp = jnp
    lo, hi = xp.minimum(u, v), xp.maximum(u, v)
    return lo, hi, lo != hi


def edge_keys(lo, hi, n: int) -> np.ndarray:
    """Collision-free int64 key ``lo * n + hi`` for canonical (lo < hi) pairs.

    Host-side (int64) form — the streaming delta layer packs the same key
    into uint32 for its on-device sorted-lookup when n ≤ 2^16
    (``repro.stream.delta``).
    """
    lo = np.asarray(lo, np.int64)
    hi = np.asarray(hi, np.int64)
    return lo * np.int64(n) + hi


def dedupe_canonical(lo, hi, w, n: int):
    """Collapse duplicate canonical pairs, keeping the smallest weight
    (ties: smallest original index) — the same policy as ``from_edges``.

    Returns (lo, hi, w) host arrays sorted by key with one entry per pair.
    """
    lo = np.asarray(lo, np.int64)
    hi = np.asarray(hi, np.int64)
    w = np.asarray(w, np.float64)
    key = edge_keys(lo, hi, n)
    order = np.lexsort((w, key))
    key, lo, hi, w = key[order], lo[order], hi[order], w[order]
    first = np.ones(len(key), bool)
    first[1:] = key[1:] != key[:-1]
    return lo[first], hi[first], w[first]


def from_edges(u: np.ndarray, v: np.ndarray, w: np.ndarray, n: int) -> Graph:
    """Build a symmetric ``Graph`` from one direction of each undirected edge.

    Self-loops are dropped; duplicate undirected pairs are collapsed
    (keeping the smallest weight, then smallest original index).
    """
    u = np.asarray(u, np.int64)
    v = np.asarray(v, np.int64)
    w = np.asarray(w, np.float64)
    lo, hi, keep = canonical_edges(u, v)
    lo, hi, w = dedupe_canonical(lo[keep], hi[keep], w[keep], n)
    m = len(lo)
    eid = np.arange(m, dtype=np.int32)
    src = np.concatenate([lo, hi]).astype(np.int32)
    dst = np.concatenate([hi, lo]).astype(np.int32)
    ww = np.concatenate([w, w]).astype(np.float32)
    ee = np.concatenate([eid, eid])
    return Graph(
        src=src,
        dst=dst,
        w=ww,
        eid=ee.astype(np.int32),
        valid=np.ones(2 * m, bool),
        n=int(n),
    )


def graph_from_canonical(lo, hi, w, eid, valid, n: int) -> Graph:
    """Symmetric ``Graph`` from canonical undirected arrays, preserving the
    caller's global eids (unlike :func:`from_edges`, which renumbers).

    Used by the coarsening engine: contracted levels carry the *original*
    input-graph eids through relabel/filter so the final MSF edge set is
    reported in input ids. Arrays may be padded (``valid`` masks).
    """
    lo = np.asarray(lo, np.int32)
    hi = np.asarray(hi, np.int32)
    w = np.asarray(w, np.float32)
    eid = np.asarray(eid, np.int32)
    valid = np.asarray(valid, bool)
    return Graph(
        src=np.concatenate([lo, hi]),
        dst=np.concatenate([hi, lo]),
        w=np.concatenate([w, w]),
        eid=np.concatenate([eid, eid]),
        valid=np.concatenate([valid, valid]),
        n=int(n),
    )


def to_csr(graph: Graph) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Return (indptr, indices, weights, eids) CSR views of the valid edges."""
    src = np.asarray(graph.src)
    dst = np.asarray(graph.dst)
    w = np.asarray(graph.w)
    eid = np.asarray(graph.eid)
    valid = np.asarray(graph.valid)
    src, dst, w, eid = src[valid], dst[valid], w[valid], eid[valid]
    order = np.argsort(src, kind="stable")
    src, dst, w, eid = src[order], dst[order], w[order], eid[order]
    indptr = np.zeros(graph.n + 1, np.int64)
    np.add.at(indptr, src + 1, 1)
    indptr = np.cumsum(indptr)
    return indptr, dst, w, eid


def nx_free_msf_weight(graph: Graph) -> float:
    """Oracle MSF weight via scipy (total weight is unique across all MSFs)."""
    import scipy.sparse as sp
    import scipy.sparse.csgraph as csg

    src = np.asarray(graph.src)
    dst = np.asarray(graph.dst)
    w = np.asarray(graph.w)
    valid = np.asarray(graph.valid)
    src, dst, w = src[valid], dst[valid], w[valid]
    a = sp.coo_matrix((w, (src, dst)), shape=(graph.n, graph.n)).tocsr()
    t = csg.minimum_spanning_tree(a)
    return float(t.data.sum(dtype=np.float64))  # exact for integer weights


def nx_free_n_components(graph: Graph) -> int:
    import scipy.sparse as sp
    import scipy.sparse.csgraph as csg

    src = np.asarray(graph.src)
    dst = np.asarray(graph.dst)
    valid = np.asarray(graph.valid)
    src, dst = src[valid], dst[valid]
    a = sp.coo_matrix(
        (np.ones(len(src)), (src, dst)), shape=(graph.n, graph.n)
    ).tocsr()
    ncc, _ = csg.connected_components(a, directed=False)
    return int(ncc)

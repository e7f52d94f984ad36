"""Plain references for the benchmark's comparisons, and the controls.

Nothing here imports the program under test. The references are scipy's
Kruskal minimum spanning tree and connected components over the same
edges, computed on the host once the measured window has closed.

Batch solves: the configuration states the strict total order (w, eid)
-- integer weight first, then edge id -- under which the minimum
spanning forest is unique. The reference encodes that order exactly as
one float64 key ``w * 2^k + eid`` (2^k > every edge id), so scipy's
forest is that unique forest and a solve is compared edge for edge.
No lower precision changes that forest: the weights are exact down to
8 bits, the whole key fits 32 bits at these sizes, and wherever a
narrower key ties, scipy falls back on storage order, which is edge-id
order. So the control breaks the stated guarantee instead: it breaks
weight ties in an order drawn from a seed, not by edge id -- what a
solve that loses track of edge ids (a wrapped slot index, a relabelled
layout) would do. Its forest has the same weight and other edges.

Stream service: the reference's partition of the loaded graph is
scipy's connected components of its edges, and the published forest
weight is checked against scipy's spanning forest weight. The control
answers from the state one acknowledged write behind (before the load),
which breaks the guarantee that an acknowledged write is visible in
every later version.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
import scipy.sparse.csgraph as csg


def _canonical_roots(parent) -> np.ndarray:
    p = np.asarray(parent)
    while True:
        gp = p[p]
        if np.array_equal(gp, p):
            return p
        p = gp


def partition_mismatch(labels, got) -> int:
    """How far the labelling ``got`` is from the partition ``labels``
    (both over the same vertices): 0 exactly when they name the same
    partition, up to renaming."""
    labels = np.asarray(labels, np.int64)
    got = np.asarray(got, np.int64)
    if labels.shape != got.shape:
        return max(len(labels), len(got))
    if not len(labels):
        return 0
    n_ref = len(np.unique(labels))
    pairs = len(np.unique(labels * (int(got.max()) + 1) + got))
    return (pairs - n_ref) + abs(len(np.unique(got)) - n_ref)


class ForestReference:
    """The unique (w, eid) minimum spanning forest of a symmetric graph."""

    def __init__(self, g, tie_seed=None):
        src, dst = np.asarray(g.src), np.asarray(g.dst)
        w, eid = np.asarray(g.w), np.asarray(g.eid)
        up = np.asarray(g.valid) & (src < dst)
        lo, hi, w, eid = src[up], dst[up], w[up], eid[up]
        self.n = int(g.n)
        shift = float(1 << max(1, int(eid.max()).bit_length()))
        rank = eid
        if tie_seed is not None:  # the control's tie order
            rank = np.random.default_rng(tie_seed).permutation(int(eid.max()) + 1)[eid]
        key = w.astype(np.float64) * shift + rank
        forest = csg.minimum_spanning_tree(
            sp.csr_matrix((key, (lo, hi)), shape=(self.n, self.n)))
        forest = forest.tocoo()
        # Map each forest entry back to its edge id by its endpoints.
        order = np.lexsort((hi, lo))
        pair = lo[order].astype(np.int64) * self.n + hi[order]
        f_lo = np.minimum(forest.row, forest.col).astype(np.int64)
        f_hi = np.maximum(forest.row, forest.col).astype(np.int64)
        at = np.searchsorted(pair, f_lo * self.n + f_hi)
        self.eids = np.sort(eid[order][at].astype(np.int64))
        ncc, self.labels = csg.connected_components(forest, directed=False)
        self.n_components = int(ncc)

    @property
    def parent(self) -> np.ndarray:
        """The partition as a parent vector: each vertex points at the
        smallest vertex of its component."""
        rep = np.full(self.n_components, self.n, np.int64)
        np.minimum.at(rep, self.labels, np.arange(self.n))
        return rep[self.labels]

    def compare(self, msf_eids, parent) -> dict:
        """Numbers of one solve against this reference; all 0 when right."""
        got = np.asarray(msf_eids, np.int64)
        uniq = np.unique(got)
        return {
            "wrong_forest_edges": int(len(np.setxor1d(uniq, self.eids))
                                      + len(got) - len(uniq)),
            "wrong_component_labels": int(partition_mismatch(
                self.labels, _canonical_roots(parent))),
        }


def forest_control(g, seed: int = 0) -> ForestReference:
    """The reference with weight ties broken in a seeded order."""
    return ForestReference(g, tie_seed=seed)


def _adjacency(n: int, lo, hi, w=None):
    data = np.ones(len(lo)) if w is None else np.asarray(w, np.float64)
    return sp.csr_matrix((data, (np.asarray(lo), np.asarray(hi))), shape=(n, n))


def components(n: int, lo, hi) -> np.ndarray:
    """Connected-component labels of the undirected edges (lo, hi)."""
    return csg.connected_components(_adjacency(n, lo, hi), directed=False)[1]


def forest_weight(n: int, lo, hi, w) -> float:
    """Weight of a minimum spanning forest of the undirected edges, each
    pair given once with a positive weight."""
    forest = csg.minimum_spanning_tree(_adjacency(n, lo, hi, w))
    return float(forest.data.sum(dtype=np.float64))

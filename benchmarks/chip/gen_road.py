"""The general generator of road-network solve traffic.

Configuration kind ``road``: a stand-in for a road network (GAP ``road``,
the DIMACS USA graph), made on the host from the seed, and solved back to
back through ``repro.solve.plan(graph, SolveSpec(**spec)).solve()`` as
``gen_batch`` solves the kron graph.

The stand-in keeps the source's shape:

- a 2^(scale/2) x 2^(scale/2) grid, vertex ids in row-major order;
- a spanning tree of the grid: every vertex but the corner (0, 0) joins
  its left or its upper neighbour (the first row joins left, the first
  column up), so the graph is one connected network with a hop diameter
  of the order of the grid's side;
- every other grid edge kept with probability ``extra_edge_p`` (0.22
  gives the source's 2.44 directed slots per vertex);
- integer lengths uniform in 1..``weight_max``, stored as float32.

The network is one fixed network, as the source is one file: its edges
and the order of its lengths (a random permutation of the edges) come
from the configuration's ``network_seed``. The run's seed draws the
lengths themselves: a sample of uniform integers, sorted and laid along
that order. So every seed hooks along the same structure, in the same
rounds and pointer jumps, and solves the same amount of work; what the
seed changes is every length, which lengths tie (the ties then go by
edge id, so the tie-breaks and the forest move with the seed) and the
forest weight.

The graph is laid out as ``repro.graphs.structures.from_edges`` lays out
its pairs: canonical pairs (lo < hi) sorted by (lo, hi), edge ids in that
order, slots ``[lo..., hi...]``.

Traffic parameters (``traffic/<mix>.json``): as ``gen_batch``'s.

The comparison is ``gen_batch``'s -- the forest edge for edge and the
labels against scipy's (``reference.ForestReference``) -- plus
``wrong_forest_weight``: the solve's reported weight against the exact
integer sum of the reference forest's weights. The control breaks the tie
order as ``gen_batch``'s does, and sums its forest's weight in float32,
one edge after another.
"""
from __future__ import annotations

import time

import numpy as np

import gen_batch
from records import Op, annotate
from reference import ForestReference, forest_control
from repro.solve import SolveReport, SolveSpec, plan


def road_pairs(scale: int, extra_edge_p: float, weight_max: int,
               network_seed: int, seed: int):
    """(n, lo, hi, w) of the stand-in: canonical pairs sorted by (lo, hi)
    and their integer lengths. The edges and the lengths' order come from
    ``network_seed``, the lengths from ``seed`` (each any integer >= 0)."""
    if scale % 2 or not 2 <= scale <= 30:
        raise ValueError(f"scale must be even and in 2..30, got {scale}")
    side = 1 << (scale // 2)
    n = side * side
    net = np.random.default_rng(int(network_seed))
    x = np.arange(n, dtype=np.int64)
    row, col = x // side, x % side
    coin = net.random(n) < 0.5
    left = np.where(row == 0, True, np.where(col == 0, False, coin))
    keep_left = (col > 0) & (left | (net.random(n) < extra_edge_p))
    keep_up = (row > 0) & (~left | (net.random(n) < extra_edge_p))
    lo = np.concatenate([x[keep_left] - 1, x[keep_up] - side])
    hi = np.concatenate([x[keep_left], x[keep_up]])
    order = np.argsort(lo * n + hi, kind="stable")
    lo, hi = lo[order].astype(np.int32), hi[order].astype(np.int32)
    lengths = np.random.default_rng(int(seed)).integers(1, int(weight_max) + 1, len(lo))
    w = np.empty(len(lo), np.float32)
    w[net.permutation(len(lo))] = np.sort(lengths)
    return n, lo, hi, w


def road_graph(scale: int, extra_edge_p: float, weight_max: int,
               network_seed: int, seed: int):
    """The stand-in as the host ``Graph`` that ``repro.solve.plan`` takes."""
    from repro.graphs.structures import Graph

    n, lo, hi, w = road_pairs(scale, extra_edge_p, weight_max, network_seed, seed)
    eid = np.arange(len(lo), dtype=np.int32)
    return Graph(
        src=np.concatenate([lo, hi]), dst=np.concatenate([hi, lo]),
        w=np.concatenate([w, w]), eid=np.concatenate([eid, eid]),
        valid=np.ones(2 * len(lo), bool), n=n,
    )


class Driver(gen_batch.Driver):
    def __init__(self, cfg: dict, mix: dict, seed: int):
        super().__init__(cfg, mix, seed)
        self.weights: list = []  # each timed solve's reported weight

    def setup(self) -> dict:
        if "shortcut_jumps" not in SolveReport._fields:
            raise RuntimeError("this program's SolveReport has no shortcut_jumps, "
                               "and its forest weight is not exact: it cannot "
                               "run a road cell")
        c = self.cfg
        self.g = road_graph(c["scale"], c["extra_edge_p"], c["weight_max"],
                            c["network_seed"], self.seed)
        self.spec = SolveSpec(**self.mix["spec"])
        p = plan(self.g, self.spec)
        for _ in range(max(1, int(self.mix.get("warmup_solves", 1)))):
            rep = p.solve()
        slots = int(self.g.src.shape[0])
        return {"n": self.g.n, "directed_slots": slots,
                "slots_per_vertex": slots / self.g.n,
                "warmup_components": rep.n_components,
                "pack": bool(p.resolved.pack), "dedupe": p.resolved.dedupe}

    def window(self, seconds: float) -> list:
        ops = []
        t0 = time.perf_counter()
        while True:
            with annotate("bench.solve"):
                s = time.perf_counter()
                try:
                    with annotate("bench.plan"):
                        p = plan(self.g, self.spec)
                    rep = p.solve()
                    ok, info = True, {"iterations": int(rep.iterations),
                                      "levels": len(rep.levels),
                                      "shortcut_jumps": int(rep.shortcut_jumps)}
                    self.outputs.append((rep.msf_eids, rep.parent))
                    self.weights.append(float(rep.weight))
                except Exception as e:  # a failed solve counts in `failed`
                    ok, info = False, {"error": repr(e)[:300]}
                    self.outputs.append(None)
                    self.weights.append(None)
                e = time.perf_counter()
            ops.append(Op("solve", s, s, e, ok, 1, info))
            if e - t0 >= seconds:
                return ops

    def _weight_of_eid(self) -> np.ndarray:
        eid = np.asarray(self.g.eid)
        w = np.empty(int(eid.max()) + 1, np.float32)
        w[eid] = np.asarray(self.g.w)
        return w

    def check(self, control: bool = False):
        """``gen_batch``'s numbers and ``wrong_forest_weight``: the largest
        distance, in weight units, of a solve's weight from the exact one.
        With ``control``, the tie-broken control forest in the program's
        place, its weight summed in float32 edge after edge."""
        ref = ForestReference(self.g)
        w_of = self._weight_of_eid()
        exact = int(w_of[ref.eids].astype(np.int64).sum())
        outs, weights = self.outputs, self.weights
        if control:
            c = forest_control(self.g, self.seed)
            outs = [(c.eids, c.parent)] * max(1, len(outs))
            weights = [float(np.cumsum(w_of[c.eids], dtype=np.float32)[-1])] * len(outs)
        worst = {"wrong_forest_edges": 0, "wrong_component_labels": 0,
                 "wrong_forest_weight": 0.0}
        missing = wrong = 0
        for out, weight in zip(outs, weights):
            if out is None:
                missing += 1
                continue
            got = ref.compare(*out)
            got["wrong_forest_weight"] = abs(weight - exact)
            wrong += any(got.values())
            for k, v in got.items():
                worst[k] = max(worst[k], v)
        return ({**{k: (v, 0) for k, v in worst.items()},
                 "solves_without_forest": (missing, 0)}, wrong)

"""The general generator of batch-solve traffic.

Configuration kind ``batch``: one graph, made in set-up from the
seed, solved back to back through the user's entry point
``repro.solve.plan(graph, SolveSpec(**spec)).solve()``. The input is the
host-resident ``Graph``; a solve ends when its forest's edge ids and
component labels are host arrays.

Traffic parameters (``traffic/<mix>.json``):

- ``spec``: the ``SolveSpec`` fields of the plan;
- ``warmup_solves``: solves in set-up, which compile every shape the
  window's solves use (they solve the same graph).
"""
from __future__ import annotations

import time

import kron
from records import Op, annotate
from reference import ForestReference, forest_control
from repro.solve import SolveSpec, plan


class Driver:
    def __init__(self, cfg: dict, mix: dict, seed: int):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.outputs: list = []

    def setup(self) -> dict:
        c = self.cfg
        self.g = kron.kron_graph(c["scale"], c["edge_factor"], self.seed)
        self.spec = SolveSpec(**self.mix["spec"])
        p = plan(self.g, self.spec)
        for _ in range(int(self.mix.get("warmup_solves", 1))):
            p.solve()
        return {"n": self.g.n, "directed_slots": int(self.g.src.shape[0]),
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
                                      "levels": len(rep.levels)}
                    self.outputs.append((rep.msf_eids, rep.parent))
                except Exception as e:  # a failed solve counts in `failed`
                    ok, info = False, {"error": repr(e)[:300]}
                    self.outputs.append(None)
                e = time.perf_counter()
            ops.append(Op("solve", s, s, e, ok, 1, info))
            if e - t0 >= seconds:
                return ops

    def facts(self) -> dict:
        return {}

    def close(self) -> None:
        pass

    def check(self, control: bool = False):
        """Every timed solve's forest and labels against the reference;
        with ``control``, the reference with ties broken in another order
        than edge id in the program's place.
        Returns ({number: (value, limit)}, solves that said a wrong thing)."""
        ref = ForestReference(self.g)
        outs = self.outputs
        if control:
            c = forest_control(self.g, self.seed)
            outs = [(c.eids, c.parent)] * max(1, len(outs))
        worst = {"wrong_forest_edges": 0, "wrong_component_labels": 0}
        missing = wrong = 0
        for out in outs:
            if out is None:
                missing += 1
                continue
            got = ref.compare(*out)
            wrong += any(got.values())
            for k, v in got.items():
                worst[k] = max(worst[k], v)
        return ({"wrong_forest_edges": (worst["wrong_forest_edges"], 0),
                 "wrong_component_labels": (worst["wrong_component_labels"], 0),
                 "solves_without_forest": (missing, 0)}, wrong)

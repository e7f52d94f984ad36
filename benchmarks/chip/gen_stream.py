"""The general generator of traffic to the served stream.

Configuration kind ``stream``: ``MSFServer`` over a stream plan
(``repro.solve.plan(n, SolveSpec(mode="stream", ...))``), as
``launch/serve_graph.py --serve`` builds it, driven over loopback by
``ServeClient``. Set-up loads the service's graph -- the Kronecker graph
of ``kron.kron_graph`` at the configuration's scale and edge factor --
through the plan's bulk surface ``recertify(u, v, w)``, then starts the
server.

Traffic parameters (``traffic/<mix>.json``):

- ``queries``: an open-loop reader: ``{"rate": requests/s, "points": 64,
  "ops": ["connected", "component_id"]}``. Arrival gaps are the
  exponential distribution's quantiles at ``rate``, in an order drawn
  from the seed, so every seed sends the same number of requests over
  the window; ops alternate as listed; endpoints are drawn uniformly
  from all the graph's vertices, so answers span the giant component,
  the small ones and the vertices with no edge.

Latency is measured from a request's due time to its response, so a
late sender shows as latency.
"""
from __future__ import annotations

import threading
import time

import numpy as np

import kron
from records import Op
from reference import components, forest_weight, partition_mismatch
from repro import serve
from repro.solve import SolveSpec, plan
from repro.stream.service import next_pow2

GRACE_S = 60.0  # how long past the window's close an answer is awaited
READBACK = 1 << 16  # loaded edges and vertices read back after the window
CHUNK = 256  # points per read-back request: a shape the window warmed


class Driver:
    def __init__(self, cfg: dict, mix: dict, seed: int):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.rng = np.random.default_rng(seed)
        self.queries: list = []
        self.handle = None

    # -- set-up -----------------------------------------------------------

    def setup(self) -> dict:
        c = self.cfg
        self.n = 1 << c["scale"]
        u, v, w = kron.raw_edges(c["scale"], c["edge_factor"] << c["scale"], self.seed)
        self.lo, self.hi, self.w = kron.dedupe(u, v, w, c["scale"])
        self.stream = plan(self.n, SolveSpec(mode="stream", **c["spec"]))
        self.stream.recertify(self.lo, self.hi, self.w)
        self.handle = serve.start_in_thread(self.stream,
                                            serve.ServeConfig(**c["serve"]))
        self.client = serve.ServeClient(self.handle.address, timeout=GRACE_S * 5)
        self.loaded = self.client.status()
        q = self.mix["queries"]
        # every padded batch shape the fused query call can see
        k = next_pow2(min(q["points"], CHUNK))
        while k <= next_pow2(c["serve"]["micro_batch"]):
            z = np.zeros(k, np.int32)
            self.stream.service.answer(z, z)
            k *= 2
        self.counters0 = self._counters()
        return {"n": self.n, "loaded_edges": int(len(self.lo)),
                "forest_edges_at_window_start":
                    int(self.loaded["result"]["n_forest_edges"]),
                "union_slots": int(self.stream.engine.union_edge_capacity)}

    def _counters(self) -> dict:
        m = self.client.metrics()["result"]["metrics"]
        occ = m["histograms"].get("serve.batch_occupancy", {})
        return {"queries": m["counters"].get("serve.queries", 0),
                "batches": occ.get("count", 0)}

    # -- the window -------------------------------------------------------

    def _schedule(self, seconds: float):
        q = self.mix["queries"]
        count = max(1, int(round(q["rate"] * seconds)))
        gaps = -np.log1p(-(np.arange(count) + 0.5) / count)
        gaps = self.rng.permutation(gaps)
        gaps *= seconds / gaps.sum()
        offsets = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        pts = self.rng.integers(0, self.n, (count, 2, q["points"]))
        return offsets, pts

    def _reader(self, seconds: float, t0: float, out: list):
        q = self.mix["queries"]
        offsets, pts = self._schedule(seconds)
        futs = []
        for i, off in enumerate(offsets):
            due = t0 + off
            while (now := time.perf_counter()) < due:
                time.sleep(min(due - now, 0.002))
            op = q["ops"][i % len(q["ops"])]
            fields = {"u": pts[i, 0].tolist()}
            if op == "connected":
                fields["v"] = pts[i, 1].tolist()
            rec = Op(op, due, time.perf_counter(), float("nan"), False,
                     q["points"], {"u": pts[i, 0], "v": pts[i, 1]})
            fut = self.client.submit(op, **fields)
            fut.add_done_callback(lambda f, r=rec: _done(f, r))
            futs.append(fut)
            out.append(rec)
        deadline = time.perf_counter() + GRACE_S + seconds
        for f in futs:
            try:
                f.result(timeout=max(0.0, deadline - time.perf_counter()))
            except Exception:
                pass  # never answered: t_done stays nan

    def window(self, seconds: float) -> list:
        ops: list = []
        t = threading.Thread(target=self._reader,
                             args=(seconds, time.perf_counter(), ops), daemon=True)
        t.start()
        t.join(timeout=seconds + 2 * GRACE_S)
        self.queries = ops
        return ops

    def facts(self) -> dict:
        c1 = self._counters()
        late = [o.t_send - o.t_sched for o in self.queries]
        return {"served_queries": c1["queries"] - self.counters0["queries"],
                "fused_batches": c1["batches"] - self.counters0["batches"],
                "sender_late_p50_ms": float(np.median(late) * 1e3) if late else None,
                "sender_late_max_ms": float(np.max(late) * 1e3) if late else None}

    def close(self) -> None:
        """Read a sample of the loaded graph back, drawn from the seed: its
        edges' endpoints must be connected, its vertices' component ids
        must name the graph's partition. Then stop the server."""
        wait = {"deadline_ms": GRACE_S * 1e3}
        self.rb_edges = np.sort(self.rng.choice(len(self.lo), min(READBACK, len(self.lo)),
                                                replace=False))
        self.rb_verts = np.sort(self.rng.choice(self.n, min(READBACK, self.n),
                                                replace=False))
        conn, comp = [], []
        for at in range(0, len(self.rb_edges), CHUNK):
            idx = self.rb_edges[at:at + CHUNK]
            r = self.client.connected(self.lo[idx], self.hi[idx], **wait)
            conn.append(r["result"]["connected"] if r.get("ok") else [False] * len(idx))
        for at in range(0, len(self.rb_verts), CHUNK):
            pts = self.rb_verts[at:at + CHUNK]
            r = self.client.component_id(pts, **wait)
            comp.append(r["result"]["component"] if r.get("ok") else [-1] * len(pts))
        self.rb_conn = np.concatenate(conn or [np.zeros(0, bool)]).astype(bool)
        self.rb_comp = np.concatenate(comp or [np.zeros(0, np.int64)])
        self.client.close()
        self.handle.drain()
        self.handle = None

    # -- the comparison ---------------------------------------------------

    def check(self, control: bool = False):
        """The loaded snapshot's forest weight, every query answer and the
        read-back against the reference's partition of the loaded graph.
        With ``control`` the answers come from the state one acknowledged
        write behind: before the load, every vertex alone.
        Returns ({number: (value, limit)}, window ops that said a wrong thing)."""
        labels = components(self.n, self.lo, self.hi)
        before = np.arange(self.n)
        version = int(self.loaded["snapshot_version"])
        got_w = float(self.loaded["result"]["weight"]) if not control else 0.0
        wrong_w = int(got_w != forest_weight(self.n, self.lo, self.hi, self.w))
        stale = int(bool(self.loaded.get("stale")))
        wrong_ans = unmatched = missing = wrong_ops = 0
        comp_true, comp_got = [], []
        for rec in self.queries:
            if np.isnan(rec.t_done):
                missing += 1
                continue
            if not rec.ok:
                continue  # refused or late: counted in `failed`
            resp = rec.info["resp"]
            if int(resp["snapshot_version"]) != version:
                unmatched += 1
                continue
            u, v = rec.info["u"], rec.info["v"]
            if rec.kind == "connected":
                got = np.asarray(resp["result"]["connected"], bool)
                if control:
                    got = before[u] == before[v]
                bad = int(np.count_nonzero(got != (labels[u] == labels[v])))
            else:
                got = np.asarray(resp["result"]["component"], np.int64)
                if control:
                    got = before[u]
                bad = partition_mismatch(labels[u], got)
                comp_true.append(labels[u])
                comp_got.append(got)
            wrong_ans += bad
            wrong_ops += bool(bad)
        if comp_true:  # the version's ids name one partition across requests
            wrong_ans += partition_mismatch(np.concatenate(comp_true),
                                            np.concatenate(comp_got))
        conn, comp = self.rb_conn, self.rb_comp
        if control:
            conn = self.lo[self.rb_edges] == self.hi[self.rb_edges]
            comp = before[self.rb_verts]
        return ({"wrong_loaded_weight": (wrong_w, 0),
                 "stale_snapshot": (stale, 0),
                 "wrong_query_answers": (int(wrong_ans), 0),
                 "answers_at_unknown_version": (unmatched, 0),
                 "answers_never_came": (missing, 0),
                 "edges_not_read_back": (int(np.count_nonzero(~conn)), 0),
                 "wrong_readback_components": (int(partition_mismatch(
                     labels[self.rb_verts], comp)), 0)}, wrong_ops)


def _done(fut, rec: Op) -> None:
    rec.t_done = time.perf_counter()
    try:
        resp = fut.result()
    except Exception:
        rec.t_done = float("nan")
        return
    rec.ok = bool(resp.get("ok"))
    rec.info["resp"] = resp

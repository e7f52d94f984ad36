"""Paper Fig 5/6: strong scaling.

Wall-clock scaling needs real chips; what the dry-run *can* measure is the
thing the paper's scaling is made of: per-device communication volume and
per-device work as p grows. We lower the distributed MSF engine for
p ∈ {1, 4, 16, 64} (2D grids) on a fixed graph shape and report per-device
collective bytes per AS iteration (from the compiled HLO) plus per-device
edge work — the strong-scaling curve of the paper's Fig 2 schedule.
Single-device wall time on the real graphs (Fig 5/6 inputs, scaled down)
anchors the absolute numbers.
"""
from __future__ import annotations

import subprocess
import sys
import os
import json

from benchmarks.common import emit, measure, point
from repro.core.msf import msf
from repro.graphs import grid_road_graph, rmat_graph

_CHILD = r"""
import sys, json
import jax
from repro.launch.mesh import make_mesh
from repro.launch.cells import build_msf_cell
from repro.configs.base import ShapeCell
from repro.analysis.hlo_analyzer import analyze
r, c, n, m = map(int, sys.argv[1:5])
mesh = make_mesh((r, c), ("data", "model"))
cell = build_msf_cell(ShapeCell(name="bench", kind="msf", n_nodes=n, n_edges=m), mesh)
co = cell.fn.lower(*cell.abstract_args).compile()
res = analyze(co.as_text())
print(json.dumps(dict(p=r*c, coll=res["collective_bytes"], bytes=res["bytes"])))
"""


def run_rows():
    out = []
    # absolute anchor: single-device iteration time, road-like + rmat
    for nm, g in [("road_300x300", grid_road_graph(300, 300, seed=0)),
                  ("rmat_s14_e8", rmat_graph(14, 8, seed=1))]:
        r = msf(g)
        m = measure(f"fig5_single_device_{nm}", lambda: msf(g))
        out.append(m.with_derived(
            f"iters={int(r.iterations)};"
            f"per_iter_us={m.median / max(int(r.iterations), 1):.0f}"
        ))
    # communication-volume strong scaling (per AS iteration, per device).
    # The children compile for placeholder host devices only; pinning them
    # to the CPU keeps them off the chip this (parent) process holds.
    n, m = 1 << 20, (1 << 20) * 8
    for (rr, cc) in [(1, 1), (2, 2), (4, 4), (8, 8)]:
        env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={rr*cc}")
        res = subprocess.run([sys.executable, "-c", _CHILD,
                              str(rr), str(cc), str(n), str(m)],
                             capture_output=True, text=True, env=env, timeout=560)
        d = json.loads(res.stdout.strip().splitlines()[-1])
        out.append(point(
            f"fig5_commvolume_p{d['p']}", d["coll"], "bytes",
            f"collective_bytes_per_device_per_iter;n={n};m={m}",
        ))
    return out


if __name__ == "__main__":
    emit(run_rows(), sys.argv[1:])

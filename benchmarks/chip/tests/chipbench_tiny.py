"""A copy of the benchmark's registry cut to sizes a CPU test can hold.

The cells, traffic and readers are the real ones; only the sizes change:
the kron graph, batch and served, at 2^10 vertices, queries at 100/s.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
CHECKOUT = CHIP.parents[1]
for p in (str(CHECKOUT / "src"), str(CHIP)):
    if p not in sys.path:
        sys.path.insert(0, p)

from registry import Registry  # noqa: E402

SCALE = 10


def tiny_registry(tmp: Path) -> Registry:
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        cfg = json.loads((CHECKOUT / c["file"]).read_text())
        cfg["scale"] = SCALE
        if cfg["kind"] == "stream":
            cfg["spec"]["batch_capacity"] = 64
        path = Path(tmp) / f"{c['name']}.json"
        path.write_text(json.dumps(cfg))
        c["file"] = str(path)
    reg = Registry(bench)
    real = reg.traffic

    def traffic(name):
        mix = real(name)
        if "queries" in mix:
            mix["queries"]["rate"] = 100
        return mix

    reg.traffic = traffic
    return reg


def run_tiny(reg, cell: str, seed: int = 2**31 + 3, seconds: float = 1.0,
             trace: bool = False) -> dict:
    import time

    import harness

    return harness.run_cell(reg, cell, seed, seconds, trace, time.perf_counter(),
                            require_tpu=False,
                            peaks=harness.load_peaks("TPU v5 lite"))

#!/usr/bin/env python3
"""Find the query rate the served stream sustains: one set-up, then the
read mix at each of a few fixed offered rates.

    python3 benchmarks/chip/sweep.py --workload stream20-read \
        --rates 400,800,1200,1600 --seconds 10 --seed 5

For each rate one JSON line: requests offered and answered, failed,
latency p50/p95/p99 from due time, the p50 of the first and of the last
third of the requests (a growing backlog shows as the last third's
rising), and how late the sender ran. The cell's traffic file fixes a
rate below the highest one sustained; the benchmark's runs never sweep.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import harness  # noqa: E402


def sweep(reg, name: str, rates, seconds: float, seed: int, *, require_tpu=True):
    import numpy as np

    _, _, drv = harness.open_cell(reg, name, seed, require_tpu)
    drv.setup()
    rows = []
    try:
        for rate in rates:
            drv.mix["queries"]["rate"] = rate
            ops = drv.window(seconds)
            lat = np.array([o.t_done - o.t_sched if o.ok and not math.isnan(o.t_done)
                            else np.inf for o in ops])
            third = max(1, len(lat) // 3)
            done = [o.t_done for o in ops if not math.isnan(o.t_done)]
            span = max(done) - min(o.t_sched for o in ops)
            fin = lat[np.isfinite(lat)]
            rows.append({
                "rate": rate, "offered": len(ops),
                "answered_per_s": int(np.isfinite(lat).sum()) / span,
                "failed": int((~np.isfinite(lat)).sum()),
                "p50_ms": float(np.percentile(fin, 50) * 1e3) if len(fin) else None,
                "p95_ms": float(np.percentile(lat, 95) * 1e3),
                "p99_ms": float(np.percentile(lat, 99) * 1e3),
                "first_third_p50_ms": float(np.median(lat[:third]) * 1e3),
                "last_third_p50_ms": float(np.median(lat[-third:]) * 1e3),
                "sender_late_max_ms": float(max(o.t_send - o.t_sched for o in ops) * 1e3),
            })
            print(json.dumps(rows[-1]), flush=True)
    finally:
        drv.close()
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)

    def body(reg):
        sweep(reg, args.workload, [float(r) for r in args.rates.split(",")],
              args.seconds, args.seed)
        return 0

    return harness.main("sweep.py", body)


if __name__ == "__main__":
    sys.exit(main())

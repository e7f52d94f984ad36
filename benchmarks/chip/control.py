#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python3 benchmarks/chip/control.py --workload kron20-coarsen \
        --seeds 11,12,13 --seconds 30

For each seed, in one process: the cell's set-up, a window at the
cell's own load, then every number of the comparison twice -- once for
the program's output (a sound run: the lower reading) and once with the
control in the program's place (the upper reading). The control is the
reference computed in a way that breaks one guarantee the configuration
states: for a batch solve, weight ties broken in a seeded order instead
of by edge id (no lower precision changes this forest: see
``reference``); for the served stream, answers one acknowledged write
behind.
One JSON line per seed, then a summary line: per number, the largest
program reading and the smallest control reading. The benchmark's own
runs never run this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
import harness  # noqa: E402


def readings(reg, name: str, seeds, seconds: float, *, require_tpu=True):
    """(per-seed rows, summary) of the program's and the control's numbers."""
    rows = []
    for seed in seeds:
        _, _, drv = harness.open_cell(reg, name, seed, require_tpu)
        drv.setup()
        ops = drv.window(seconds)
        drv.facts()
        drv.close()
        prog, _ = drv.check()
        ctrl, _ = drv.check(control=True)
        rows.append({"seed": seed, "ops": len(ops),
                     "program": {k: v for k, (v, _) in prog.items()},
                     "control": {k: v for k, (v, _) in ctrl.items()}})
        print(json.dumps(rows[-1]), flush=True)
    summary = {k: {"program_max": max(r["program"][k] for r in rows),
                   "control_min": min(r["control"][k] for r in rows)}
               for k in rows[0]["program"]}
    return rows, summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    def body(reg):
        _, summary = readings(reg, args.workload,
                              [int(s) for s in args.seeds.split(",")], args.seconds)
        print(json.dumps({"workload": args.workload, "summary": summary,
                          "total_s": time.perf_counter() - T_START}), flush=True)
        return 0

    return harness.main("control.py", body)


if __name__ == "__main__":
    sys.exit(main())

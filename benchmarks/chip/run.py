#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload kron20-coarsen --seed 7 \
        --seconds 30 --trace 0

Reads ``BENCHMARK.json`` at the checkout's root, runs the cell on the
machine it is started on, and prints one JSON object as the last line of
standard output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared with the reference beside its limit. The same numbers
are the last lines of standard error.

Exits 2, with no result, when JAX's first device is not a TPU or there
are fewer chips than the cell asks for. JAX's persistent compilation
cache is kept in ``.jax_cache/`` at the checkout's root.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default=None,
                    help="with --trace 1: write the window's reduced trace "
                         "(trace_reduce.load_xplane's lists) to this JSON file")
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    def body(reg):
        result = harness.run_cell(reg, args.workload, args.seed, args.seconds,
                                  bool(args.trace), T_START,
                                  dump_trace=args.dump_trace)
        print(json.dumps(result), flush=True)
        for k, c in result["checks"].items():
            print(f"{k}: {c['value']} (limit {c['limit']})", file=sys.stderr,
                  flush=True)
        return 0

    return harness.main("run.py", body)


if __name__ == "__main__":
    sys.exit(main())

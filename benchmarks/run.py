"""Benchmark driver — one module per paper table/figure. Prints
``name,us_per_call,derived`` CSV rows (benchmarks/bench_*.py each map to a
paper figure; the roofline/§Perf numbers come from launch/dryrun.py);
every row is a structured ``benchmarks.common.Measurement`` underneath.

``--json-dir DIR`` writes one ``bench-rows/v2`` document per module
(``DIR/BENCH_<slug>.json``) — the shapes the regression sentinel
compares. ``--history DIR`` appends each module's document to the
append-only per-(suite, backend, device_count) history store
(``benchmarks/history.py``) — the weekly CI job's trajectory artifact.

``--metrics-summary`` turns ``repro.obs`` metrics mode on for the whole
run and prints the registry snapshot (counters + span-latency summaries)
to stderr after each registered bench, resetting between benches so each
snapshot is per-bench (rows measured under it also carry the snapshot in
their ``metrics`` field)."""
from __future__ import annotations

import json
import os
import re
import sys
import time


def _slug(label: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.-]+", "_", label)


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        bench_coarsen,
        bench_graph_suite,
        bench_multilinear,
        bench_shortcut,
        bench_solve,
        bench_stream,
        bench_strong_scaling,
        bench_weak_scaling,
    )
    from benchmarks.common import document, flag_value

    argv = sys.argv[1:]
    metrics = "--metrics-summary" in argv
    json_dir = flag_value(argv, "--json-dir")
    history_dir = flag_value(argv, "--history")
    if json_dir:
        os.makedirs(json_dir, exist_ok=True)
    if metrics:
        from repro import obs

        obs.enable("metrics")

    mods = [
        ("fig3/4-shortcut", bench_shortcut),
        ("fig5/6-strong-scaling", bench_strong_scaling),
        ("fig7-weak-scaling", bench_weak_scaling),
        ("fig8-multilinear-vs-pairwise", bench_multilinear),
        ("table1-graph-suite", bench_graph_suite),
        ("stream-msf-serving", bench_stream),
        ("coarsen-levels-vs-flat", bench_coarsen),
        ("solve-api-parity", bench_solve),
    ]
    print("name,us_per_call,derived")
    for label, mod in mods:
        t0 = time.time()
        rows = list(mod.run_rows())
        for r in rows:
            print(r, flush=True)
        print(f"# {label} done in {time.time()-t0:.0f}s", file=sys.stderr)
        if json_dir or history_dir:
            doc = document(rows)
            if json_dir:
                path = os.path.join(json_dir, f"BENCH_{_slug(label)}.json")
                with open(path, "w") as f:
                    json.dump(doc, f, indent=1, sort_keys=True)
            if history_dir:
                from benchmarks.history import append

                append(history_dir, _slug(label), doc)
        if metrics:
            from repro import obs

            print(
                f"# metrics[{label}]: "
                + json.dumps(obs.metrics_snapshot(), sort_keys=True),
                file=sys.stderr,
            )
            obs.metrics_reset()


if __name__ == "__main__":
    main()

"""Shared benchmark harness: structured :class:`Measurement` rows.

Every bench module emits ``Measurement`` records through this harness
(timing on the CPU container; the TPU story is the dry-run roofline,
EXPERIMENTS.md §Roofline). A measurement carries the full sample
statistics (median/IQR/min/max over k post-warmup iterations), the
per-bench ``repro.obs`` metrics snapshot when metrics mode is on, and a
``unit`` so non-time rows (speedups, communication volume, iteration
counts) stay structured instead of being smuggled through the time
column. ``write_json`` persists them as a ``bench-rows/v2`` document
with an environment fingerprint — the trajectory points the regression
sentinel (``tools/check_bench_regression.py``) and the append-only
history store (``benchmarks/history.py``) consume. DESIGN.md §11.
"""
from __future__ import annotations

import dataclasses
import json
import os
import platform
import time
from typing import Optional

import jax
import numpy as np

SCHEMA = "bench-rows/v2"


@dataclasses.dataclass(frozen=True)
class Measurement:
    """One bench row. ``median``/``iqr``/``min``/``max`` are in ``unit``
    (microseconds for time rows); ``iters`` is the post-warmup sample
    count (1 for single-shot and non-time point values)."""

    name: str
    median: float
    iqr: float = 0.0  # q75 - q25 of the samples; 0 when iters < 2
    min: float = 0.0
    max: float = 0.0
    iters: int = 1
    warmup: int = 0
    unit: str = "us"  # "us" | "x" | "bytes" | "count"
    derived: str = ""  # free-form key=value;... context (v1 compat)
    metrics: Optional[dict] = None  # obs snapshot; None when obs off

    def __str__(self) -> str:
        # the printed CSV row (run.py header: name,us_per_call,derived)
        return f"{self.name},{self.median:.1f},{self.derived}"

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        if d["metrics"] is None:
            del d["metrics"]
        return d

    def with_derived(self, derived: str) -> "Measurement":
        """Same measurement, new derived string (stats are immutable)."""
        return dataclasses.replace(self, derived=derived)


def _obs_snapshot() -> Optional[dict]:
    from repro import obs

    return obs.metrics_snapshot() if obs.metrics_active() else None


def from_samples(
    name: str,
    samples_s,
    *,
    warmup: int = 0,
    derived: str = "",
    per: float = 1.0,
) -> Measurement:
    """Build a time Measurement from raw wall-clock samples (seconds).

    ``per`` divides every sample (e.g. batches per sample) so the row
    reports per-call microseconds.
    """
    us = np.asarray(samples_s, dtype=np.float64) / max(per, 1e-30) * 1e6
    if us.size == 0:
        raise ValueError(f"{name}: no samples")
    q25, q75 = np.percentile(us, [25, 75]) if us.size > 1 else (us[0], us[0])
    return Measurement(
        name=name,
        median=float(np.median(us)),
        iqr=float(q75 - q25),
        min=float(us.min()),
        max=float(us.max()),
        iters=int(us.size),
        warmup=int(warmup),
        unit="us",
        derived=derived,
        metrics=_obs_snapshot(),
    )


def measure_samples(fn, *args, warmup: int = 1, iters: int = 3) -> list:
    """Raw post-warmup wall-clock samples (seconds) of ``fn(*args)``,
    blocking on device results — the shared timing core of
    :func:`measure` / :func:`timeit`, and the measurement harness the
    SolveSpec autotuner (``repro.solve.tune``, DESIGN.md §12) runs its
    candidates under."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return ts


def measure(
    name: str,
    fn,
    *args,
    warmup: int = 1,
    iters: int = 3,
    derived: str = "",
    per: float = 1.0,
) -> Measurement:
    """Time ``fn(*args)`` (blocking on device results) into a Measurement."""
    ts = measure_samples(fn, *args, warmup=warmup, iters=iters)
    return from_samples(name, ts, warmup=warmup, derived=derived, per=per)


def point(name: str, value: float, unit: str, derived: str = "") -> Measurement:
    """A non-time scalar row (speedup, byte volume, iteration count)."""
    v = float(value)
    return Measurement(
        name=name, median=v, iqr=0.0, min=v, max=v, iters=1, warmup=0,
        unit=unit, derived=derived, metrics=_obs_snapshot(),
    )


def timeit(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall-time (seconds) of jitted fn(*args), post-warmup —
    the scalar core of :func:`measure`, kept for ratio rows that need
    raw seconds (speedup numerators/denominators)."""
    return float(np.median(measure_samples(fn, *args, warmup=warmup,
                                           iters=iters)))


def eid_set(r) -> set:
    """MSF edge-id set of a SolveReport (trimmed) or an engine result
    (IMAX-padded ``msf_eids`` + ``n_msf_edges``)."""
    eids = np.asarray(r.msf_eids)
    return set(eids[: int(r.n_msf_edges)].tolist())


def assert_msf_parity(ref, other, what: str) -> None:
    """The shared weight + eid-set parity gate of the smoke benches —
    one definition so every CI gate enforces the same contract."""
    assert abs(float(ref.weight) - float(other.weight)) <= max(
        1.0, 1e-6 * abs(float(ref.weight))
    ), (what, float(ref.weight), float(other.weight))
    assert eid_set(ref) == eid_set(other), f"{what}: MSF edge set drifted"


def cost_fragment(rep, t_s: float) -> str:
    """Analytic-count and device-rate derived fields from
    ``SolveReport.cost``.

    ``flops``/``hbm_bytes`` are the analytic counts of the plan's
    executable (× iterations when the convergence loop is dynamic). The
    rates — ``gflops_per_s`` and ``roofline_frac`` (analytic bound time
    over the measured time) — are device metrics: they are reported only
    on an accelerator, against the peaks of its ``device_kind``
    (``repro.analysis.roofline.peaks``, which raises for an unknown
    kind), and left out of a CPU run."""
    c = getattr(rep, "cost", None)
    if c is None or t_s <= 0:
        return ""
    mult = max(int(rep.iterations), 1) if c.dynamic_loops else 1
    flops, byts = c.flops * mult, c.bytes * mult
    out = f";flops={flops:.4g};hbm_bytes={byts:.4g}"
    if jax.default_backend() == "cpu":
        return out
    from repro.analysis.roofline import peaks

    hw = peaks()
    bound_s = max(flops / hw["peak_flops_bf16"], byts / hw["hbm_bw"])
    return (
        out + f";gflops_per_s={flops / t_s / 1e9:.3f}"
        f";roofline_frac={bound_s / t_s:.2e}"
    )


def env_fingerprint() -> dict:
    """The comparability key of a bench document: two runs are
    comparable iff backend and device_count agree (the sentinel's
    skip rule); the rest is provenance."""
    return {
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "device_count": jax.device_count(),
        "python": platform.python_version(),
        "machine": platform.machine(),
        "cpu_count": os.cpu_count(),
    }


def document(rows: list) -> dict:
    """The ``bench-rows/v2`` document of a run: environment fingerprint
    + structured rows — no string re-parsing, so bench names are free to
    contain anything (the v1 schema split on commas and corrupted any
    name containing one)."""
    return {
        "schema": SCHEMA,
        "env": env_fingerprint(),
        # duplicated at top level for cheap jq access / v1 familiarity
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "rows": [r.as_dict() for r in rows],
    }


def write_json(path: str, rows: list) -> None:
    """Persist Measurement rows as a BENCH_*.json trajectory point."""
    with open(path, "w") as f:
        json.dump(document(rows), f, indent=1, sort_keys=True)


def emit(rows: list, argv: list[str]) -> None:
    """Print rows; honor ``--json PATH`` when present."""
    print("\n".join(str(r) for r in rows))
    path = flag_value(argv, "--json")
    if path is not None:
        write_json(path, rows)


def flag_value(argv: list[str], flag: str) -> str | None:
    """PATH/value operand of ``flag`` in argv, or None when absent."""
    if flag not in argv:
        return None
    at = argv.index(flag)
    if at + 1 >= len(argv) or argv[at + 1].startswith("--"):
        raise SystemExit(f"{flag} requires an argument")
    return argv[at + 1]


def with_trace(argv: list[str], fn):
    """Run ``fn()`` under ``repro.obs`` trace mode when ``--trace PATH``
    is present, exporting the Chrome-trace/Perfetto JSON to PATH after —
    the shared bench-side surface of DESIGN.md §10.5. Without the flag,
    ``fn()`` runs untouched (obs stays off)."""
    path = flag_value(argv, "--trace")
    if path is None:
        return fn()
    from repro import obs

    obs.enable("trace")
    try:
        return fn()
    finally:
        obs.export_trace(path)
        obs.disable()
        obs.reset()

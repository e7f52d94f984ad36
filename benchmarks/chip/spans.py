"""The program's own host spans in a traced window.

The program writes its ``repro.obs`` spans into the profiler's trace as
TraceMe events with fixed names (``coarsen.level``, ``solve.fetch``,
``serve.flush``, ...); ``trace_reduce.load_xplane`` keeps them among the
host events. Every reader here takes a ``trace_reduce.Trace`` and reads
only those events' names and intervals, clipped to the window. A run of
a program without the spans finds none, and the readers return None.
"""
from __future__ import annotations

import numpy as np

from trace_reduce import _union_length


def intervals(trace, names) -> np.ndarray:
    """[k, 2] nanosecond intervals of the host events named in ``names``,
    clipped to the window; the empty ones dropped."""
    iv = np.array([[h[1], h[1] + h[2]] for h in trace.host if h[0] in names],
                  np.int64).reshape(-1, 2)
    iv = np.clip(iv, trace.t0, trace.t1)
    return iv[iv[:, 1] > iv[:, 0]]


def seconds_per_solve(run, names):
    """Summed seconds of the spans named in ``names`` over the window's
    completed solves; None without such spans or solves."""
    if run.trace is None:
        return None
    iv = intervals(run.trace, names)
    done = sum(o.ok for o in run.of("solve"))
    if not len(iv) or not done:
        return None
    return float((iv[:, 1] - iv[:, 0]).sum()) / 1e9 / done


def union_share_pct(trace, names):
    """Percent of the window covered by the union of the spans named in
    ``names``; None without such spans."""
    if trace is None:
        return None
    iv = intervals(trace, names)
    if not len(iv):
        return None
    return 100.0 * _union_length(iv) / max(1, trace.t1 - trace.t0)


def percentile_ms(trace, names, q: float):
    """The ``q``-th percentile (linear) of the spans' clipped durations,
    in milliseconds; None without such spans."""
    if trace is None:
        return None
    iv = intervals(trace, names)
    if not len(iv):
        return None
    return float(np.percentile(iv[:, 1] - iv[:, 0], q)) / 1e6

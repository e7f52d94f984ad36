"""Spans on the profiler's clock (DESIGN.md §10.1).

Usage::

    from repro import obs

    with obs.span("coarsen.level", level=0):
        out = jitted_fn(...)
        n = int(out.count)          # the span ends where the host waits

    with jax.profiler.trace("/tmp/prof"):   # records every span above
        ...

Every span is a ``jax.profiler.TraceAnnotation`` (a TraceMe). While a
profiler session records, it lands on the ``/host:CPU`` plane of the
session's xplane, on the same clock as the device's operations, nested
under the spans that enclose it on the same thread. Keyword attributes
become the event's stats; they never change its name, which is the fixed
string a reader matches.

Two modes, process-global:

- ``"off"`` (default): with no profiler recording, :func:`span` returns
  the shared :data:`NOOP_SPAN` -- one TraceMe activity check, no
  allocation, no registry access -- so spans stay in hot paths;
- ``"metrics"``: each span's host duration also feeds the
  ``span.<name>`` fixed-bucket histogram of the default
  :mod:`repro.obs.metrics` registry (p50/p95/p99 summaries).

A span never synchronises with the device. Dispatch is asynchronous, so
a span that must cover device work closes after a host read the code
makes anyway (a count, a fetched array); the device trace shows what ran
inside it.
"""
from __future__ import annotations

import threading
import time
from contextlib import contextmanager

from jax.profiler import TraceAnnotation

from repro.obs import metrics as _metrics

MODES = ("off", "metrics")

_lock = threading.Lock()
_mode: str = "off"
_recording = TraceAnnotation.is_enabled  # a profiler session is recording


def _check_mode(mode: str) -> str:
    if mode not in MODES:
        raise ValueError(f"unknown obs mode {mode!r} (expected one of {MODES})")
    return mode


def mode() -> str:
    """Current process-global observability mode."""
    return _mode


def metrics_active() -> bool:
    """True in "metrics" mode."""
    return _mode == "metrics"


def enable(mode: str = "metrics") -> None:
    """Set the process-global mode (until :func:`disable`)."""
    global _mode
    _check_mode(mode)
    with _lock:
        _mode = mode


def disable() -> None:
    enable("off")


@contextmanager
def enabled(mode: str = "metrics"):
    """Scoped enable: raise the mode for the duration, restore after.

    Upgrade-only -- ``enabled("off")`` inside a process in "metrics"
    mode keeps the metrics (a spec-level knob never silences a global
    ``obs.enable``).
    """
    global _mode
    _check_mode(mode)
    if MODES.index(mode) <= MODES.index(_mode):
        yield
        return
    with _lock:
        prev, _mode = _mode, mode
    try:
        yield
    finally:
        with _lock:
            _mode = prev


class _NoopSpan:
    """The span when nothing records and metrics are off: ``span()``
    returns this one instance."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NOOP_SPAN = _NoopSpan()


class _TimedSpan:
    """A metrics-mode span: the host duration goes to ``span.<name>``;
    the TraceMe is there only while a profiler records."""

    __slots__ = ("_name", "_tm", "_t0")

    def __init__(self, name: str, tm):
        self._name = name
        self._tm = tm

    def __enter__(self):
        if self._tm is not None:
            self._tm.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur_s = (time.perf_counter_ns() - self._t0) * 1e-9
        _metrics.DEFAULT_REGISTRY.histogram(f"span.{self._name}").observe(dur_s)
        if self._tm is not None:
            self._tm.__exit__(*exc)
        return False


def span(name: str, **attrs):
    """Context manager over one host region named ``name`` (a fixed
    string); ``attrs`` become the profiler event's stats."""
    recording = _recording()
    if _mode != "metrics":
        return TraceAnnotation(name, **attrs) if recording else NOOP_SPAN
    return _TimedSpan(name, TraceAnnotation(name, **attrs) if recording else None)

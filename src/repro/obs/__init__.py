# Observability substrate (DESIGN.md §10): spans written into the JAX
# profiler's trace (repro.obs.trace) + a process-global metrics registry
# of counters / gauges / fixed-bucket latency histograms
# (repro.obs.metrics). Leaf package — imported by every layer (core,
# coarsen, stream, solve, serve, launch, benchmarks), so it must not
# import any of them.
#
#     from repro import obs
#     with obs.span("solve", n=graph.n):
#         report = run(graph)
#
# A span costs one TraceMe activity check while no profiler records; a
# `jax.profiler.trace(dir)` session records every span on the device
# trace's clock. `obs.enable("metrics")` (or `SolveSpec(obs="metrics")`,
# scoped per solve by the plan layer) also feeds `span.<name>` histograms.
from repro.obs.metrics import (
    DEFAULT_LATENCY_BUCKETS,
    DEFAULT_REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_registry,
)
from repro.obs.trace import (
    MODES,
    NOOP_SPAN,
    disable,
    enable,
    enabled,
    metrics_active,
    mode,
    span,
)

__all__ = [
    # tracing
    "MODES",
    "NOOP_SPAN",
    "disable",
    "enable",
    "enabled",
    "metrics_active",
    "mode",
    "span",
    # metrics
    "DEFAULT_LATENCY_BUCKETS",
    "DEFAULT_REGISTRY",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "default_registry",
    "counter",
    "gauge",
    "histogram",
    "metrics_snapshot",
    "metrics_reset",
]


def counter(name: str) -> Counter:
    """Named counter in the process-global registry."""
    return DEFAULT_REGISTRY.counter(name)


def gauge(name: str) -> Gauge:
    return DEFAULT_REGISTRY.gauge(name)


def histogram(name: str, bounds=DEFAULT_LATENCY_BUCKETS) -> Histogram:
    return DEFAULT_REGISTRY.histogram(name, bounds)


def metrics_snapshot() -> dict:
    """JSON-safe snapshot of the process-global registry."""
    return DEFAULT_REGISTRY.snapshot()


def metrics_reset() -> None:
    DEFAULT_REGISTRY.reset()

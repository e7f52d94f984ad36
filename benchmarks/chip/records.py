"""Records shared by the traffic generators and the metric readers."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Op:
    """One timed operation, on the host's clock (``time.perf_counter``)."""

    kind: str  # solve / connected / component_id
    t_sched: float  # when it was due (open loop) or sent (closed loop)
    t_send: float
    t_done: float  # response host-resident; nan if it never came
    ok: bool  # False: raised, refused or past its deadline
    items: int  # solves 1, queries points
    info: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Run:
    """What a metric reader reads: one run of one cell."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    setup_s: float
    window_s: float  # first op sent -> last op done, host clock
    ops: list
    facts: dict  # sizes and counters the generator reports
    trace: object = None  # trace_reduce.Trace in a --trace 1 run
    peaks: dict = None  # the chip's row of peaks.json

    def of(self, *kinds) -> list:
        return [o for o in self.ops if o.kind in kinds]


def annotate(name: str):
    """A host span in the profiler's trace (free when nothing traces)."""
    import jax

    return jax.profiler.TraceAnnotation(name)

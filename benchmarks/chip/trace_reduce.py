"""From a profiler trace of the measured window to per-layer numbers.

A ``Trace`` holds, in nanoseconds on the profiler's one clock:

- ``device``: per chip, the operations that ran on it (the "XLA Ops"
  line of each ``/device:TPU:<i>`` plane): ``[name, start, duration]``,
  where a TPU op's name is its HLO instruction's text, shapes included;
- ``host``: the host's events ``[name, start, duration, thread]``, among
  them the benchmark's own ``bench.*`` annotations;
- ``window``: the ``bench.window`` annotation's start and end.

Everything is computed inside the window:

- busy time of a chip: the length of the union of its operations'
  intervals, control flow (``while``, ``conditional``, ``call``) left out
  since its span holds its body's gaps; idle share = 1 - busy / window,
  averaged over the chips;
- kernel time: the summed device durations of the events that the
  kernel's entry in ``kernels.json`` assigns to it (patterns on the HLO
  text);
- roofline share: least time / kernel time, the least time being the
  bytes the reduction must move over the chip's peak bandwidth;
- breakdown: the device operations that took most time, and the longest
  idle gaps, each named by the host annotation it fell in.
"""
from __future__ import annotations

import glob
import json
import re
from collections import defaultdict
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
# control flow whose device time is that of the operations inside it
CONTAINER = re.compile(r"^%(while|conditional|call)\b")


def load_xplane(log_dir: str) -> dict:
    """The raw trace a ``jax.profiler`` session wrote under ``log_dir``,
    reduced to plain lists: per chip ``[hlo_text, start, duration]`` of
    its "XLA Ops" line (a TPU op event is named by its HLO instruction's
    text, shapes included), and ``[name, start, duration, thread]`` of
    every host event."""
    from jax.profiler import ProfileData

    files = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, found {files}")
    pd = ProfileData.from_file(files[0])
    device: dict = {}
    host: list = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OP_LINE:
                    device[m.group(1)] = [[e.name, int(e.start_ns), int(e.duration_ns)]
                                          for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [[e.name, int(e.start_ns), int(e.duration_ns), line.name]
                         for e in line.events]
    wins = [(h[1], h[1] + h[2]) for h in host if h[0] == WINDOW]
    if len(wins) != 1:
        raise RuntimeError(f"expected one {WINDOW} annotation, found {len(wins)}")
    return {"window": list(wins[0]), "device": device, "host": host}


def _union_length(iv: np.ndarray) -> int:
    """Total length of the union of intervals ``iv`` [k, 2]."""
    if not len(iv):
        return 0
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    # a new run starts where an interval begins after all earlier ends
    starts_run = np.ones(len(iv), bool)
    starts_run[1:] = iv[1:, 0] > ends[:-1]
    run_id = np.cumsum(starts_run) - 1
    run_lo = iv[starts_run, 0]
    run_hi = np.zeros(run_id[-1] + 1, np.int64)
    np.maximum.at(run_hi, run_id, iv[:, 1])
    return int((run_hi - run_lo).sum())


class Trace:
    def __init__(self, raw: dict):
        self.t0, self.t1 = (int(x) for x in raw["window"])
        self.device = raw["device"]
        self.host = raw["host"]

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    @property
    def n_chips(self) -> int:
        return max(1, len(self.device))

    def _clipped(self, events, lo, hi) -> np.ndarray:
        if not events:
            return np.zeros((0, 2), np.int64)
        iv = np.array([[s, s + d] for _, s, d, *_ in events], np.int64)
        iv[:, 0] = np.clip(iv[:, 0], lo, hi)
        iv[:, 1] = np.clip(iv[:, 1], lo, hi)
        return iv[iv[:, 1] > iv[:, 0]]

    def leaf_ops(self, dev) -> list:
        """A chip's operations without control-flow containers, whose
        span would count the gaps between their body's operations."""
        return [e for e in self.device[dev] if not CONTAINER.match(e[0])]

    def busy_ns(self, lo=None, hi=None) -> float:
        """Device-busy nanoseconds in [lo, hi], averaged over the chips."""
        lo = self.t0 if lo is None else lo
        hi = self.t1 if hi is None else hi
        if not self.device:
            return 0.0
        return sum(_union_length(self._clipped(self.leaf_ops(d), lo, hi))
                   for d in self.device) / self.n_chips

    @property
    def busy_s(self) -> float:
        return self.busy_ns() / 1e9

    def idle_share(self) -> float:
        """1 - busy / window, in [0, 1]."""
        return 1.0 - self.busy_ns() / max(1, self.t1 - self.t0)

    def ops_in_window(self):
        for dev, evs in self.device.items():
            for e in evs:
                if e[1] >= self.t0 and e[1] + e[2] <= self.t1:
                    yield dev, e

    def kernel_events(self, kernel: str, table: dict | None = None) -> list:
        """The window's device events that ``kernels.json`` assigns to
        ``kernel``."""
        table = table or load_kernel_table()
        rules = [re.compile(r) for r in table[kernel]["events"]]
        return [e for _, e in self.ops_in_window()
                if any(r.search(e[0]) for r in rules)]

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (control flow left
        out: its time is its body's), and the longest idle gaps of the
        first chip, each named by the ``bench.*`` annotation it fell in
        and by the innermost host event that covers most of it."""
        by_name: dict = defaultdict(int)
        for _, e in self.ops_in_window():
            if not CONTAINER.match(e[0]):
                by_name[op_label(e[0])] += e[2]
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        gaps = []
        if self.device:
            iv = self._clipped(self.leaf_ops(sorted(self.device)[0]), self.t0, self.t1)
            iv = iv[np.argsort(iv[:, 0], kind="stable")]
            edge = self.t0
            for s, e in iv:
                if s > edge:
                    gaps.append((edge, s))
                edge = max(edge, e)
            if edge < self.t1:
                gaps.append((edge, self.t1))
        gaps.sort(key=lambda g: g[0] - g[1])
        named = []
        for lo, hi in gaps[:top]:
            named.append([self._host_doing(lo, hi), (hi - lo) / 1e9])
        return {"device_ops": [[n, d / 1e9] for n, d in ops], "idle_gaps": named}

    def _host_doing(self, lo: int, hi: int) -> str:
        """``bench.<op>`` around [lo, hi] and the shortest host event that
        covers at least half of it, joined by '/'."""
        mid, need = (lo + hi) // 2, (hi - lo) / 2
        bench, inner = [], []
        for h in self.host:
            s, e = h[1], h[1] + h[2]
            if h[0] == WINDOW or not s <= mid <= e:
                continue
            if h[0].startswith("bench."):
                bench.append((h[2], h[0]))
            elif min(e, hi) - max(s, lo) >= need:
                inner.append((h[2], h[0]))
        parts = [min(bench)[1] if bench else "no_bench_op"]
        if inner:
            parts.append(min(inner)[1][:80])
        return "/".join(parts)


def op_label(hlo_text: str) -> str:
    """A TPU op event's HLO text without layouts, cut to 120 characters."""
    return re.sub(r"\{[^{}]*\}", "", hlo_text)[:120]


def load_kernel_table(path: Path = HERE / "kernels.json") -> dict:
    return json.loads(Path(path).read_text())


_SHAPE = re.compile(r"\b(?:f32|s32|u32|pred|f16|bf16|s8|u8|s64|u64|f64)\[([\d,]*)\]")


def shapes_of(hlo_text: str) -> list:
    """Element counts of the array shapes in one HLO instruction's text,
    in order: the result first, then the operands."""
    out = []
    for dims in _SHAPE.findall(hlo_text or ""):
        out.append(int(np.prod([int(d) for d in dims.split(",") if d])) if dims else 1)
    return out


def segmin_roofline_pct(run, slots=None, segments=None, kernel="segmin"):
    """Percent of the roofline that the window's segment-min reductions
    reach: least time over measured device time. Each event's work is
    (8 E + 4 S) bytes -- a 32-bit key and a 32-bit segment id read per
    slot, a 32-bit minimum written per segment -- with S the result's
    elements and E the largest operand's, from the event's HLO text (a
    TPU op event is named by it);
    where the event has no shapes, ``slots`` and ``segments``. None when
    the window has no such event or its work cannot be told."""
    tr = run.trace
    if tr is None:
        return None
    events = tr.kernel_events(kernel)
    busy = sum(e[2] for e in events)
    if not events or busy <= 0:
        return None
    nbytes = 0
    for e in events:
        shapes = shapes_of(e[0])
        if len(shapes) >= 2:
            s, n = shapes[0], max(shapes[1:])
        elif slots and segments:
            s, n = segments, slots
        else:
            return None
        nbytes += 8 * n + 4 * s
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / (busy / 1e9)

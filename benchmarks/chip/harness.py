"""Run one cell once: set-up, the measured window, the comparison, the
result line.

The order inside a run:

1. find the cell, its configuration and its traffic by name
   (``registry``); refuse to go on without the chips the cell asks for;
2. set-up by the general generator of the configuration's kind,
   ``gen_<kind>.py`` in this directory (``gen_batch``, ``gen_stream``):
   data from the seed, plans, compiles or cache loads,
   warm-up of every shape the window uses. ``setup_s`` runs from the
   process's start to the first timed operation;
3. the window: timed operations for ``seconds``; it closes when the
   operation in flight then completes. With ``trace`` the profiler
   records it (Python tracing off) and the per-layer readers read it;
4. the chip's peak memory is read, the program's state freed, and what
   the window produced is compared with the plain reference.
"""
from __future__ import annotations

import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

from records import Run, annotate

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
CACHE_DIR = CHECKOUT / ".jax_cache"


class NoChip(RuntimeError):
    """The accelerator the cell needs is not there."""


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise NoChip(f"JAX's first device is {d.platform!r} ({d.device_kind}), "
                     f"not a TPU; this benchmark measures only on the chip")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def load_peaks(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


class CompileCounter:
    """Counts JAX's compile and cache-load events while it is open."""

    def __init__(self):
        self.counts: dict = {}
        self.open = False

    def __call__(self, event: str, *args, **kw) -> None:
        if self.open and "compil" in event:
            self.counts[event] = self.counts.get(event, 0) + 1

    def install(self) -> "CompileCounter":
        import jax

        jax.monitoring.register_event_listener(self)
        jax.monitoring.register_event_duration_secs_listener(self)
        return self


def _trace_window(drv, seconds: float, dump=None):
    import jax

    from trace_reduce import Trace, load_xplane

    log_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            with annotate("bench.window"):
                ops = drv.window(seconds)
        finally:
            jax.profiler.stop_trace()
        raw = load_xplane(log_dir)
        if dump:
            Path(dump).write_text(json.dumps(raw))
        return ops, Trace(raw)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def bootstrap():
    """The start-up every script of the benchmark shares: JAX's persistent
    compilation cache in the checkout (the program reads the variable too,
    so both keep one cache), the program on ``sys.path``, and the
    registry of ``BENCHMARK.json``."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    if str(CHECKOUT / "src") not in sys.path:
        sys.path.insert(0, str(CHECKOUT / "src"))
    import jax

    from registry import Registry

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return Registry.load(CHECKOUT / "BENCHMARK.json")


def main(script: str, body) -> int:
    """Run ``body(registry)`` after :func:`bootstrap`; exit 2 with the
    reason, and no result, when the chip the cell needs is not there."""
    reg = bootstrap()
    try:
        return body(reg)
    except NoChip as e:
        print(f"{script}: {e}", file=sys.stderr, flush=True)
        return 2


def open_cell(reg, name: str, seed: int, require_tpu: bool = True):
    """(cell, device, driver) of cell ``name``: its configuration and mix
    found by name, the chips it asks for checked, and its kind's general
    generator (``gen_<kind>.py``) made ready for ``seed``."""
    cell = reg.cell(name)
    cfg = reg.config(cell["config"])
    mix = reg.traffic(cell["traffic"])
    if mix.get("kind", cfg["kind"]) != cfg["kind"]:
        raise ValueError(f"traffic {cell['traffic']!r} is for {mix['kind']!r}, "
                         f"configuration {cell['config']!r} is {cfg['kind']!r}")
    device = device_info(int(cell["chips"]), require_tpu)
    return cell, device, reg.generator(cfg["kind"]).Driver(cfg, mix, seed)


def run_cell(reg, name: str, seed: int, seconds: float, trace: bool,
             t_start: float, *, require_tpu: bool = True, peaks=None,
             dump_trace=None) -> dict:
    """One run of cell ``name``; returns the result line's object.
    ``dump_trace``: a path to write the traced window's reduced trace to."""
    import jax

    cell, device, drv = open_cell(reg, name, seed, require_tpu)
    cfg, mix = drv.cfg, drv.mix
    if peaks is None:
        peaks = load_peaks(device["kind"])
    compiles = CompileCounter().install()
    compiles.open = True
    facts = drv.setup()
    setup_s = time.perf_counter() - t_start
    print(json.dumps({"cell": name, "seed": seed, "setup_s": setup_s,
                      "compile_events_in_setup": compiles.counts,
                      "at_window_start": facts}), flush=True)
    compiles.counts = {}
    if trace:
        ops, tr = _trace_window(drv, seconds, dump_trace)
    else:
        with annotate("bench.window"):
            ops = drv.window(seconds)
        tr = None
    compiles.open = False
    facts.update(drv.facts())
    stats = jax.devices()[0].memory_stats() or {}
    drv.close()
    t = time.perf_counter()
    checks, wrong = drv.check()
    check_s = time.perf_counter() - t
    done = [o.t_done for o in ops if not math.isnan(o.t_done)]
    window_s = (max(done) - min(o.t_sched for o in ops)) if done else float(seconds)
    run = Run(cell=cell, config=cfg, traffic=mix, seed=seed, setup_s=setup_s,
              window_s=window_s, ops=ops, facts=facts, trace=tr, peaks=peaks)
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in reg.metrics(section, name):
        value = reg.reader(section, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
    result = {
        "correct": all(v <= lim for v, lim in checks.values()),
        "attempted": len(ops),
        "failed": sum(not o.ok for o in ops) + int(wrong),
        "metrics": metrics,
        "device": device,
    }
    if tr is not None:
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        result["breakdown"] = tr.breakdown()
    kinds = sorted({o.kind for o in ops})
    op_s = {k: sorted(o.t_done - o.t_send for o in ops if o.kind == k) for k in kinds}
    print(json.dumps({"cell": name, "window_s": window_s, "check_s": check_s,
                      "compile_events_in_window": compiles.counts,
                      "op_s_median": {k: v[len(v) // 2] for k, v in op_s.items()},
                      "facts": facts}, default=str), flush=True)
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return result

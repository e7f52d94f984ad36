"""Roofline terms from the compiled dry-run artifact (no hardware needed).

  compute    = HLO_FLOPs_per_device / peak_FLOP/s            (seconds)
  memory     = HLO_bytes_per_device / HBM_bw                 (seconds)
  collective = collective_bytes_per_device / link_bw          (seconds)

Two sources are combined:

- ``repro.analysis.hlo_analyzer`` — parses the compiled (post-SPMD,
  per-device) HLO with *while-loop trip-count multipliers*. XLA's built-in
  ``cost_analysis()`` counts loop bodies once, so a 61-layer scanned
  transformer would be 61× under-reported; the analyzer fixes that and is
  the primary source for all three terms (validated against hand counts).
- ``compiled.cost_analysis()`` — kept as the ``xla_*`` cross-check fields
  (no loop multiplicity, but an independent elementwise-FLOP count to
  sanity-check the analyzer's ``ew_flops`` against).

Dynamic-trip-count loops (the MSF engine's convergence loop) are flagged:
their numbers are per loop iteration — the paper's own reporting unit
(time *per iteration*, Fig 3/4).

Hardware constants come from :data:`PEAKS`, keyed by the
``device_kind`` JAX reports. A device that is not in the table is an
error, never a default.
"""
from __future__ import annotations

from typing import Dict

from repro.analysis.hlo_analyzer import analyze

#: Published per-chip peaks by ``jax.devices()[0].device_kind``.
#: "TPU v5 lite" (TPU v5e): Google Cloud documentation, "TPU v5e" —
#: 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s interchip
#: interconnect (4 links of ~50 GB/s).
PEAKS = {
    "TPU v5 lite": dict(
        peak_flops_bf16=197e12,  # per chip
        hbm_bw=819e9,  # B/s
        hbm_bytes=16e9,
        ici_bw=50e9,  # B/s per link
    ),
}

#: The chip the analytic projections (the autotuner's pruning model,
#: the dry-run roofline) are made for when no such chip is attached.
TARGET_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str | None = None) -> Dict:
    """Peak rates of ``device_kind`` (default: the first JAX device's).
    Raises ``KeyError`` for a kind with no published entry — a CPU run
    has no device peaks to compare against."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)})"
        ) from None


def roofline(compiled, *, n_devices: int, model_flops: float | None = None,
             hw: Dict | None = None) -> Dict:
    """Roofline terms of ``compiled`` against ``hw`` (default: the peaks
    of the device this process runs on, via :func:`peaks`)."""
    hw = peaks() if hw is None else hw
    ca = compiled.cost_analysis() or {}
    res = analyze(compiled.as_text())
    flops = max(float(res["flops"]), float(ca.get("flops", 0.0)))
    bytes_acc = max(float(res["bytes"]), float(ca.get("bytes accessed", 0.0)))
    coll_total = float(res["collective_bytes"])

    t_compute = flops / hw["peak_flops_bf16"]
    t_memory = bytes_acc / hw["hbm_bw"]
    t_collective = coll_total / hw["ici_bw"]
    terms = dict(compute=t_compute, memory=t_memory, collective=t_collective)
    dominant = max(terms, key=terms.get)

    mem = compiled.memory_analysis()
    out = dict(
        flops_per_device=flops,
        bytes_per_device=bytes_acc,
        collective_bytes_per_device=coll_total,
        t_compute_s=t_compute,
        t_memory_s=t_memory,
        t_collective_s=t_collective,
        dominant=dominant,
        bound_time_s=max(terms.values()),
        dynamic_loops=int(res["dynamic_loops"]),
        xla_flops_per_device=float(ca.get("flops", 0.0)),
        xla_bytes_per_device=float(ca.get("bytes accessed", 0.0)),
        arg_bytes_per_device=int(mem.argument_size_in_bytes),
        temp_bytes_per_device=int(mem.temp_size_in_bytes),
        output_bytes_per_device=int(mem.output_size_in_bytes),
    )
    if model_flops:
        out["model_flops"] = float(model_flops)
        hlo_global = flops * n_devices
        out["useful_flops_ratio"] = float(model_flops) / max(hlo_global, 1.0)
        # roofline fraction: useful-work rate vs peak, if the step ran at
        # its binding roofline term
        out["roofline_fraction"] = (
            float(model_flops) / n_devices / hw["peak_flops_bf16"]
        ) / max(out["bound_time_s"], 1e-30)
    return out


# re-exported for tests
from repro.analysis.hlo_analyzer import HloCost  # noqa: E402,F401


def collective_bytes(hlo_text: str) -> float:
    return analyze(hlo_text)["collective_bytes"]

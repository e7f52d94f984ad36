import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST precede any jax import (jax locks the device
count at first init); 512 placeholder host devices let ``jax.make_mesh``
build the production meshes. Run:

  PYTHONPATH=src python -m repro.launch.dryrun                    # everything
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --mesh single --msf
  PYTHONPATH=src python -m repro.launch.dryrun --variant triangle_skip=1

Per cell: ``.lower().compile()`` must succeed; prints
``memory_analysis()`` (fits?) and ``cost_analysis()`` (FLOPs/bytes), plus
the parsed collective bytes; writes a JSON artifact per cell under
``experiments/dryrun/`` for EXPERIMENTS.md §Dry-run and §Roofline.
"""
import argparse
import json
import time
import traceback


def parse_variant(s):
    out = {}
    if not s:
        return out
    for kv in s.split(","):
        k, v = kv.split("=")
        out[k] = int(v) if v.lstrip("-").isdigit() else v
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--msf", action="store_true", help="also run MSF engine cells")
    ap.add_argument("--msf-only", action="store_true")
    ap.add_argument("--variant", default="", help="k=v,... perf-variant knobs")
    ap.add_argument("--outdir", default="experiments/dryrun")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    import jax
    from repro.analysis.roofline import TARGET_DEVICE_KIND, peaks, roofline
    from repro.configs import registry
    from repro.configs.base import MSF_SHAPES
    from repro.launch.cells import build_cell, build_msf_cell, lower_cell
    from repro.launch.mesh import make_production_mesh

    os.makedirs(args.outdir, exist_ok=True)
    meshes = {"single": make_production_mesh(multi_pod=False)}
    if args.mesh in ("multi", "both"):
        meshes["multi"] = make_production_mesh(multi_pod=True)
    if args.mesh == "multi":
        meshes.pop("single")

    cells = []
    if not args.msf_only:
        for arch, shape in registry.all_cells():
            if args.arch and arch != args.arch:
                continue
            if args.shape and shape != args.shape:
                continue
            cells.append(("arch", arch, shape))
    if args.msf or args.msf_only:
        for s in MSF_SHAPES:
            if args.shape and s.name != args.shape:
                continue
            cells.append(("msf", "msf-engine", s.name))

    variant = parse_variant(args.variant)
    n_ok = n_fail = 0
    for mesh_name, mesh in meshes.items():
        n_dev = mesh.size
        for kind, arch, shape in cells:
            cell_id = f"{arch}:{shape}@{mesh_name}" + (f"+{args.tag}" if args.tag else "")
            t0 = time.time()
            try:
                if kind == "msf":
                    scfg = next(s for s in MSF_SHAPES if s.name == shape)
                    cell = build_msf_cell(scfg, mesh, **{
                        k: v for k, v in variant.items() if k in ("shortcut", "capacity", "pack")
                    })
                else:
                    cell = build_cell(arch, shape, mesh, variant)
                lowered = lower_cell(cell)
                compiled = lowered.compile()
                mem = compiled.memory_analysis()
                # an analytic projection onto the target chip, not a
                # measurement: the cells compile for placeholder devices
                rf = roofline(
                    compiled, n_devices=n_dev,
                    model_flops=cell.meta.get("model_flops"),
                    hw=peaks(TARGET_DEVICE_KIND),
                )
                rec = dict(
                    cell=cell_id, arch=arch, shape=shape, mesh=mesh_name,
                    n_devices=n_dev, ok=True,
                    compile_s=round(time.time() - t0, 1),
                    meta={k: v for k, v in cell.meta.items() if k != "family"},
                    family=cell.meta.get("family"),
                    **rf,
                )
                print(
                    f"[OK ] {cell_id:48s} {rec['compile_s']:6.1f}s "
                    f"flops/dev={rf['flops_per_device']:.3e} "
                    f"bytes/dev={rf['bytes_per_device']:.3e} "
                    f"coll/dev={rf['collective_bytes_per_device']:.3e} "
                    f"dom={rf['dominant']} "
                    f"args={mem.argument_size_in_bytes/2**30:.2f}GiB "
                    f"temp={mem.temp_size_in_bytes/2**30:.2f}GiB"
                )
                n_ok += 1
            except Exception as e:  # noqa: BLE001 — record and continue
                rec = dict(
                    cell=cell_id, arch=arch, shape=shape, mesh=mesh_name,
                    n_devices=n_dev, ok=False, error=f"{type(e).__name__}: {e}",
                    compile_s=round(time.time() - t0, 1),
                )
                print(f"[FAIL] {cell_id}: {type(e).__name__}: {str(e)[:300]}")
                traceback.print_exc(limit=4)
                n_fail += 1
            fname = cell_id.replace(":", "_").replace("@", "_").replace("+", "_")
            with open(os.path.join(args.outdir, fname + ".json"), "w") as f:
                json.dump(rec, f, indent=1, default=str)
    print(f"\ndry-run: {n_ok} ok, {n_fail} failed")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())

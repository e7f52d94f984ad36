#!/usr/bin/env python3
"""Prove that the MSF system's main path runs on a TPU chip.

    python chip_smoke.py                # one chip: batch solves + served stream
    python chip_smoke.py --four-chips   # 2x2 mesh: the distributed solve only

Everything runs in this one process, through the entry points users
call (``repro.solve.plan``, ``repro.serve``), and every result is checked
against an independent reference (scipy's minimum spanning tree and
connected components). Each phase prints one JSON line of facts: sizes,
resolved backends, compile and run seconds, result against reference.

One chip:

1. **device** — the first JAX device must be a TPU; there is no CPU
   fallback.
2. **batch** — Graph500 R-MAT (A/B/C = .57/.19/.19, edge factor 8) at
   the ``rmat_s23_e8`` shape with integer weights 1..255 from ``--seed``,
   solved by a flat plan and a fused coarsen plan. The dominant
   executable of each plan is compiled ahead of time first: its
   ``memory_analysis()`` must fit the chip (else the scale steps down,
   and the cut is printed) and its text shows whether the Pallas kernels
   are in it (``tpu_custom_call``). 2^23 vertices carry more than 2^24
   directed edge slots, which turns the pack32 path off, so the phase
   repeats at the largest scale whose slots keep it on: that run must
   compile the flat and the sorted segment-min kernels and dedupe on the
   device.
3. **serve** — the serving tier as ``launch/serve_graph.py --serve``
   builds it (``MSFServer`` over a ``SolveSpec(mode="stream")`` plan) at
   n = 2^20, driven over loopback by ``ServeClient``: insert batches,
   a few hundred ``connected``/``component_id`` queries, one delete
   batch. No response may carry an error, the answers must match the
   reference partition, the final forest weight must equal the
   reference over the surviving edges, and the union solve, lowered
   with the engine's own statics, must hold the Pallas flat kernel.

Four chips (``--four-chips``): a 2x2 mesh of ``jax.devices()``, the graph
of phase 2 partitioned with ``partition_edges_2d``, solved by the dist
plan with flat rounds and with in-mesh coarsen levels; each device's
peak memory is printed.

The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
and appears only when every phase passed; any failure exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

EDGE_FACTOR = 8
BATCH_SCALE = 23  # MSF_SHAPES "rmat_s23_e8"
STREAM_SCALE = 20
SERVE_BATCH = 512  # serve_graph --serve's --batch-capacity default
WARM_BATCHES = 8
QUERY_REQUESTS = 300
QUERY_POINTS = 64
DELETE_EDGES = 512


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}, default=_jsonable), flush=True)


def _jsonable(x):
    if isinstance(x, (np.integer, np.bool_)):
        return x.item()
    if isinstance(x, np.floating):
        return float(x)
    return str(x)


def _backend_name(fn) -> str:
    """Readable name of a resolved packed segment-min callable."""
    if fn is None:
        return "none"
    return {"_jnp": "xla", "_pallas": "pallas-flat", "_sorted": "pallas-sorted"}.get(
        fn.__name__, fn.__name__
    )


# ---------------------------------------------------------------------------
# references
# ---------------------------------------------------------------------------

def reference(g):
    """scipy MSF weight and component count of ``g``."""
    from repro.graphs.structures import nx_free_msf_weight, nx_free_n_components

    t = time.perf_counter()
    weight = nx_free_msf_weight(g)
    ncc = nx_free_n_components(g)
    return weight, ncc, time.perf_counter() - t


def check_forest(g, rep, ref_weight: float, ref_ncc: int, what: str) -> dict:
    """The reported MSF against the reference: its edges form a spanning
    forest of ``g`` (n − components edges, the reference's components)
    whose exact weight is the reference weight."""
    import scipy.sparse as sp
    import scipy.sparse.csgraph as csg

    src, dst = np.asarray(g.src), np.asarray(g.dst)
    w, eid = np.asarray(g.w), np.asarray(g.eid)
    m = int(eid.max()) + 1
    lo = np.empty(m, np.int64)
    hi = np.empty(m, np.int64)
    wt = np.empty(m, np.float64)
    lo[eid], hi[eid], wt[eid] = src, dst, w
    f = np.asarray(rep.msf_eids)[: int(rep.n_msf_edges)]
    check(len(np.unique(f)) == len(f), f"{what}: repeated MSF edge ids")
    exact = float(wt[f].sum())
    forest = sp.coo_matrix((np.ones(len(f)), (lo[f], hi[f])), shape=(g.n, g.n))
    ncc_forest = int(csg.connected_components(forest, directed=False)[0])
    facts = dict(
        weight=float(rep.weight), exact_edge_weight=exact,
        ref_weight=ref_weight, n_components=int(rep.n_components),
        ref_components=ref_ncc, msf_edges=len(f),
    )
    check(exact == ref_weight, f"{what}: MSF weight {exact} != reference {ref_weight}")
    # the reported scalar is a float32 accumulation
    check(abs(float(rep.weight) - ref_weight) <= max(1.0, 1e-5 * ref_weight),
          f"{what}: reported weight {rep.weight} != reference {ref_weight}")
    check(int(rep.n_components) == ref_ncc,
          f"{what}: {rep.n_components} components != reference {ref_ncc}")
    check(len(f) == g.n - ref_ncc and ncc_forest == ref_ncc,
          f"{what}: {len(f)} edges in {ncc_forest} components is not a "
          f"spanning forest")
    return facts


def make_graph(scale: int, seed: int):
    from repro.graphs.generators import rmat_graph
    from repro.solve.spec import weights_packable

    t = time.perf_counter()
    g = rmat_graph(scale, EDGE_FACTOR, seed=seed)
    check(weights_packable(g.w), "R-MAT weights are not integers in 1..255")
    return g, time.perf_counter() - t


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(want_count: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SmokeFailure(
            f"no TPU: JAX's first device is {d.platform!r} ({d.device_kind}); "
            f"this smoke needs the chip and has no CPU fallback"
        )
    check(len(devs) >= want_count,
          f"{want_count} TPU devices needed, JAX sees {len(devs)}")
    from repro.compile_cache import enable_compile_cache

    info = dict(platform=d.platform, kind=d.device_kind, count=len(devs))
    log("device", **info, jax=jax.__version__,
        hbm_bytes=d.memory_stats().get("bytes_limit"),
        compile_cache=enable_compile_cache())
    return info


def _solve_plan(g, spec, mode: str, ref, hbm_bytes: int):
    """AOT-compile the plan's dominant executable, then solve twice
    (first call, steady call) and check against the reference."""
    import jax

    from repro.solve import plan
    from repro.solve.cost import lower_plan
    from repro.solve.spec import resolve_level_segmins

    rs = spec.resolve(g)
    t = time.perf_counter()
    lowered, analyzed = lower_plan(mode, g, rs)
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    facts = dict(
        mode=mode, pack=bool(rs.pack), segmin=_backend_name(rs.segmin_flat),
        dedupe=rs.dedupe, analyzed=analyzed, aot_compile_s=compile_s,
        aot_bytes=int(need),
        mosaic_kernels=compiled.as_text().count("tpu_custom_call"),
    )
    if rs.coarsen is not None:
        hook, dedupe_fn = resolve_level_segmins(rs.coarsen.segmin, bool(rs.pack))
        facts.update(level_hook_segmin=_backend_name(hook),
                     level_dedupe_segmin=_backend_name(dedupe_fn))
    if need > hbm_bytes:
        return facts, False
    p = plan(g, spec)
    # the solve runs what was lowered above only if it resolved alike
    check(p.resolved == rs, f"{mode} plan resolved {p.resolved}, the AOT "
          f"lowering used {rs}")
    t = time.perf_counter()
    rep = p.solve()
    jax.block_until_ready(rep.parent)
    facts["first_solve_s"] = time.perf_counter() - t
    t = time.perf_counter()
    rep = p.solve()
    jax.block_until_ready(rep.parent)
    facts["solve_s"] = time.perf_counter() - t
    facts["iterations"] = int(rep.iterations)
    facts["levels"] = len(rep.levels)
    facts.update(check_forest(g, rep, ref[0], ref[1], f"{mode} plan"))
    return facts, True


def phase_batch(seed: int, hbm_bytes: int) -> None:
    from repro.core.semiring import PACK_IDX_BITS
    from repro.solve import SolveSpec

    specs = (("flat", SolveSpec(mode="flat")),
             ("coarsen", SolveSpec(mode="coarsen", fused=True)))
    scale, packed = BATCH_SCALE, False
    while True:
        g, gen_s = make_graph(scale, seed)
        ref_w, ref_ncc, ref_s = reference(g)
        ok, results = True, []
        for mode, spec in specs:
            facts, fits = _solve_plan(g, spec, mode, (ref_w, ref_ncc), hbm_bytes)
            results.append(facts)
            ok = ok and fits
            if not fits:
                break
        if not ok:
            log("batch.cut", scale=scale, reason="AOT memory_analysis exceeds "
                "the chip's memory", facts=results[-1], next_scale=scale - 1)
            check(scale > 16, "no R-MAT scale fits the chip")
            scale -= 1
            continue
        for facts in results:
            log(f"batch.{facts['mode']}", scale=scale, n=g.n,
                directed_edges=int(g.src.shape[0]), seed=seed,
                generate_s=gen_s, reference_s=ref_s, **facts)
        packed = packed or all(f["pack"] for f in results)
        if packed:
            break
        # pack32 indexes directed slots (flat) and pow2-padded directed
        # slots (coarsen levels) in 24 bits: 2·2^(s + log2 ef) < 2^24
        # bounds the scale that keeps it on.
        pack_scale = PACK_IDX_BITS - 2 - (EDGE_FACTOR.bit_length() - 1)
        log("batch.cut", scale=scale, next_scale=pack_scale,
            reason="pack32 is off above 2^24 directed edge slots; repeat at "
                   "the largest scale that keeps it on, so that the Pallas "
                   "kernels run")
        check(scale > pack_scale, "pack32 is off at its own bound")
        scale = pack_scale
    last = results
    check(all(f["pack"] for f in last), "pack32 did not resolve on")
    flat, coarsen = last
    check(flat["segmin"] == "pallas-flat" and flat["mosaic_kernels"] > 0,
          f"the flat plan did not compile the Pallas flat kernel: {flat}")
    check(coarsen["dedupe"] == "device",
          f"coarsen dedupe resolved to {coarsen['dedupe']!r}, not the device")
    check(coarsen["level_dedupe_segmin"] == "pallas-sorted"
          and coarsen["mosaic_kernels"] > 0,
          f"the coarsen level did not compile the Pallas sorted kernel: {coarsen}")


def phase_serve(seed: int) -> None:
    from repro import serve
    from repro.graphs.structures import from_edges, nx_free_msf_weight
    from repro.launch.serve_graph import edge_stream
    from repro.solve import SolveSpec, plan

    import scipy.sparse as sp
    import scipy.sparse.csgraph as csg

    n = 1 << STREAM_SCALE
    lo, hi, w = edge_stream(STREAM_SCALE, EDGE_FACTOR, seed)
    m = WARM_BATCHES * SERVE_BATCH
    lo, hi, w = lo[:m], hi[:m], w[:m]
    stream = plan(n, SolveSpec(mode="stream", batch_capacity=SERVE_BATCH))
    cfg = serve.ServeConfig(deadline_ms=60_000)
    handle = serve.start_in_thread(stream, cfg)
    responses = []
    try:
        with serve.ServeClient(handle.address, timeout=600.0) as c:
            insert_s = []
            for k in range(WARM_BATCHES):
                sl = slice(k * SERVE_BATCH, (k + 1) * SERVE_BATCH)
                t = time.perf_counter()
                responses.append(c.insert(lo[sl], hi[sl], w[sl]))
                insert_s.append(time.perf_counter() - t)
            # reference partition of everything inserted so far
            a = sp.coo_matrix((np.ones(m), (lo, hi)), shape=(n, n))
            labels = csg.connected_components(a, directed=False)[1]
            rng = np.random.default_rng(seed)
            touched = np.concatenate([lo, hi])
            qu = rng.choice(touched, (QUERY_REQUESTS, QUERY_POINTS))
            qv = rng.choice(touched, (QUERY_REQUESTS, QUERY_POINTS))
            # pipelined in waves that stay inside the admission queue
            wave = cfg.queue_cap // QUERY_POINTS // 2
            answers = []
            t = time.perf_counter()
            for at in range(0, QUERY_REQUESTS, wave):
                futs = [
                    c.submit("connected", u=[int(x) for x in qu[i]],
                             v=[int(x) for x in qv[i]]) if i % 2 == 0
                    else c.submit("component_id", u=[int(x) for x in qu[i]])
                    for i in range(at, min(at + wave, QUERY_REQUESTS))
                ]
                answers += [f.result(timeout=600.0) for f in futs]
            query_s = time.perf_counter() - t
            responses += answers
            comp_of = {}
            for i, r in enumerate(answers):
                if not r.get("ok"):
                    continue
                if i % 2 == 0:
                    want = labels[qu[i]] == labels[qv[i]]
                    check(list(want) == r["result"]["connected"],
                          f"connected answers differ from the reference (request {i})")
                else:
                    for x, comp in zip(qu[i], r["result"]["component"]):
                        comp_of.setdefault(comp, set()).add(labels[x])
            check(all(len(s) == 1 for s in comp_of.values())
                  and len(comp_of) == len({next(iter(s)) for s in comp_of.values()}),
                  "component_id answers do not match the reference partition")
            kill = rng.choice(m, DELETE_EDGES, replace=False)
            t = time.perf_counter()
            responses.append(c.delete(lo[kill], hi[kill]))
            delete_s = time.perf_counter() - t
            status = c.status()
            responses.append(status)
    finally:
        handle.drain()
    errors = [r for r in responses if not r.get("ok")]
    check(not errors, f"{len(errors)} responses carried an error: {errors[:3]}")
    keep = np.ones(m, bool)
    keep[kill] = False
    ref = nx_free_msf_weight(
        from_edges(lo[keep], hi[keep], w[keep].astype(np.float64), n)
    )
    got = status["result"]["weight"]
    lowered, statics = stream.engine.lower_union()
    union = dict(pack=statics["pack"], segmin=_backend_name(statics["segmin"]),
                 mosaic_kernels=lowered.compile().as_text().count("tpu_custom_call"))
    log("serve", n=n, seed=seed, batch_capacity=SERVE_BATCH, **union,
        inserted=m, deleted=DELETE_EDGES, requests=len(responses),
        errors=len(errors), first_insert_s=insert_s[0],
        insert_s_median=float(np.median(insert_s[1:])),
        queries=QUERY_REQUESTS * QUERY_POINTS, query_s=query_s,
        delete_s=delete_s, snapshot_version=status["snapshot_version"],
        stale=status["stale"], recompiles=stream.engine.recompiles,
        forest_weight=got, ref_weight=ref)
    check(not status["stale"], "the published snapshot is stale")
    check(got == ref, f"served forest weight {got} != reference {ref}")
    check(union["pack"] and union["segmin"] == "pallas-flat"
          and union["mosaic_kernels"] > 0,
          f"the stream union solve did not compile the Pallas flat kernel: {union}")


def phase_four_chips(seed: int) -> None:
    import jax

    from repro.coarsen.config import CoarsenConfig
    from repro.compat import make_mesh
    from repro.graphs.partition import partition_edges_2d
    from repro.solve import SolveSpec, plan

    scale = BATCH_SCALE
    mesh = make_mesh((2, 2), ("data", "model"))
    g, gen_s = make_graph(scale, seed)
    ref_w, ref_ncc, ref_s = reference(g)
    t = time.perf_counter()
    part = partition_edges_2d(g, 2, 2)
    part_s = time.perf_counter() - t
    for name, spec in (("flat", SolveSpec(mode="dist")),
                       ("coarsen", SolveSpec(mode="dist", coarsen=CoarsenConfig()))):
        p = plan(part, spec, mesh=mesh)
        # one solve each, compile included: a second, steady solve would
        # cost four chips another minute and prove nothing more
        t = time.perf_counter()
        rep = p.solve()
        jax.block_until_ready(rep.parent)
        solve_s = time.perf_counter() - t
        facts = check_forest(g, rep, ref_w, ref_ncc, f"dist {name} plan")
        log(f"dist.{name}", scale=scale, n=g.n,
            directed_edges=int(g.src.shape[0]), mesh="2x2",
            block_edges=part.e_max, pack=bool(p.resolved.pack),
            dedupe=p.resolved.dedupe, generate_s=gen_s, reference_s=ref_s,
            partition_s=part_s, first_solve_s=solve_s,
            iterations=int(rep.iterations), levels=len(rep.levels), **facts)
        log("dist.memory", after=name, peak_bytes_in_use={
            str(d.id): d.memory_stats()["peak_bytes_in_use"]
            for d in jax.devices()
        })


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-mesh distributed solve")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    try:
        device = phase_device(4 if args.four_chips else 1)
        if args.four_chips:
            check(device["count"] == 4, f"--four-chips needs exactly 4 chips, "
                  f"JAX sees {device['count']}")
            phase_four_chips(args.seed)
        else:
            import jax

            hbm = jax.devices()[0].memory_stats()["bytes_limit"]
            phase_batch(args.seed, hbm)
            phase_serve(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    log("done", total_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

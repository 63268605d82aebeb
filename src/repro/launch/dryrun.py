"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape) cell and both production meshes
(16x16 single-pod, 2x16x16 multi-pod), ``lower().compile()`` the step
function with full-size ShapeDtypeStruct inputs + NamedShardings, print
memory/cost analysis, and persist roofline terms to JSON.

No arrays are ever allocated: params/optimizer/caches/batches are all SDS.
The 512 devices are host (CPU) devices: ``main()`` sets XLA_FLAGS before
JAX starts a backend; callers that import ``run_cell`` set it themselves.
"""

import argparse
import json
import os
import time
import traceback

import jax

from repro.configs.base import SHAPES, get_arch, shapes_for
from repro.configs import archs  # noqa: F401
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import (analytic_bytes, parse_collectives,
                                   roofline_terms)
from repro.launch.specs import make_cell, model_flops


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str, overrides=None, tag: str = "") -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    t0 = time.time()
    cell = make_cell(arch, shape_name, mesh, overrides=overrides)
    rec = {
        "arch": arch, "shape": shape_name, "kind": cell.kind,
        "mesh": list(mesh.shape.values()), "chips": chips,
        "multi_pod": multi_pod, "tag": tag, "ok": False,
    }
    try:
        with jax.set_mesh(mesh):
            jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                             donate_argnums=cell.donate)
            lowered = jitted.lower(*cell.args)
            compiled = lowered.compile()
        try:
            mem = compiled.memory_analysis()
            rec["memory"] = {
                k: int(getattr(mem, k)) for k in (
                    "argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "generated_code_size_in_bytes")
                if hasattr(mem, k)}
            print(f"[{arch}/{shape_name}] memory_analysis:", rec["memory"])
        except Exception as e:                           # CPU backend limits
            rec["memory"] = {"error": str(e)}
        cost = compiled.cost_analysis()
        flops = float(cost.get("flops", 0.0))
        nbytes = float(cost.get("bytes accessed", 0.0))
        rec["cost"] = {"flops": flops, "bytes_accessed": nbytes}
        print(f"[{arch}/{shape_name}] cost_analysis: flops={flops:.3e} "
              f"bytes={nbytes:.3e}")

        hlo = compiled.as_text()
        colls = parse_collectives(hlo)
        coll_bytes = sum(v["bytes"] for v in colls.values())
        rec["collectives"] = colls
        rec["roofline"] = roofline_terms(
            flops_per_device=flops, bytes_per_device=nbytes,
            coll_bytes_per_device=coll_bytes, chips=chips,
            model_flops=model_flops(cfg, shape),
            analytic_bytes_per_device=analytic_bytes(cfg, shape, chips))
        rec["compile_s"] = round(time.time() - t0, 1)
        rec["ok"] = True
    except Exception:
        rec["error"] = traceback.format_exc()[-2000:]
        rec["compile_s"] = round(time.time() - t0, 1)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        pod = "multi" if multi_pod else "single"
        suffix = f"_{tag}" if tag else ""
        path = os.path.join(out_dir,
                            f"{arch}_{shape_name}_{pod}{suffix}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    status = "OK" if rec["ok"] else "FAIL"
    print(f"[{status}] {arch} x {shape_name} x "
          f"{'2x16x16' if multi_pod else '16x16'} "
          f"({rec['compile_s']}s)", flush=True)
    return rec


def _lower_stats(arch: str, shape_name: str, multi_pod: bool, depth: int,
                 extra_overrides=None) -> dict:
    """Lower+compile at reduced depth (static_unroll), return raw stats."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_arch(arch)
    plen = len(cfg.layer_period or "A")
    assert depth % plen == 0
    ov = {"n_layers": depth, "static_unroll": True}
    if cfg.encoder_layers:
        ov["encoder_layers"] = depth
    if extra_overrides:
        ov.update(extra_overrides)
    cell = make_cell(arch, shape_name, mesh, overrides=ov)
    with jax.set_mesh(mesh):
        jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                         donate_argnums=cell.donate)
        compiled = jitted.lower(*cell.args).compile()
    cost = compiled.cost_analysis()
    colls = parse_collectives(compiled.as_text())
    mem = {}
    try:
        ma = compiled.memory_analysis()
        mem = {k: int(getattr(ma, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes") if hasattr(ma, k)}
    except Exception as e:
        mem = {"error": str(e)}
    return {
        "depth": depth,
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll_bytes": sum(v["bytes"] for v in colls.values()),
        "collectives": colls,
        "memory": mem,
    }


def run_cell_scaled(arch: str, shape_name: str, multi_pod: bool,
                    out_dir: str, tag: str = "scaled",
                    extra_overrides=None) -> dict:
    """Differential-depth roofline: lower at 1x and 2x the layer period
    (fully unrolled so XLA costs every op), then scale the per-period delta
    to the architecture's full depth.  Head/embed/CE costs cancel in the
    delta and are added once.  Validated against a full-depth unroll in
    EXPERIMENTS.md §Dry-run."""
    mesh_chips = 512 if multi_pod else 256
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    plen = len(cfg.layer_period or "A")
    n_periods = cfg.n_layers // plen
    t0 = time.time()
    rec = {"arch": arch, "shape": shape_name, "chips": mesh_chips,
           "multi_pod": multi_pod, "tag": tag, "ok": False,
           "method": f"differential depth {plen}+{2*plen} -> "
                     f"{cfg.n_layers} layers"}
    try:
        s1 = _lower_stats(arch, shape_name, multi_pod, plen,
                          extra_overrides)
        s2 = _lower_stats(arch, shape_name, multi_pod, 2 * plen,
                          extra_overrides)

        def scale(k):
            return s1[k] + (s2[k] - s1[k]) * (n_periods - 1)

        flops, nbytes, coll = scale("flops"), scale("bytes"), \
            scale("coll_bytes")
        rec["cost"] = {"flops": flops, "bytes_accessed": nbytes,
                       "per_period_flops": s2["flops"] - s1["flops"],
                       "head_flops": 2 * s1["flops"] - s2["flops"]}
        rec["collectives_1p"] = s1["collectives"]
        rec["collectives_2p"] = s2["collectives"]
        rec["memory_1p"], rec["memory_2p"] = s1["memory"], s2["memory"]
        if "argument_size_in_bytes" in s1["memory"]:
            rec["memory_scaled_args"] = int(
                s1["memory"]["argument_size_in_bytes"]
                + (s2["memory"]["argument_size_in_bytes"]
                   - s1["memory"]["argument_size_in_bytes"])
                * (n_periods - 1))
        rec["roofline"] = roofline_terms(
            flops_per_device=flops, bytes_per_device=nbytes,
            coll_bytes_per_device=coll, chips=mesh_chips,
            model_flops=model_flops(cfg, shape),
            analytic_bytes_per_device=analytic_bytes(cfg, shape,
                                                     mesh_chips))
        rec["compile_s"] = round(time.time() - t0, 1)
        rec["ok"] = True
    except Exception:
        rec["error"] = traceback.format_exc()[-2000:]
        rec["compile_s"] = round(time.time() - t0, 1)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        pod = "multi" if multi_pod else "single"
        path = os.path.join(out_dir,
                            f"{arch}_{shape_name}_{pod}_{tag}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    status = "OK" if rec["ok"] else "FAIL"
    print(f"[{status}] scaled {arch} x {shape_name} "
          f"({rec['compile_s']}s)", flush=True)
    return rec


def main() -> None:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--unroll", action="store_true",
                    help="static-unroll scans so cost_analysis counts every "
                         "iteration (roofline runs; tag='unroll')")
    ap.add_argument("--scaled", action="store_true",
                    help="differential-depth roofline mode (tag='scaled')")
    args = ap.parse_args()
    overrides = {"static_unroll": True} if args.unroll else None
    tag = "unroll" if args.unroll else ""

    cells = []
    if args.all or args.arch is None:
        for a in archs.ALL:
            for s in shapes_for(get_arch(a)):
                cells.append((a, s))
    else:
        shapes = [args.shape] if args.shape else shapes_for(
            get_arch(args.arch))
        cells = [(args.arch, s) for s in shapes]

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    n_fail = 0
    for arch, shape in cells:
        for mp in meshes:
            pod = "multi" if mp else "single"
            suffix = "_scaled" if args.scaled else (f"_{tag}" if tag else "")
            path = os.path.join(args.out,
                                f"{arch}_{shape}_{pod}{suffix}.json")
            if args.skip_existing and os.path.exists(path):
                with open(path) as f:
                    if json.load(f).get("ok"):
                        print(f"[skip] {arch} x {shape} x {pod}{suffix}")
                        continue
            if args.scaled:
                rec = run_cell_scaled(arch, shape, mp, args.out)
            else:
                rec = run_cell(arch, shape, mp, args.out,
                               overrides=overrides, tag=tag)
            n_fail += 0 if rec["ok"] else 1
    print(f"dry-run complete: {n_fail} failures")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()

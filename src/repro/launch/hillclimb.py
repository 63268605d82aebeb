"""Perf-hillclimb harness (§Perf): lower one (arch x shape) cell at reduced
depth, attribute every collective / big op to its source (HLO metadata), and
diff roofline terms across named variants.

Usage:
  PYTHONPATH=src python -m repro.launch.hillclimb --arch qwen2-vl-7b \
      --shape prefill_32k --variant baseline --depth 1 [--overrides k=v ...]

Variants are named override-sets defined in VARIANTS below; each run writes
experiments/hillclimb/<arch>_<shape>_<variant>.json so EXPERIMENTS.md §Perf
can diff before/after.
"""

import argparse
import json
import os
import re
import time
from collections import defaultdict

import jax

from repro.configs.base import SHAPES, get_arch
from repro.configs import archs  # noqa: F401
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import (_DTYPE_BYTES, analytic_bytes,
                                   parse_collectives, roofline_terms)
from repro.launch.specs import make_cell, model_flops

_COLL_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_META_RE = re.compile(r'op_name="([^"]*)"')


def _shape_bytes(dtype: str, dims: str) -> int:
    nb = _DTYPE_BYTES.get(dtype, 0)
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * nb


def attribute_collectives(hlo_text: str, top: int = 25):
    """Group collective operand bytes by (kind, source op_name prefix)."""
    from repro.launch.roofline import iter_collectives
    groups = defaultdict(lambda: [0.0, 0])
    for kind, nbytes, rhs in iter_collectives(hlo_text):
        meta = _META_RE.search(rhs)
        name = meta.group(1) if meta else "?"
        # strip jit prefix and array indices for grouping
        name = re.sub(r"^jit\([^)]*\)/", "", name)
        name = re.sub(r"\d+", "#", name)
        groups[(kind, name)][0] += nbytes
        groups[(kind, name)][1] += 1
    rows = sorted(((b, c, k, n) for (k, n), (b, c) in groups.items()),
                  reverse=True)
    return rows[:top]


VARIANTS = {
    # paper-faithful / current default
    "baseline": {},
    # hillclimb steps (hypotheses in EXPERIMENTS.md §Perf):
    "seq": {"attn_shard": "seq"},
    "seq_bf16": {"attn_shard": "seq", "scores_dtype": "bfloat16"},
    "bf16scores": {"scores_dtype": "bfloat16"},
    "seq_causal": {"attn_shard": "seq", "causal_bound": True},
    "seq_causal_bf16": {"attn_shard": "seq", "causal_bound": True,
                        "scores_dtype": "bfloat16"},
    "causal": {"causal_bound": True},
    "kv_int8": {"kv_dtype": "int8"},
    "seq_attn_only": {"attn_shard": "seq", "seq_residual": False},
    "seq_causal_attn_only": {"attn_shard": "seq", "seq_residual": False,
                             "causal_bound": True},
}


def run(arch: str, shape_name: str, variant: str, depth: int,
        multi_pod: bool, out_dir: str, extra: dict, attribute: bool = True):
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    plen = len(cfg.layer_period or "A")
    depth = depth * plen
    ov = {"n_layers": depth, "static_unroll": True}
    if cfg.encoder_layers:
        ov["encoder_layers"] = depth
    ov.update(VARIANTS.get(variant, {}))
    ov.update(extra)
    t0 = time.time()
    cell = make_cell(arch, shape_name, mesh, overrides=ov)
    with jax.set_mesh(mesh):
        jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                         donate_argnums=cell.donate)
        compiled = jitted.lower(*cell.args).compile()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    colls = parse_collectives(hlo)
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    coll = sum(v["bytes"] for v in colls.values())
    chips = mesh.size
    rec = {
        "arch": arch, "shape": shape_name, "variant": variant,
        "depth": depth, "chips": chips, "overrides": {
            k: str(v) for k, v in ov.items()},
        "flops": flops, "bytes": nbytes, "coll_bytes": coll,
        "collectives": colls,
        "compile_s": round(time.time() - t0, 1),
    }
    # roofline at THIS depth (not scaled) — variants compare like-for-like
    rec["roofline_at_depth"] = roofline_terms(
        flops_per_device=flops, bytes_per_device=nbytes,
        coll_bytes_per_device=coll, chips=chips,
        model_flops=model_flops(cfg, shape) * depth / cfg.n_layers,
        analytic_bytes_per_device=analytic_bytes(cfg, shape, chips)
        * depth / cfg.n_layers)
    print(f"== {arch} x {shape_name} [{variant}] depth={depth} "
          f"chips={chips} compile={rec['compile_s']}s")
    print(f"   flops/dev={flops:.3e} bytes/dev={nbytes:.3e} "
          f"coll/dev={coll:.3e}")
    rf = rec["roofline_at_depth"]
    print(f"   t_comp={rf['t_compute_s']:.4f}s t_mem={rf['t_memory_s']:.4f}s "
          f"t_coll={rf['t_collective_s']:.4f}s dom={rf['dominant']}")
    if attribute:
        print("   top collectives by operand bytes:")
        for b, c, k, n in attribute_collectives(hlo):
            print(f"     {b:12.3e}B x{c:<3d} {k:<20s} {n[:90]}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir,
                            f"{arch}_{shape_name}_{variant}_d{depth}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    # a compile-only tool: the production mesh as host devices
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=512")
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--depth", type=int, default=1,
                    help="layer periods to lower (scaled roofline uses 1+2)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="experiments/hillclimb")
    ap.add_argument("--set", nargs="*", default=[],
                    help="extra cfg overrides k=v (int/float/str/bool)")
    args = ap.parse_args()
    extra = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        if v in ("true", "false"):
            v = v == "true"
        extra[k] = v
    run(args.arch, args.shape, args.variant, args.depth, args.multi_pod,
        args.out, extra)


if __name__ == "__main__":
    main()

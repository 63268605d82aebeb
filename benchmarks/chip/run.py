#!/usr/bin/env python3
"""Run one cell of the chip benchmark once, in this process, and print its
result as the last line of standard output.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell's
configuration file (``configs/<config>.json``, which names its engine and
its reference), its traffic file (``traffic/<cell>.json``) and, with
``--trace 1``, one reader per per-layer metric (``metrics/<metric>.py``).
With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` a stretch of the window is traced and the metrics are its
per-layer metrics.  Without a TPU, or with fewer chips than the cell asks
for, it exits 1 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import importlib.util
import json
import os
import sys
import types
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T_START:8.3f}] {msg}", file=sys.stderr,
          flush=True)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> dict:
    """The cell ``name`` with its configuration, traffic and metrics."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    model = json.loads((ROOT / conf["file"]).read_text())
    return {
        "name": name, "chips": cell["chips"], "model": model,
        "engine": model["engine"],
        "traffic": json.loads((HERE / "traffic" / f"{name}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"] if _applies(m, name)],
        "per_layer": [m for m in bench["per_layer"] if _applies(m, name)],
    }


def load_reader(metric: str):
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.metrics.{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
    import jax

    from benchmarks.chip.peaks import peaks_for

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"JAX found no TPU (platform {dev.platform!r}); nothing was run")
        return 1
    if len(devices) < cell["chips"]:
        log(f"{args.workload} needs {cell['chips']} chips, JAX found "
            f"{len(devices)}; nothing was run")
        return 1
    peak = peaks_for(dev.device_kind)

    from repro.launch.cache import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"{args.workload} seed {args.seed}: {dev.device_kind} x "
        f"{len(devices)}, jax {jax.__version__}, compile cache "
        f"{enable_compile_cache()}")
    engine = importlib.import_module(
        f"benchmarks.chip.engines.{cell['engine']['kind']}")
    out = engine.run(cell, args.seed, args.seconds, bool(args.trace), peak,
                     T_START, log)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    result = {"correct": bool(out.correct), "attempted": out.attempted,
              "failed": out.failed}
    if args.trace:
        red = out.reduced
        ctx = types.SimpleNamespace(model=cell["model"], peak=peak,
                                    reduced=red, counts=out.counts,
                                    programs=cell["engine"].get("programs", {}))
        metrics = {}
        for m in cell["per_layer"]:
            v = load_reader(m["name"])(ctx) if red is not None else None
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if red is not None:
            device["busy_s"] = red.busy_s()
            device["window_s"] = red.window_s
            for d in red.devices:
                log(f"trace {d.name}: busy {d.busy_s:.6f} s of "
                    f"{red.window_s:.6f} s, idle "
                    f"{100 * (1 - d.busy_s / red.window_s):.3f}%")
            log(f"traced counts: {json.dumps(out.counts)}")
    else:
        metrics = {m["name"]: {"value": out.e2e[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"] if m["name"] in out.e2e}
    result["metrics"] = metrics
    result["device"] = device
    if args.trace and out.reduced is not None:
        from benchmarks.chip.tracereduce import breakdown

        result["breakdown"] = breakdown(out.reduced)
    result["compared"] = out.compared
    for name, c in out.compared.items():
        rule = ">=" if c.get("at_least") else "<="
        log(f"compared {name}: {c['value']!r} (limit {rule} {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

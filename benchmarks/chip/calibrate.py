#!/usr/bin/env python3
"""Read the numbers a cell's limit is set from, on the chip, in one process:
the program's reading on each seed (a short window at the cell's own load,
then the cell's own comparison) and, on the first few seeds, the reading
of each control: the reference computed in a lower precision put in the
program's place.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 1,2,...,12 [--controls int8,fp8] [--control-seeds 4] \
        [--seconds 6]

One JSON line per seed, then a summary: ``lower`` is the largest reading
of the program, ``upper`` the smallest of each control.  The limit lies
between them (see PERF.md); the benchmark's own runs never run a control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import run as harness_main  # benchmarks/chip/run.py: paths and cell lookup


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="int8,fp8")
    ap.add_argument("--control-seeds", type=int, default=4)
    ap.add_argument("--seconds", type=float, default=6.0)
    args = ap.parse_args(argv)
    cell = harness_main.load_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import importlib

    import jax

    from benchmarks.chip.peaks import peaks_for

    dev = jax.devices()[0]
    if dev.platform != "tpu" or len(jax.devices()) < cell["chips"]:
        harness_main.log("not enough TPU chips; nothing was run")
        return 1
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    engine = importlib.import_module(
        f"benchmarks.chip.engines.{cell['engine']['kind']}")
    controls = tuple(q for q in args.controls.split(",") if q)
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = engine.run(cell, seed, args.seconds, False,
                         peaks_for(dev.device_kind), t0, harness_main.log,
                         controls=controls if i < args.control_seeds else ())
        name = next(iter(out.compared))
        limit = out.compared[name]["limit"]
        row = {"seed": seed, "number": name,
               "value": out.compared[name]["value"],
               "correct": out.correct, "controls": out.controls,
               # a control's tokens in the program's place, judged alike
               "control_correct": {q: v <= limit
                                   for q, v in out.controls.items()},
               "compared": out.compared, "e2e": out.e2e,
               "seconds": time.perf_counter() - t0}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"workload": args.workload, "seeds": len(rows),
               "lower": max(r["value"] for r in rows),
               "upper": {q: min(r["controls"][q] for r in rows
                                if q in r["controls"]) for q in controls}}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

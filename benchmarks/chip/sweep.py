#!/usr/bin/env python3
"""Find the knee of a serving cell once, on the chip: the cell's traffic at
each of several rates in turn, one batcher, one process.

    python3 benchmarks/chip/sweep.py --workload <cell> --rates 4,6,8 \
        [--seconds 20] [--seed 7]

For each rate it prints one JSON line: requests due and finished,
time-to-first-token quantiles of the first and last third of the window,
and the backlog at the close (requests due by then without a first
token).  A rate is sustained when the backlog is under one second of
arrivals and the last third's median TTFT is under twice the first
third's plus 50 ms: the queue does not grow.  The traffic file's fixed
rate is about four fifths of the highest sustained rate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import run as harness_main  # benchmarks/chip/run.py: paths and cell lookup

from benchmarks.chip import harness, stats, traffic
from benchmarks.chip.engines import batcher as eng_batcher


def sustained(row: dict) -> bool:
    return (row["backlog_at_close"] <= row["rate_per_s"]
            and row["ttft_p50_last_ms"]
            <= 2 * row["ttft_p50_first_ms"] + 50)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    cell = harness_main.load_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    if jax.devices()[0].platform != "tpu":
        harness_main.log("JAX found no TPU; nothing was run")
        return 1
    from repro.launch.cache import enable_compile_cache
    from repro.serve.scheduler import Request

    enable_compile_cache()
    m, tr = cell["model"], cell["traffic"]
    rates = [float(r) for r in args.rates.split(",")]
    plans = {r: traffic.open_loop(dict(tr, rate_per_s=r), args.seed,
                                  args.seconds, m["vocab_size"])
             for r in rates}
    b = eng_batcher.build(cell, args.seed,
                          [p for r in rates for p in plans[r]],
                          harness_main.log)
    make = lambda p: Request(rid=p.rid, prompt=p.prompt, max_new=p.max_new)
    for r in rates:
        with harness.compile_clock() as xla:
            served, _ = harness.drive_open_loop(
                b, make, plans[r], args.seconds, tr["warmup_s"],
                tr["drain_cap_s"], harness.Tracer(False))
        win = [s for s in served if s.in_window]
        third = args.seconds / 3

        def p50(xs):
            return stats.percentile(xs, 50) if xs else float("nan")

        first = [(s.times[0] - s.plan.due) * 1e3 for s in win
                 if s.times and s.plan.due < third]
        last = [(s.times[0] - s.plan.due) * 1e3 for s in win
                if s.times and s.plan.due >= 2 * third]
        ttft = [(s.times[0] - s.plan.due) * 1e3 for s in win if s.times]
        itl = [(y - x) * 1e3 for s in win for x, y in zip(s.times, s.times[1:])]
        row = {"rate_per_s": r, "due": len(win),
               "finished": sum(s.finished for s in win),
               "backlog_at_close": sum(not s.times or s.times[0] > args.seconds
                                       for s in win),
               "ttft_p50_first_ms": p50(first), "ttft_p50_last_ms": p50(last),
               "ttft_p95_ms": stats.percentile(ttft, 95) if ttft else None,
               "itl_p99_ms": stats.percentile(itl, 99) if itl else None,
               "compiles": xla["n"]}
        row["sustained"] = sustained(row)
        print(json.dumps(row), flush=True)
        # drain anything left before the next rate
        b.run_until_drained()
    return 0


if __name__ == "__main__":
    sys.exit(main())

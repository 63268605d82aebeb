"""Example: request-level serving on the CM accelerator + the JAX batcher.

The paper's accelerator is configured once and *streamed* (§1-§2).  Part 1
drives the cycle-accurate serving runtime end-to-end: compile two models
onto disjoint core sets of one chip (weight-stationary co-residency),
submit a Poisson request stream against both tenants, drain, and print the
per-request latency table plus per-tenant percentiles.  Part 2 keeps the
JAX-side analogue: a fixed-slot continuous batcher whose freed slots
backfill mid-flight.

Run: PYTHONPATH=src python examples/continuous_serving.py
"""

import numpy as np

from repro.core import (build_fig2_graph, build_resnet_block_chain,
                        make_chip, place_tenants)
from repro.runtime import CmServer, poisson_arrivals, split_stats


def cm_serving():
    rng = np.random.default_rng(0)
    chip = make_chip(8, "banded")
    placement = place_tenants(
        [build_fig2_graph(), build_resnet_block_chain(2)], chip)
    print(f"tenant core ranges: {placement.core_ranges}")

    server = CmServer(placement, max_inflight=4)

    # open-loop Poisson traffic, requests alternating between the tenants
    n = 10
    arrivals = poisson_arrivals(n, rate=0.02, seed=7)
    for i, arrival in enumerate(arrivals):
        image = rng.normal(size=(4, 8, 8)).astype(np.float32)
        server.submit_image(image, arrival=int(arrival), tenant=i % 2)

    report = server.drain()            # submit -> drain -> latency table
    # to_table() = per-request table + the metrics-registry footer
    # (counters + cycle histograms CmServer populated during the serve)
    print(report.to_table())
    for tk in range(placement.n_tenants):
        print(f"tenant {tk}: p50={report.percentile(50, tenant=tk):.0f} "
              f"p99={report.percentile(99, tenant=tk):.0f} cycles")
    per = split_stats(report.stats, placement,
                      [r.tenant for r in report.requests])
    for tk, s in enumerate(per):
        print(f"tenant {tk}: busy cores={sorted(s.busy)} "
              f"mean util={s.mean_utilization():.1%}")
    # machine-readable form of the same report (summary + per-request
    # rows + metrics snapshot), e.g. for dashboards / regression diffs
    print(f"to_json(): {len(report.to_json())} bytes of JSON")


def jax_batcher():
    from repro.configs.base import smoke_config
    from repro.serve.scheduler import ContinuousBatcher, Request

    cfg = smoke_config("qwen2-7b")
    rng = np.random.default_rng(0)
    engine = ContinuousBatcher(cfg, n_slots=4, max_len=64)

    # a bursty arrival pattern: 10 requests, ragged prompts/budgets
    reqs = []
    for i in range(10):
        prompt = rng.integers(0, cfg.vocab_size,
                              (int(rng.integers(4, 14)),)).astype(np.int32)
        reqs.append(Request(rid=i, prompt=prompt,
                            max_new=int(rng.integers(3, 8))))

    # submit in two bursts with engine ticks in between (requests queue
    # while slots are busy, then backfill as slots free)
    for r in reqs[:6]:
        engine.submit(r)
    for _ in range(4):
        engine.step()
    for r in reqs[6:]:
        engine.submit(r)
    engine.run_until_drained()

    for r in reqs:
        print(f"request {r.rid}: prompt_len={len(r.prompt)} "
              f"-> {len(r.out)} tokens {r.out}")
    st = engine.stats
    print(f"engine steps: {st['steps']}, prefills: {st['prefills']}, "
          f"slot utilization: "
          f"{st['slot_busy_ticks'] / (st['steps'] * engine.n_slots):.1%}")


def main():
    print("=== CM serving runtime (cycle-accurate) ===")
    cm_serving()
    print("\n=== JAX continuous batcher ===")
    jax_batcher()


if __name__ == "__main__":
    main()

"""Serving benchmark: the CM serving runtime + continuous batching.

Paper tie-in: the CM accelerator's throughput case is a *stream* of
inference requests through a resident model (§1).  Two serving planes are
measured:

  * **CM runtime** (``repro.runtime.CmServer``): cycle-accurate
    request-level serving over the event simulator — latency p50/p99 vs
    offered load (open-loop Poisson sweep, queueing at the GCU admission
    point), and 1-tenant vs 2-tenant co-residency on disjoint core sets of
    one chip.  The co-residency rows assert the isolation contract: a
    tenant's outputs are bitwise those of the same program served alone;
    only timing shifts.
  * **JAX batcher**: continuous batching vs static waves (slot utilization,
    steps to drain) — the decode-loop analogue of the same economics.

Reports land in ``BENCH_serve.json`` (CI runs ``--smoke``).
"""

from __future__ import annotations

import numpy as np

from repro.configs.base import smoke_config
from repro.core import (build_fig2_graph, build_resnet_block_chain,
                        compile_model, make_chip, place_tenants)
from repro.runtime import CmRequest, CmServer, load_sweep, split_stats
from repro.serve.scheduler import ContinuousBatcher, Request


# ----------------------------------------------------------- CM runtime rows
def _cm_images(n, shape=(4, 8, 8), seed=0):
    rng = np.random.default_rng(seed)
    return [rng.normal(size=shape).astype(np.float32) for _ in range(n)]


def _measure_cm_load_sweep(smoke: bool):
    g = build_fig2_graph()
    chip = make_chip(4, "all_to_all")
    prog = compile_model(g, chip)
    srv = CmServer(prog, chip)
    n = 8 if smoke else 24
    rates = [0.002, 0.01, 0.05] if smoke else [0.002, 0.005, 0.01, 0.02, 0.05]
    rows = []
    for r in load_sweep(srv, _cm_images(n), rates=rates, seed=3):
        rows.append({"bench": "serve", "mode": "cm_load_sweep",
                     "requests": n, **{k: (round(v, 6) if isinstance(v, float)
                                           else v) for k, v in r.items()}})
    p99s = [r["p99_latency"] for r in rows]
    assert p99s[0] < p99s[-1], \
        f"p99 must rise with offered load: {p99s}"
    return rows


def _measure_cm_tenancy(smoke: bool):
    """1-tenant vs 2-tenant co-residency; asserts bitwise isolation."""
    chip = make_chip(8, "banded")
    pl = place_tenants([build_fig2_graph(), build_resnet_block_chain(2)],
                       chip)
    n_per = 3 if smoke else 8
    imgsA = _cm_images(n_per, seed=1)
    imgsB = _cm_images(n_per, seed=2)

    # each tenant alone on its core set (the co-residency oracle)
    alone = {}
    for tk, imgs in ((0, imgsA), (1, imgsB)):
        srv = CmServer(pl.programs[tk], chip)
        for i, im in enumerate(imgs):
            srv.submit_image(im, arrival=i * 20)
        alone[tk] = srv.drain()

    # co-resident: interleaved arrivals through the shared GCU
    srv = CmServer(pl)
    reqs = []
    for i in range(n_per):
        reqs.append(CmRequest(rid=2 * i, image=imgsA[i], arrival=i * 20,
                              tenant=0))
        reqs.append(CmRequest(rid=2 * i + 1, image=imgsB[i],
                              arrival=i * 20, tenant=1))
    rep = srv.serve(reqs)

    # isolation contract: outputs bitwise equal to the tenant-alone run
    by_rid = rep.by_rid()
    for i in range(n_per):
        for rid, tk, idx in ((2 * i, 0, i), (2 * i + 1, 1, i)):
            want = alone[tk].by_rid()[idx].output
            got = by_rid[rid].output
            for k in want:
                np.testing.assert_array_equal(got[k], want[k])

    per = split_stats(rep.stats, pl, [r.tenant for r in rep.requests])
    rows = []
    for tk in (0, 1):
        rows.append({
            "bench": "serve", "mode": f"cm_tenant{tk}_alone",
            "requests": n_per,
            "p50_latency": alone[tk].p50, "p99_latency": alone[tk].p99,
            "makespan": alone[tk].makespan,
        })
        rows.append({
            "bench": "serve", "mode": f"cm_tenant{tk}_coresident",
            "requests": n_per,
            "p50_latency": rep.percentile(50, tenant=tk),
            "p99_latency": rep.percentile(99, tenant=tk),
            "makespan": rep.makespan,
            "busy_cores": len(per[tk].busy),
            "outputs_bitwise_equal_alone": True,
        })
    return rows


# ------------------------------------------------------------- JAX batcher
def _slot_utilization(b: ContinuousBatcher) -> float:
    st = b.stats
    return st["slot_busy_ticks"] / max(1, st["steps"] * b.n_slots)


def _measure(n_requests: int = 12, n_slots: int = 4, seed: int = 0):
    cfg = smoke_config("qwen2-7b")
    rng = np.random.default_rng(seed)
    lens = rng.integers(4, 12, n_requests)
    news = rng.integers(3, 9, n_requests)

    def mk():
        return [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            (lens[i],)).astype(np.int32),
                        max_new=int(news[i]))
                for i in range(n_requests)]

    # rebuild identical prompts per engine (rng reseed)
    rng = np.random.default_rng(seed)
    lens = rng.integers(4, 12, n_requests)
    news = rng.integers(3, 9, n_requests)
    continuous = ContinuousBatcher(cfg, n_slots=n_slots, max_len=64)
    for r in mk():
        continuous.submit(r)
    continuous.run_until_drained()

    # static batching: admit in waves of n_slots, drain each wave fully
    rng = np.random.default_rng(seed)
    lens = rng.integers(4, 12, n_requests)
    news = rng.integers(3, 9, n_requests)
    static = ContinuousBatcher(cfg, n_slots=n_slots, max_len=64,
                               params=continuous.params)
    reqs = mk()
    static_steps = 0
    for w in range(0, n_requests, n_slots):
        wave = reqs[w:w + n_slots]
        for r in wave:
            static.submit(r)
        # drain the wave completely before the next (static batching)
        while any(s is not None for s in static.slots) or static.queue:
            static.step()
    static_steps = static.stats["steps"]

    rows = {
        "continuous": {
            "steps": continuous.stats["steps"],
            "utilization": round(_slot_utilization(continuous), 3),
            "prefills": continuous.stats["prefills"],
        },
        "static_waves": {
            "steps": static_steps,
            "utilization": round(_slot_utilization(static), 3),
            "prefills": static.stats["prefills"],
        },
    }
    speedup = static_steps / max(1, continuous.stats["steps"])
    return rows, speedup


def run(smoke: bool = False):
    """Harness entry: list of row dicts (benchmarks.run convention)."""
    out = []
    out.extend(_measure_cm_load_sweep(smoke))
    out.extend(_measure_cm_tenancy(smoke))
    rows, speedup = _measure()
    for name, r in rows.items():
        out.append({"bench": "serve", "mode": name, **r})
    out.append({"bench": "serve", "mode": "speedup",
                "continuous_vs_static": f"{speedup:.2f}x"})
    assert speedup >= 1.0
    return out


if __name__ == "__main__":
    for row in run():
        print(row)

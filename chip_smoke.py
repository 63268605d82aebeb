#!/usr/bin/env python3
"""Run the system's main paths once on a TPU and check what comes out.

Everything runs in this one process, through the entry points a user calls:

* CM phase — the paper's path.  ``build_lenet_like`` and
  ``build_tiny_transformer`` are compiled with int8-dequantized crossbars and
  served through ``CmServer`` from seeded Poisson arrivals, once on the
  ``pallas`` plane (the compiled Mosaic crossbar kernel) and once on the
  ``numpy`` plane.  Simulated cycles, messages and every request's latency
  must be identical; outputs must agree within the plane's documented atol.
* LM phase — qwen2-7b at its published widths in bf16, depth cut to 4
  layers, served by ``ContinuousBatcher`` (8 slots, max_len 2048, 12
  requests).  Every request must finish with its tokens, and prefill of
  ``prompt[:n]`` plus one ``decode_step`` must give the logits of prefill of
  ``prompt[:n+1]`` within the bf16 tolerance below.
* ``--four-chip`` runs only the pipelined prefill of the whole 28-layer
  qwen2-7b, 4 stages of 7 layers on a (pod=4, data=1, model=1) mesh, against
  a stage-by-stage forward in which each stage runs on the chip holding it.

Without a TPU it exits non-zero before any phase and prints no result.  The
last line of stdout is ``{"ok": true, "device": {...}}``.

Run: python chip_smoke.py [--four-chip]
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# Decode-vs-prefill and pipelined-vs-stagewise agreement, as max|a - b| over
# max|b|.  bf16 keeps an 8-bit significand (unit roundoff 2^-8); the two
# paths of each comparison round in different orders through every layer, so
# the bound is 8 units of roundoff.  f32 runs (the CPU rehearsal) round at
# 2^-24 and are held to 1e-4.
REL_TOL = {"bfloat16": 2.0 ** -5, "float32": 1e-4}
# pallas plane vs numpy plane on dequantized-int8 crossbars: f32 accumulation
# rounding only (tests/test_compute_plane.py holds the same bounds).
CM_ATOL, CM_RTOL = 2e-5, 1e-5


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise AssertionError(msg)


@contextlib.contextmanager
def compile_clock():
    """Yields a dict whose ``"s"`` sums XLA backend-compile seconds inside."""
    import jax

    acc = {"s": 0.0, "n": 0}

    def listen(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            acc["s"] += secs
            acc["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield acc
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


# ------------------------------------------------------------------ CM phase
def _cm_requests(shape, n, rate, seed):
    import numpy as np
    from repro.runtime import CmRequest, poisson_arrivals

    rng = np.random.default_rng(seed)
    arrivals = poisson_arrivals(n, rate=rate, seed=seed)
    return [CmRequest(rid=i, arrival=int(a),
                      image=rng.normal(size=shape).astype(np.float32))
            for i, a in enumerate(arrivals)]


def _first_descriptor(prog):
    return next(c.compute for c in prog.cores.values()
                if c.compute is not None)


def kernel_hlo(desc, batch: int = 8) -> str:
    """Optimised HLO of one padded crossbar call compiled for the default
    backend (``interpret=False``)."""
    import functools

    import jax
    import jax.numpy as jnp
    from repro.kernels.mxv import crossbar_mxv_padded

    m, n = desc.wq.shape
    fn = jax.jit(functools.partial(crossbar_mxv_padded, interpret=False))
    return fn.lower(jax.ShapeDtypeStruct((batch, n), jnp.float32),
                    jax.ShapeDtypeStruct((m, n), jnp.int8),
                    jax.ShapeDtypeStruct((m,), jnp.float32)
                    ).compile().as_text()


def cm_phase(*, interpret: bool, n_requests: int = 8, rate: float = 0.02,
             seed: int = 0, log=print) -> dict:
    """Serve both zoo models on the pallas and numpy planes and compare."""
    import numpy as np
    from repro.core import (build_lenet_like, build_tiny_transformer,
                            compile_model, dequantize_int8, make_chip)
    from repro.core.compute_plane import PallasPlane
    from repro.runtime import CmServer

    plane = PallasPlane(interpret=interpret)
    cases = [("lenet", build_lenet_like(), make_chip(8, "banded"), (1, 12, 12)),
             ("tiny_xfmr", build_tiny_transformer(), make_chip(12, "banded"),
              (8, 4, 1))]
    out = {}
    for name, graph, chip, shape in cases:
        t0 = time.perf_counter()
        prog = compile_model(graph, chip, quantizer=dequantize_int8)
        cm_compile_s = time.perf_counter() - t0
        reports = {}
        with compile_clock() as xla:
            for pname, p in (("pallas", plane), ("numpy", "numpy")):
                server = CmServer(prog, chip, compute_plane=p)
                reports[pname] = server.serve(
                    _cm_requests(shape, n_requests, rate, seed))
        a, b = reports["pallas"], reports["numpy"]
        _check(a.stats.cycles == b.stats.cycles
               and a.stats.messages == b.stats.messages,
               f"{name}: pallas plane cycles/messages "
               f"{a.stats.cycles}/{a.stats.messages} != numpy "
               f"{b.stats.cycles}/{b.stats.messages}")
        lat_a = [r.latency_cycles for r in a.requests]
        lat_b = [r.latency_cycles for r in b.requests]
        _check(lat_a == lat_b, f"{name}: latencies {lat_a} != {lat_b}")
        _check(len(a.requests) == n_requests
               and all(r.succeeded for r in a.requests),
               f"{name}: not every request completed")
        rows = 0
        max_err = 0.0
        for ra, rb in zip(a.requests, b.requests):
            for v, want in rb.output.items():
                got = ra.output[v]
                np.testing.assert_allclose(got, want, rtol=CM_RTOL,
                                           atol=CM_ATOL,
                                           err_msg=f"{name} rid={ra.rid} {v}")
                rows += int(np.prod(want.shape[:-1], dtype=np.int64))
                max_err = max(max_err, float(np.abs(got - want).max()))
        res = {"requests": n_requests, "cycles": a.stats.cycles,
               "messages": a.stats.messages, "rows_compared": rows,
               "max_abs_err": max_err, "cm_compile_s": cm_compile_s,
               "xla_compile_s": xla["s"], "xla_compiles": xla["n"]}
        if not interpret:
            hlo = kernel_hlo(_first_descriptor(prog))
            _check("tpu_custom_call" in hlo,
                   f"{name}: the padded crossbar call has no tpu_custom_call")
            res["tpu_custom_call"] = True
        log(f"[cm] {name}: {n_requests} requests, cycles={res['cycles']} "
            f"messages={res['messages']} identical on pallas and numpy planes; "
            f"rows compared={rows} max_abs_err={max_err:.3e} "
            f"(atol {CM_ATOL}); CM compile {cm_compile_s:.2f} s, XLA compile "
            f"{xla['s']:.2f} s over {xla['n']} programs"
            + ("; tpu_custom_call in HLO" if not interpret else ""))
        out[name] = res
    return out


# ------------------------------------------------------------------ LM phase
def _rel_err(got, want):
    """max|got - want| / max|want|, reduced on the device."""
    import jax.numpy as jnp

    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def lm_phase(cfg, *, n_requests: int = 12, n_slots: int = 8,
             max_len: int = 2048, prompt_range=(64, 1024),
             new_tokens: int = 32, check_len: int = 777, seed: int = 0,
             log=print) -> dict:
    """Drain a request stream through the batcher, then check decode
    against prefill."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.serve.scheduler import ContinuousBatcher, Request

    rng = np.random.default_rng(seed)
    with compile_clock() as xla:
        t0 = time.perf_counter()
        batcher = ContinuousBatcher(cfg, n_slots=n_slots, max_len=max_len,
                                    seed=seed)
        model, params = batcher.model, batcher.params
        jax.block_until_ready((params, batcher.cache))
        init_s = time.perf_counter() - t0

        lo, hi = prompt_range
        reqs = [Request(rid=i, max_new=new_tokens,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            int(rng.integers(lo, hi + 1))
                                            ).astype(np.int32))
                for i in range(n_requests)]
        for r in reqs:
            batcher.submit(r)
        t0 = time.perf_counter()
        batcher.run_until_drained()
        drain_s = time.perf_counter() - t0
    bad = [r.rid for r in reqs if not r.done or len(r.out) != new_tokens]
    _check(not bad, f"requests {bad} did not finish with {new_tokens} tokens")
    n_tokens = sum(len(r.out) for r in reqs)
    log(f"[lm] {cfg.name}: {n_requests} requests (prompts "
        f"{min(len(r.prompt) for r in reqs)}-{max(len(r.prompt) for r in reqs)}"
        f" tokens) drained, {n_tokens} tokens generated in "
        f"{batcher.stats['steps']} decode steps over {n_slots} slots; "
        f"init {init_s:.2f} s, drain {drain_s:.2f} s host clock incl. "
        f"compiles; XLA compile {xla['s']:.2f} s over {xla['n']} programs")

    # decode one token after prefill(prompt[:n]) == last logits of
    # prefill(prompt[:n + 1]); compared as logits, on the device
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, check_len + 1),
                         jnp.int32)
    prefill = jax.jit(lambda p, t: model.prefill(p, {"tokens": t}, max_len))
    decode = jax.jit(model.decode_step)
    with compile_clock() as xla:
        _, cache = prefill(params, prompt[None, :check_len])
        got, _ = decode(params, cache, prompt[check_len:check_len + 1])
        want, _ = prefill(params, prompt[None, :check_len + 1])
        rel = _rel_err(got, want)
    tol = REL_TOL[cfg.compute_dtype]
    log(f"[lm] decode vs prefill at n={check_len}: {want.shape[1]} logits "
        f"compared, max|d|/max|ref| = {rel:.3e} (tolerance {tol:.3e}, "
        f"{cfg.compute_dtype}); XLA compile {xla['s']:.2f} s")
    _check(rel <= tol, f"decode vs prefill logits differ: {rel} > {tol}")
    return {"requests": n_requests, "tokens": n_tokens, "rel_err": rel,
            "tol": tol, "steps": batcher.stats["steps"]}


# ----------------------------------------------------------- four-chip phase
def _shard_on(x, device):
    """The single-device array of ``x``'s shard on ``device``."""
    return next(s.data for s in x.addressable_shards if s.device == device)


def four_chip_phase(cfg, *, n_stages: int = 4, n_micro: int = 4,
                    batch: int = 8, seq_len: int = 1024, seed: int = 0,
                    log=print) -> dict:
    """Pipelined prefill over ``n_stages`` devices vs a stage-by-stage
    forward that keeps each stage on the device holding it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.mesh import make_mesh
    from repro.launch.pipeline_prefill import (make_pipelined_prefill,
                                               pipeline_param_init,
                                               stage_config)
    from repro.models import lm

    _check(len(jax.devices()) >= n_stages,
           f"need {n_stages} devices, found {len(jax.devices())}")
    mesh = make_mesh((n_stages, 1, 1), ("pod", "data", "model"),
                     jax.devices()[:n_stages])
    # stage s lives on the mesh's s-th device: make_mesh lays the pod axis
    # along the physical ring (ids 0, 1, 3, 2 on a 2x2 v5e), not in id order
    devices = list(mesh.devices.flat)
    b_m = batch // n_micro
    fn, _, in_sh, sched = make_pipelined_prefill(cfg, mesh, n_micro, seq_len,
                                                 batch)
    with compile_clock() as xla:
        stage_params, embed = pipeline_param_init(cfg, n_stages, in_sh)(
            jax.random.key(seed))
        tokens_np = np.random.default_rng(seed).integers(
            0, cfg.vocab_size, (n_micro, b_m, seq_len)).astype(np.int32)
        tokens = jax.device_put(tokens_np, in_sh[2])
        with jax.set_mesh(mesh):
            got = jax.jit(fn, in_shardings=in_sh)(stage_params, embed, tokens)
            got = jax.block_until_ready(got)

        scfg = stage_config(cfg, n_stages)
        pos = jnp.broadcast_to(jnp.arange(seq_len)[None], (b_m, seq_len))
        stage_fn = jax.jit(lambda p, x, pos: lm.run_stack(
            scfg, jax.tree.map(lambda l: l[0], p), x, pos))
        mine = [jax.tree.map(lambda l, d=d: _shard_on(l, d), stage_params)
                for d in devices]
        embed0 = _shard_on(embed, devices[0])[0]
        want = []
        for m in range(n_micro):
            x = embed0[jax.device_put(tokens_np[m], devices[0])]
            for s, d in enumerate(devices):
                x = stage_fn(mine[s], jax.device_put(x, d),
                             jax.device_put(pos, d))
            want.append(x[:, -1, :])
        rel = _rel_err(jax.device_put(got, devices[-1]), jnp.stack(want))
    tol = REL_TOL[cfg.compute_dtype]
    log(f"[4chip] {cfg.name}: {cfg.n_layers} layers as {n_stages} stages of "
        f"{scfg.n_layers} on mesh {dict(mesh.shape)}; {n_micro} microbatches "
        f"x {b_m} x {seq_len} tokens in {sched.n_ticks} ticks; "
        f"{int(np.prod(got.shape[:-1]))} hidden rows compared, "
        f"max|d|/max|ref| = {rel:.3e} (tolerance {tol:.3e}); XLA compile "
        f"{xla['s']:.2f} s over {xla['n']} programs")
    _check(rel <= tol, f"pipelined vs stagewise differ: {rel} > {tol}")
    return {"rel_err": rel, "tol": tol, "ticks": sched.n_ticks}


# ---------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the 4-stage pipelined prefill of the "
                         "whole qwen2-7b (needs 4 chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 1

    from repro.configs.base import depth_cut, get_arch
    from repro.launch.cache import enable_compile_cache

    print(f"device: {dev.platform} {dev.device_kind} x {len(devices)}; "
          f"jax {jax.__version__}; compile cache {enable_compile_cache()}",
          flush=True)
    if args.four_chip:
        cfg = get_arch("qwen2-7b")
        print(f"[4chip] {cfg.name} whole: {cfg.n_layers} layers at published "
              "widths, bf16, random weights", flush=True)
        four_chip_phase(cfg, seed=args.seed)
    else:
        cm_phase(interpret=False, seed=args.seed)
        cfg = depth_cut("qwen2-7b", 4)
        print(f"[lm] {cfg.name} reduced: depth cut 28 -> {cfg.n_layers} "
              f"layers; published widths d_model {cfg.d_model}, heads "
              f"{cfg.n_heads}/{cfg.n_kv_heads} x {cfg.hd}, d_ff {cfg.d_ff}, "
              f"vocab {cfg.vocab_size}, {cfg.param_dtype}, random weights",
              flush=True)
        lm_phase(cfg, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

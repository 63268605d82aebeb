"""Benchmark 4: Pallas kernel wall-time (interpret mode on CPU — a
correctness-side proxy, never a TPU number) and achieved-vs-oracle
consistency."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.flash_attn import flash_attention
from repro.kernels.mxv import crossbar_mxv
from repro.kernels.mamba_scan import selective_scan


def _time(fn, *args, reps=3):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps


def run() -> list:
    rows = []
    rng = np.random.default_rng(0)

    # crossbar mxv
    w = rng.normal(size=(512, 512)).astype(np.float32)
    wq, sc = ref.quantize_crossbar(w)
    x = rng.normal(size=(16, 512)).astype(np.float32)
    t_k = _time(lambda: crossbar_mxv(x, wq, sc))
    t_r = _time(lambda: jnp.asarray(ref.crossbar_mxv_ref(x, wq, sc)))
    rows.append({"bench": "kernel", "case": "mxv 16x512x512",
                 "pallas_interp_ms": round(t_k * 1e3, 3),
                 "jnp_oracle_ms": round(t_r * 1e3, 3),
                 "flops": 2 * 16 * 512 * 512})

    # flash attention
    q = rng.normal(size=(1, 4, 512, 64)).astype(np.float32)
    k = rng.normal(size=(1, 2, 512, 64)).astype(np.float32)
    v = rng.normal(size=(1, 2, 512, 64)).astype(np.float32)
    t_k = _time(lambda: flash_attention(q, k, v, bq=128, bk=128))
    t_r = _time(lambda: ref.attention_ref(q, k, v))
    rows.append({"bench": "kernel", "case": "flash 4h x 512 x 64",
                 "pallas_interp_ms": round(t_k * 1e3, 3),
                 "jnp_oracle_ms": round(t_r * 1e3, 3),
                 "flops": 4 * 2 * 2 * 512 * 512 * 64})

    # selective scan
    u = rng.normal(size=(2, 256, 64)).astype(np.float32) * 0.3
    dt = np.abs(rng.normal(size=(2, 256, 64))).astype(np.float32) * 0.05
    a = -np.abs(rng.normal(size=(64, 16))).astype(np.float32)
    b = rng.normal(size=(2, 256, 16)).astype(np.float32)
    c = rng.normal(size=(2, 256, 16)).astype(np.float32)
    d = rng.normal(size=(64,)).astype(np.float32)
    t_k = _time(lambda: selective_scan(u, dt, a, b, c, d, bd=64, bl=64))
    t_r = _time(lambda: ref.selective_scan_ref(u, dt, a, b, c, d))
    rows.append({"bench": "kernel", "case": "mamba_scan 2x256x64",
                 "pallas_interp_ms": round(t_k * 1e3, 3),
                 "jnp_oracle_ms": round(t_r * 1e3, 3),
                 "flops": 2 * 256 * 64 * 16 * 6})
    return rows

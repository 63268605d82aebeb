"""Benchmark 5: host-side train/serve throughput on reduced configs — the
end-to-end sanity row, never a TPU number."""

from __future__ import annotations

import time


from repro.configs.base import smoke_config
from repro.serve import ServeEngine
from repro.train import Trainer


def run() -> list:
    rows = []
    for arch in ("llama3.2-3b", "falcon-mamba-7b", "qwen2-moe-a2.7b"):
        cfg = smoke_config(arch)
        tr = Trainer(cfg=cfg, batch=8, seq_len=64, peak_lr=3e-3)
        t0 = time.monotonic()
        tr.run(12)
        dt = time.monotonic() - t0
        rows.append({
            "bench": "train", "case": arch,
            "ms_per_step": round(dt / 12 * 1e3, 1),
            "tok_per_s": round(8 * 64 * 12 / dt),
            "loss_drop": round(tr.history[0] - tr.history[-1], 3),
        })
    eng = ServeEngine(smoke_config("qwen2-7b"), max_len=64)
    stats = eng.throughput_probe(4, 32, 8)
    rows.append({"bench": "serve", "case": "qwen2-7b(reduced)",
                 "prefill_ms": round(stats["prefill_s"] * 1e3, 1),
                 "decode_tok_per_s": round(stats["decode_tok_per_s"], 1)})
    return rows

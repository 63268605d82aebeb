"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs the fault-tolerant Trainer on a (possibly reduced) config over however
many local devices exist; on a real cluster the same entrypoint runs under
the production mesh (the dry-run proves the shardings).
"""

from __future__ import annotations

import argparse
import time

from repro.configs import archs  # noqa: F401  (register)
from repro.configs.base import get_arch, smoke_config
from repro.launch.cache import enable_compile_cache
from repro.train import Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=archs.ALL)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke (reduced) config — CPU friendly")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = smoke_config(args.arch) if args.reduced else get_arch(args.arch)
    tr = Trainer(cfg=cfg, batch=args.batch, seq_len=args.seq_len,
                 ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                 peak_lr=args.lr, seed=args.seed)
    state = tr.resume_or_init() if args.resume else tr.init_state()
    print(f"training {cfg.name} ({cfg.param_count()/1e6:.1f}M params) "
          f"from step {int(state.step)}")
    t0 = time.monotonic()
    state = tr.run(args.steps, state=state)
    dt = time.monotonic() - t0
    n = len(tr.history)
    print(f"steps={n} loss {tr.history[0]:.4f} -> {tr.history[-1]:.4f} "
          f"({dt/max(n,1)*1e3:.1f} ms/step)")
    if tr.slow_steps:
        print(f"watchdog flagged {len(tr.slow_steps)} slow steps")


if __name__ == "__main__":
    main()

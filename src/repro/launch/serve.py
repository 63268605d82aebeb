"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Batched prefill + decode over the ServeEngine; reduced configs run on CPU,
full configs target the production mesh proven by the dry-run.
"""

from __future__ import annotations

import argparse


from repro.configs import archs  # noqa: F401
from repro.configs.base import get_arch, smoke_config
from repro.launch.cache import enable_compile_cache
from repro.serve import ServeEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=archs.ALL)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-tokens", type=int, default=16)
    ap.add_argument("--reduced", action="store_true")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = smoke_config(args.arch) if args.reduced else get_arch(args.arch)
    eng = ServeEngine(cfg, max_len=args.prompt_len + args.gen_tokens + 1)
    stats = eng.throughput_probe(args.batch, args.prompt_len,
                                 args.gen_tokens)
    print(f"{cfg.name}: prefill {stats['prefill_s']*1e3:.1f} ms, "
          f"decode {stats['decode_tok_per_s']:.1f} tok/s "
          f"(batch={args.batch}, prompt={args.prompt_len})")


if __name__ == "__main__":
    main()

"""Model zoo: a uniform API over decoder-only / hybrid / enc-dec archs.

``build_model(cfg)`` returns a :class:`Model` with
  init(key) -> params
  loss(params, batch) -> (scalar, metrics)          # training objective
  prefill(params, batch, max_len) -> (logits, cache)
  decode_step(params, cache, tokens) -> (logits, cache)
  init_cache(batch, max_len[, enc_len]) -> cache

The decoder-only models' ``prefill`` and ``decode_step`` also take ``live``,
a mask of the rows MoE layers route, as the batcher passes it; they then
return the routed load third: ``(logits, cache, load)``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict

from repro.configs.base import ArchConfig
from . import encdec, lm


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig
    init: Callable
    loss: Callable
    prefill: Callable
    decode_step: Callable
    init_cache: Callable


def build_model(cfg: ArchConfig) -> Model:
    if cfg.is_encdec:
        return Model(
            cfg=cfg,
            init=lambda key: encdec.init_encdec(cfg, key),
            loss=lambda p, b: encdec.encdec_loss(cfg, p, b),
            prefill=lambda p, b, max_len: encdec.encdec_prefill(
                cfg, p, b["embeds"], b["tokens"], max_len),
            decode_step=lambda p, c, t: encdec.encdec_decode_step(
                cfg, p, c, t),
            init_cache=lambda batch, max_len, enc_len=0: (
                encdec.init_encdec_cache(cfg, batch, max_len, enc_len)),
        )

    def prefill(p, b, max_len, live=None):
        out = lm.prefill(cfg, p, b.get("embeds", b.get("tokens")), max_len,
                         live)
        return out if live is not None else out[:2]

    def decode_step(p, c, t, live=None):
        out = lm.decode_step(cfg, p, c, t, live)
        return out if live is not None else out[:2]

    return Model(
        cfg=cfg,
        init=lambda key: lm.init_lm(cfg, key),
        loss=lambda p, b: lm.lm_loss(cfg, p, b),
        prefill=prefill,
        decode_step=decode_step,
        init_cache=lambda batch, max_len, enc_len=0: (
            lm.init_cache(cfg, batch, max_len)),
    )


__all__ = ["Model", "build_model", "lm", "encdec"]

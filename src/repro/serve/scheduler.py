"""Continuous batching: admit requests into free decode slots mid-flight.

The paper's accelerator is configured once and *streamed* (§1-§2); the
serving analogue is a decode loop that never drains — a fixed-slot batch
where finished sequences free their slot for the next queued request
(vLLM-style continuous batching, minus paging):

  * one jit'd single-sequence prefill per prompt-length *bucket* writes a
    new request's KV/SSM state directly into its slot of the live cache;
  * one jit'd batched ``decode_step`` advances every live slot;
  * per-slot lengths come from the cache's ``length`` vector, so ragged
    batches are exact (the model masks attention by length).

Determinism invariant (tested): a request's output is identical whether it
ran alone or was co-scheduled with arbitrary other traffic.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import build_model


@dataclasses.dataclass
class Request:
    """One serving request.

    Shared by the JAX continuous batcher (``prompt``/``max_new``/``out``
    drive the decode loop) and — via the ``runtime.CmRequest`` subclass —
    the cycle-accurate CM serving runtime, which adds the image payload and
    arrival/latency bookkeeping.  ``prompt``/``max_new`` default to empty so
    non-token workloads can construct the base type directly.
    """

    rid: int
    prompt: Optional[np.ndarray] = None   # (S_p,) int32
    max_new: int = 0
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


def _buckets(n: int, sizes=(16, 32, 64, 128, 256, 512, 1024, 2048, 4096)):
    for s in sizes:
        if n <= s:
            return s
    return sizes[-1]


class ContinuousBatcher:
    """Slot-based continuous batching over a fixed decode batch."""

    def __init__(self, cfg: ArchConfig, n_slots: int, max_len: int,
                 params: Any = None, eos: Optional[int] = None, seed: int = 0):
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.eos = eos
        self.model = build_model(cfg)
        # jitted: the f32 draws behind bf16 weights stay inside one program
        # instead of each living as its own device buffer
        self.params = params if params is not None else \
            jax.jit(self.model.init)(jax.random.key(seed))
        self.cache = self.model.init_cache(n_slots, max_len)
        self.slots: List[Optional[Request]] = [None] * n_slots
        self.queue: List[Request] = []
        self.last_tok = np.zeros((n_slots,), np.int32)
        self.stats = {"steps": 0, "prefills": 0, "slot_busy_ticks": 0}

        self._decode = jax.jit(
            lambda p, c, t: self.model.decode_step(p, c, t))
        self._prefill_cache: Dict[int, Any] = {}        # bucket -> jit fn

    # ------------------------------------------------------------ plumbing
    def _prefill_fn(self, bucket: int):
        if bucket not in self._prefill_cache:
            def fn(p, tokens, true_len):
                # tokens (1, bucket); run full-bucket prefill, then reset
                # length to the true prompt length (suffix is padding that
                # the length mask hides from future attention)
                logits_last, cache = self.model.prefill(
                    p, {"tokens": tokens}, self.max_len)
                cache["length"] = jnp.full((1,), true_len, jnp.int32)
                # logits at the true last token, not the padded tail
                return cache
            self._prefill_cache[bucket] = jax.jit(fn)
        return self._prefill_cache[bucket]

    def _insert_slot(self, slot: int, one_cache: Any) -> None:
        """Write a single-sequence cache into batch slot ``slot``."""
        def ins(batch_leaf, one_leaf):
            if batch_leaf.ndim == 1:                     # length (B,)
                return batch_leaf.at[slot].set(one_leaf[0])
            # (P, B, ...) vs (P, 1, ...)
            return jax.lax.dynamic_update_slice_in_dim(
                batch_leaf, one_leaf.astype(batch_leaf.dtype), slot, axis=1)
        self.cache = jax.tree.map(ins, self.cache, one_cache)

    def _slot_logits_token(self, logits_row: np.ndarray) -> int:
        return int(np.argmax(logits_row))

    # ------------------------------------------------------------- control
    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def _admit(self) -> None:
        for slot in range(self.n_slots):
            if self.slots[slot] is not None or not self.queue:
                continue
            req = self.queue.pop(0)
            sp = len(req.prompt)
            bucket = _buckets(sp)
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :sp] = req.prompt
            cache1 = self._prefill_fn(bucket)(
                self.params, jnp.asarray(toks), sp)
            self._insert_slot(slot, cache1)
            self.slots[slot] = req
            self.stats["prefills"] += 1
            # next-token seed: greedy over the last *true* prompt position.
            # Re-run one decode ahead of the loop would double-step; instead
            # take argmax of the prefill logits recomputed at true length:
            # cheap approach — decode once with the last prompt token.
            self.last_tok[slot] = int(req.prompt[-1])

    def step(self) -> None:
        """One engine tick: admit, batched-decode, retire."""
        self._admit()
        live = [i for i, r in enumerate(self.slots) if r is not None]
        if not live:
            return
        self.stats["steps"] += 1
        self.stats["slot_busy_ticks"] += len(live)
        logits, self.cache = self._decode(
            self.params, self.cache, jnp.asarray(self.last_tok))
        logits = np.asarray(logits)
        for i in live:
            req = self.slots[i]
            tok = self._slot_logits_token(logits[i])
            req.out.append(tok)
            self.last_tok[i] = tok
            if (self.eos is not None and tok == self.eos) or \
                    len(req.out) >= req.max_new:
                req.done = True
                self.slots[i] = None                     # free the slot

    def run_until_drained(self, max_steps: int = 10_000) -> None:
        for _ in range(max_steps):
            if not self.queue and all(s is None for s in self.slots):
                return
            self.step()
        raise RuntimeError("scheduler did not drain")

    @property
    def utilization(self) -> float:
        s = self.stats
        return s["slot_busy_ticks"] / max(1, s["steps"] * self.n_slots)

"""Continuous batching: admit requests into free decode slots mid-flight.

The paper's accelerator is configured once and *streamed* (§1-§2); the
serving analogue is a decode loop that never drains — a fixed-slot batch
where finished sequences free their slot for the next queued request
(vLLM-style continuous batching, minus paging):

  * one jit'd single-sequence prefill per prompt-length *bucket* writes a
    new request's KV/SSM state directly into its slot of the live cache;
  * one jit'd batched ``decode_step`` advances every live slot, updating
    the donated cache in place;
  * per-slot lengths come from the cache's ``length`` vector, so ragged
    batches are exact (the model masks attention by length).

Determinism invariant (tested): a request's output is identical whether it
ran alone or was co-scheduled with arbitrary other traffic.

The host loop marks its boundaries with ``jax.profiler.TraceAnnotation``
spans named in ``SPANS``.  Without a profiler session they cost about a
microsecond each; in a profiler trace they share the device's clock, so
device time and idle gaps can be attributed to what the host was doing.
The three programs have stable names: ``jit_decode_step``, ``jit_prefill``
(one per bucket) and ``jit_insert_slot``.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ArchConfig
from repro.models import build_model

# the batcher's spans; one request's spans share its ``rid``
SPANS = ("batcher.submit",   # submit: rid
         "batcher.admit",    # token upload, prefill and insert dispatch:
                             #   rid, bucket, n (true prompt length)
         "batcher.decode",   # token upload and decode dispatch: live, queued
         "batcher.fetch",    # wait for the decode program, copy logits out
         "batcher.sample")   # per-slot argmax, append, retire


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


@functools.partial(jax.jit, donate_argnums=(0,))
def insert_slot(cache: Any, one_cache: Any, slot) -> Any:
    """``cache`` with the single-sequence ``one_cache`` written into batch
    slot ``slot`` (a traced int32: one program for every slot).  In place:
    the cache is donated, so the call writes one slot's rows and the cache
    it was given is gone after."""
    def ins(batch_leaf, one_leaf):
        if batch_leaf.ndim == 1:                         # length (B,)
            return batch_leaf.at[slot].set(one_leaf[0])
        # (P, B, ...) vs (P, 1, ...)
        return jax.lax.dynamic_update_slice_in_dim(
            batch_leaf, one_leaf.astype(batch_leaf.dtype), slot, axis=1)
    return jax.tree.map(ins, cache, one_cache)


class ContinuousBatcher:
    """Slot-based continuous batching over a fixed decode batch.

    ``stats`` is the operator's view when no profiler runs, counted on the
    host since construction:

    * ``steps``: decode steps; ``slot_busy_ticks``: live slots summed over
      them (``slot_busy_ticks / (steps * n_slots)`` is slot utilization);
    * ``queued_ticks``: requests still queued at each decode step, summed;
    * ``kv_positions``: cache positions the live slots attended, summed
      over decode steps (a slot at length n attends n + 1, at most
      ``max_len``); ``kv_positions / (steps * n_slots * max_len)`` is the
      share of the cache the decode attention reads;
    * ``prefills``: admitted requests; ``prefill_tokens`` their true prompt
      tokens and ``prefill_padded_tokens`` the bucket tokens prefilled;
    * ``queue_wait_s``: admission minus submission time on the host's
      ``perf_counter``, summed over admitted requests;
    * ``moe_pairs``: (row, pick) pairs the decode steps routed to held
      experts, summed over MoE layers and steps; ``moe_experts_hit``: held
      (layer, expert) entries that took at least one pair, summed over
      steps; ``moe_prefill_pairs``: the pairs the prefills routed.  Idle
      slots and bucket padding route nothing.  The programs return the
      load beside their output, and it comes to the host with the decode
      step's logits.
    """

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
        self.stats = {"steps": 0, "prefills": 0, "slot_busy_ticks": 0,
                      "queued_ticks": 0, "kv_positions": 0,
                      "prefill_tokens": 0,
                      "prefill_padded_tokens": 0, "queue_wait_s": 0.0,
                      "moe_pairs": 0, "moe_experts_hit": 0,
                      "moe_prefill_pairs": 0}
        self._submitted: Dict[int, float] = {}          # id(req) -> time
        self._prefill_loads: List[Any] = []             # not yet fetched

        def decode_step(p, c, t, live):
            return self.model.decode_step(p, c, t, live)
        # the cache is donated: the step writes each slot's new K/V row
        # into it in place, and the cache it was given is gone after
        self._decode = jax.jit(decode_step, donate_argnums=(1,))
        self._prefill_cache: Dict[int, Any] = {}        # bucket -> jit fn

    # ------------------------------------------------------------ plumbing
    def _prefill_fn(self, bucket: int):
        if bucket not in self._prefill_cache:
            def prefill(p, tokens, true_len):
                # tokens (1, bucket); run full-bucket prefill, then reset
                # length to the true prompt length (suffix is padding that
                # the length mask hides from future attention, and that
                # MoE layers do not route)
                live = jnp.arange(bucket)[None] < true_len
                logits_last, cache, load = self.model.prefill(
                    p, {"tokens": tokens}, self.max_len, live)
                cache["length"] = jnp.full((1,), true_len, jnp.int32)
                # logits at the true last token, not the padded tail
                return cache, load
            self._prefill_cache[bucket] = jax.jit(prefill)
        return self._prefill_cache[bucket]

    def _slot_logits_token(self, logits_row: np.ndarray) -> int:
        return int(np.argmax(logits_row))

    # ------------------------------------------------------------- control
    def submit(self, req: Request) -> None:
        with TraceAnnotation("batcher.submit", rid=req.rid):
            self._submitted[id(req)] = time.perf_counter()
            self.queue.append(req)

    def _admit(self) -> None:
        for slot in range(self.n_slots):
            if self.slots[slot] is not None or not self.queue:
                continue
            t_admit = time.perf_counter()
            req = self.queue.pop(0)
            sp = len(req.prompt)
            bucket = _buckets(sp)
            with TraceAnnotation("batcher.admit", rid=req.rid, bucket=bucket,
                                 n=sp):
                toks = np.zeros((1, bucket), np.int32)
                toks[0, :sp] = req.prompt
                cache1, load = self._prefill_fn(bucket)(
                    self.params, jnp.asarray(toks), sp)
                self._prefill_loads.append(load)
                self.cache = insert_slot(self.cache, cache1, np.int32(slot))
            self.slots[slot] = req
            st = self.stats
            st["prefills"] += 1
            st["prefill_tokens"] += sp
            st["prefill_padded_tokens"] += bucket
            st["queue_wait_s"] += t_admit - self._submitted.pop(id(req))
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
        st = self.stats
        st["steps"] += 1
        st["slot_busy_ticks"] += len(live)
        st["queued_ticks"] += len(self.queue)
        with TraceAnnotation("batcher.decode", live=len(live),
                             queued=len(self.queue)):
            mask = np.zeros((self.n_slots,), bool)
            mask[live] = True
            logits, self.cache, load = self._decode(
                self.params, self.cache, jnp.asarray(self.last_tok),
                jnp.asarray(mask))
        with TraceAnnotation("batcher.fetch"):
            logits, load, pre = jax.device_get(
                (logits, load, self._prefill_loads))
        self._prefill_loads = []
        st["moe_pairs"] += int(load.sum())
        st["moe_experts_hit"] += int(np.count_nonzero(load))
        st["moe_prefill_pairs"] += sum(int(x.sum()) for x in pre)
        with TraceAnnotation("batcher.sample"):
            for i in live:
                req = self.slots[i]
                tok = self._slot_logits_token(logits[i])
                req.out.append(tok)
                # it attended its prompt, the tokens before tok and its new row
                st["kv_positions"] += min(len(req.prompt) + len(req.out),
                                          self.max_len)
                self.last_tok[i] = tok
                if (self.eos is not None and tok == self.eos) or \
                        len(req.out) >= req.max_new:
                    req.done = True
                    self.slots[i] = None                 # free the slot

    def run_until_drained(self, max_steps: int = 10_000) -> None:
        for _ in range(max_steps):
            if not self.queue and all(s is None for s in self.slots):
                return
            self.step()
        raise RuntimeError("scheduler did not drain")

"""Serving cells: open-loop traffic through ``ContinuousBatcher``'s public
API (``submit`` when a request is due, then ``step``), one process, one
chip.

Correctness: once the window has closed and the program's state is freed,
a seeded sample of the window's finished requests, the longest among them,
is run through the plain float32 reference over its prompt and served
tokens, and every served token's reference logit is held against the
reference's best at that position (``token_gap``).

Decoding may start from either handoff: the batcher today feeds the last
prompt token again as the first decode input (``repeat``); the fix of
that defect feeds the first served token (``next``).  A request is judged
under ``repeat`` and, if that fails, under ``next``: tokens that follow
neither are wrong under both.
"""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import counts, harness, stats, traffic, weights
from benchmarks.chip.reference import load_reference

HANDOFFS = ("repeat", "next")


def program_config(m: dict, eng: dict):
    """The program's ``ArchConfig`` for configuration ``m``: the registered
    architecture ``eng["arch"]`` at ``m``'s depth, refused unless every size
    agrees with the file."""
    from repro.configs.base import get_arch

    cfg = dataclasses.replace(get_arch(eng["arch"]),
                              n_layers=m["num_hidden_layers"])
    want = {"d_model": m["hidden_size"], "d_ff": m["intermediate_size"],
            "n_heads": m["num_attention_heads"],
            "n_kv_heads": m["num_key_value_heads"],
            "hd": counts.head_dim(m), "vocab_size": m["vocab_size"],
            "rope_theta": m["rope_theta"],
            "tie_embeddings": m["tie_word_embeddings"],
            "qkv_bias": m["attention_bias"], "moe": None, "ssm": None,
            "param_dtype": m["torch_dtype"], "compute_dtype": m["torch_dtype"],
            "norm": "rmsnorm", "mlp_act": m["hidden_act"]}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise ValueError(f"{eng['arch']} differs from the configuration "
                         f"file (program, file): {bad}")
    return cfg


def to_program(layers: dict, head: dict) -> dict:
    """The benchmark's weights as ``repro.models.lm``'s parameter tree
    (one period position, layers stacked on the leading axis)."""
    pos = {"norm1": {"scale": layers["ln1"]},
           "norm2": {"scale": layers["ln2"]},
           "attn": {k: layers[k] for k in
                    ("wq", "wk", "wv", "wo", "bq", "bk", "bv")},
           "mlp": {k: layers[k] for k in ("gate", "up", "down")}}
    out = {"embed": head["embed"], "positions": [pos],
           "final_norm": {"scale": head["final_norm"]}}
    if "lm_head" in head:
        out["lm_head"] = head["lm_head"]
    return out


def check_tree(params, model) -> None:
    """Refuse weights whose tree, shapes or dtypes differ from what the
    program's own init would make."""
    want = jax.eval_shape(model.init, jax.random.key(0))
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       params)
    if jax.tree.structure(got) != jax.tree.structure(want) or \
            jax.tree.leaves(got) != jax.tree.leaves(want):
        raise ValueError("the benchmark's weights do not fit the program's "
                         f"parameter tree:\n{got}\nvs\n{want}")


def bucket_set(lengths) -> List[int]:
    """The prefill shapes the given prompt lengths compile: the batcher's
    buckets where it exposes them, else the lengths themselves."""
    from repro.serve import scheduler

    of = getattr(scheduler, "_buckets", lambda n: n)
    return sorted({of(int(n)) for n in lengths})


class StepCounter:
    """Counts the work of each traced step from the requests that gained a
    token in it: the prefills admitted (true prompt lengths) and the decode
    contexts."""

    def __init__(self, m: dict, peak: dict):
        self.m, self.peak = m, peak
        self.c = {"steps": 0, "decode_tokens": 0, "decode_flops": 0.0,
                  "decode_bytes": 0.0, "decode_roofline_s": 0.0,
                  "prefills": 0, "prefill_true_tokens": 0,
                  "prefill_flops": 0.0}

    def __call__(self, grew: List[harness.Served]) -> None:
        c, m = self.c, self.m
        ctx = [len(s.plan.prompt) + len(s.times) - 1 for s in grew]
        new = [len(s.plan.prompt) for s in grew if len(s.times) == 1]
        c["steps"] += 1
        c["decode_tokens"] += len(ctx)
        f = counts.decode_flops(m, ctx)
        b = counts.decode_step_bytes(m, ctx)
        c["decode_flops"] += f
        c["decode_bytes"] += b
        c["decode_roofline_s"] += counts.roofline_s(f, b, self.peak)
        c["prefills"] += len(new)
        c["prefill_true_tokens"] += sum(new)
        c["prefill_flops"] += counts.prefill_flops(m, new)

    def totals(self) -> Dict[str, float]:
        out = dict(self.c)
        out["model_flops"] = out["decode_flops"] + out["prefill_flops"]
        return out


# ----------------------------------------------------------- correctness
def served_sequence(prompt: np.ndarray, out: List[int], handoff: str):
    """(tokens, positions): the sequence the decode steps consumed under
    ``handoff``, and the position whose logits chose each served token."""
    sp = len(prompt)
    if handoff == "repeat":
        seq = np.concatenate([prompt, prompt[-1:], out[:-1]])
        first = sp
    else:
        seq = np.concatenate([prompt, out[:-1]])
        first = sp - 1
    return seq.astype(np.int32), np.arange(first, first + len(out))


def reference_logits(ref, m, layers, head, seq, pos, length: int,
                     rows: int, quant=None):
    """Reference logits (rows, vocab) at positions ``pos`` of ``seq``, then
    padding rows.  The sequence is padded at its end to ``length`` (causal:
    padding changes nothing before it) and the positions to ``rows``, so
    that a few programs serve every request of a cell."""
    toks = np.zeros(length, np.int32)
    toks[:len(seq)] = seq
    at = np.zeros(rows, np.int32)
    at[:len(pos)] = pos
    return ref.logits_at(m, layers, head, jnp.asarray(toks),
                         jnp.asarray(at), quant)


@jax.jit
def _gaps(ref_logits, chosen):
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, chosen[:, None], axis=-1)[:, 0]
    return best - got


def token_gaps(ref_logits, chosen) -> np.ndarray:
    """How far each chosen token's reference logit lies below the
    reference's best at its position, for the first ``len(chosen)`` rows."""
    pad = np.zeros(ref_logits.shape[0], np.int32)
    pad[:len(chosen)] = chosen
    return np.asarray(_gaps(ref_logits, jnp.asarray(pad)))[:len(chosen)]


def sample(served: List[harness.Served], seed: int, min_tokens: int
           ) -> List[harness.Served]:
    """The window's finished requests to check: the longest (prompt plus
    output), one drawn from the seed in each other prefill bucket the
    window used, then others in a seeded order until ``min_tokens`` served
    tokens are in."""
    done = [s for s in served if s.in_window and s.finished]
    if not done:
        return []
    longest = max(done, key=lambda s: len(s.plan.prompt) + s.plan.max_new)
    order = traffic.rng_for(seed, 2).permutation(len(done))
    pick = [longest]
    covered = set(bucket_set([len(longest.plan.prompt)]))
    for i in order:
        b = bucket_set([len(done[i].plan.prompt)])[0]
        if b not in covered:
            covered.add(b)
            pick.append(done[i])
    n = sum(s.plan.max_new for s in pick)
    for i in order:
        if n >= min_tokens:
            break
        if all(done[i] is not s for s in pick):
            pick.append(done[i])
            n += done[i].plan.max_new
    return pick


def pad_length(n: int, cap: int) -> int:
    """The length a reference sequence of ``n`` tokens is padded to: a power
    of two from 256 up, at most ``cap``, so that a few programs serve every
    request of a cell."""
    return min(cap, max(256, 1 << (n - 1).bit_length()))


def check(m, seed, picked, limit: float, max_len: int, rows: int,
          controls=()):
    """Judge the served tokens of ``picked`` against the reference.

    Returns ``(gap, tokens, readings)``: the widest gap over the requests,
    each under the handoff that fits it better; the tokens compared; and,
    for each precision in ``controls``, the widest gap of the tokens that
    the reference computed in that precision would put first."""
    ref = load_reference(m)
    layers, head = weights.draw(m, seed, (m["num_hidden_layers"],))
    worst, n_tok = 0.0, 0
    readings = {q: 0.0 for q in controls}
    for s in picked:
        out = [int(t) for t in s.req.out][:rows]   # a longer answer is
        best, best_h = None, None                  # wrong_length's to catch
        for handoff in HANDOFFS:
            seq, pos = served_sequence(s.plan.prompt, out, handoff)
            length = pad_length(len(seq), max_len)
            lg = reference_logits(ref, m, layers, head, seq, pos, length,
                                  rows)
            g = float(token_gaps(lg, out).max())
            if best is None or g < best:
                best, best_h = g, (seq, pos, lg)
            if g <= limit:
                break
        worst = max(worst, best)
        n_tok += len(out)
        for q in controls:
            seq, pos, lg = best_h
            lq = reference_logits(ref, m, layers, head, seq, pos,
                                  pad_length(len(seq), max_len), rows, q)
            top = np.asarray(jnp.argmax(lq, axis=-1))[:len(out)]
            readings[q] = max(readings[q], float(token_gaps(lg, top).max()))
    return worst, n_tok, readings


# ------------------------------------------------------------------- run
def build(cell: dict, seed: int, plan: List[traffic.Planned], log):
    """The batcher with the benchmark's weights, every shape that ``plan``
    uses compiled: each prefill bucket, the slot insert and the decode
    step, run once with the longest prompt of each bucket."""
    from repro.serve.scheduler import ContinuousBatcher, Request

    m, eng = cell["model"], cell["engine"]
    cfg = program_config(m, eng)
    t0 = time.perf_counter()
    with harness.compile_clock() as xla:
        params = to_program(*weights.draw(m, seed,
                                          (m["num_hidden_layers"],)))
        batcher = ContinuousBatcher(cfg, n_slots=eng["slots"],
                                    max_len=eng["max_len"], params=params)
        check_tree(params, batcher.model)
        jax.block_until_ready((params, batcher.cache))
    del params
    log(f"weights and cache: {time.perf_counter() - t0:.2f} s, "
        f"{xla['n']} compiles ({xla['s']:.2f} s)")
    longest: Dict[int, int] = {}
    for p in plan:
        b = bucket_set([len(p.prompt)])[0]
        longest[b] = max(longest.get(b, 0), len(p.prompt))
    rng = traffic.rng_for(seed, 3)
    t0 = time.perf_counter()
    with harness.compile_clock() as xla:
        for i, n in enumerate(sorted(longest.values())):
            batcher.submit(Request(rid=-1 - i, max_new=2, prompt=rng.integers(
                0, m["vocab_size"], n).astype(np.int32)))
        batcher.run_until_drained()
        jax.block_until_ready(batcher.cache)
    log(f"warm-up of prefill buckets {sorted(longest)}: "
        f"{time.perf_counter() - t0:.2f} s, {xla['n']} compiles "
        f"({xla['s']:.2f} s)")
    return batcher


def run(cell: dict, seed: int, seconds: float, trace: bool, peak: dict,
        t_start: float, log, controls=()) -> harness.Outcome:
    from repro.serve.scheduler import Request

    m, eng, tr = cell["model"], cell["engine"], cell["traffic"]
    plan = traffic.open_loop(tr, seed, seconds, m["vocab_size"])
    batcher = build(cell, seed, plan, log)
    log(f"set-up before the warm-up traffic: "
        f"{time.perf_counter() - t_start:.2f} s since start")

    tracer = harness.Tracer(trace)
    counter = StepCounter(m, peak)
    occupancy: Dict[str, float] = {}
    make = lambda p: Request(rid=p.rid, prompt=p.prompt, max_new=p.max_new)
    t_len = eng["trace_seconds"]
    t_from = max(0.0, seconds / 2 - t_len / 2)
    # what set-up made lives to the end: no collection pass walks it again
    gc.collect()
    gc.freeze()
    try:
        with harness.compile_clock() as xla:
            served, t_open = harness.drive_open_loop(
                batcher, make, plan, seconds, tr["warmup_s"],
                tr["drain_cap_s"], tracer, (t_from, t_from + t_len), counter,
                occupancy)
            t_end = time.perf_counter() - t_open
    finally:
        gc.unfreeze()
    tracer.collect()
    setup_s = t_open - t_start
    window = [s for s in served if s.in_window]
    finished = [s for s in window if s.finished]
    late = [s.submitted - s.plan.due for s in window]
    log(f"window: {len(window)} requests due in {seconds} s, {len(finished)}"
        f" finished, loop ended {t_end - seconds:.2f} s after the close; "
        f"{xla['n']} compiles ({xla['s']:.3f} s) after set-up; generator "
        f"late by p50 {stats.percentile(late, 50) * 1e3:.3f} ms, max "
        f"{max(late) * 1e3:.3f} ms")
    steps = occupancy.get("steps", 0)
    if steps:
        log(f"slots: {steps} steps in the window, busy mean "
            f"{occupancy['busy'] / steps:.3f} of {eng['slots']}, max "
            f"{occupancy['busy_max']}; waiting for a slot mean "
            f"{occupancy['queued'] / steps:.3f}")
    e2e: Dict[str, float] = {"setup_s": setup_s}
    ttft = [(s.times[0] - s.plan.due) * 1e3 for s in window if s.times]
    itl = [(b - a) * 1e3 for s in window for a, b in zip(s.times, s.times[1:])]
    # each tail at a percentile with ten or more samples beyond it
    if ttft:
        e2e["ttft_p90_ms"] = stats.percentile(ttft, 90)
    if itl:
        e2e["itl_p99_ms"] = stats.percentile(itl, 99)
    for name, xs in (("ttft", ttft), ("itl", itl)):
        if xs:
            log(f"{name} ms over {len(xs)}: " + ", ".join(
                f"p{q} {stats.percentile(xs, q):.3f}"
                for q in (50, 90, 95, 99)))
    mem = int((jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use", 0))

    # the program's state goes before the reference runs
    del batcher
    gc.collect()

    check_cfg = tr["check"]
    picked = sample(served, seed, check_cfg["min_tokens"])
    limit = check_cfg["limit_token_gap"]
    t0 = time.perf_counter()
    with harness.compile_clock() as xla:
        gap, n_tok, readings = check(m, seed, picked, limit,
                                     eng["max_len"], traffic.output_bound(tr),
                                     controls)
    used = bucket_set([len(s.plan.prompt) for s in finished])
    checked = bucket_set([len(s.plan.prompt) for s in picked])
    log(f"check: {len(picked)} requests, {n_tok} served tokens, prefill "
        f"buckets {checked} of {used}, against the reference in "
        f"{time.perf_counter() - t0:.2f} s, {xla['n']} compiles "
        f"({xla['s']:.2f} s)")
    wrong_len = sum(len(s.req.out) != s.plan.max_new for s in finished)
    unfinished = len(window) - len(finished)
    compared = {
        "token_gap": {"value": gap, "limit": limit},
        "tokens_checked": {"value": n_tok, "limit": check_cfg["min_tokens"],
                           "at_least": True},
        "buckets_checked": {"value": len(checked), "limit": len(used),
                            "at_least": True},
        "unfinished": {"value": unfinished, "limit": 0},
        "wrong_length": {"value": wrong_len, "limit": 0},
    }
    correct = (gap <= limit and n_tok >= check_cfg["min_tokens"]
               and len(checked) >= len(used) and unfinished == 0
               and wrong_len == 0)
    return harness.Outcome(
        attempted=len(window), failed=unfinished, setup_s=setup_s, e2e=e2e,
        compared=compared, correct=correct, memory_peak_bytes=mem,
        counts=counter.totals(), reduced=tracer.reduced, controls=readings)

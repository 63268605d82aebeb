"""Serving cells of a mixture-of-experts configuration: open-loop traffic
through ``ContinuousBatcher``'s public API on one chip, as in
``engines/batcher.py``, whose helpers this engine reuses.  Its own are what
the expert layer changes: the program's configuration (one chip's share of
the experts), the weight draw and its map onto the program's tree, the
per-step counts (which read the routed load the batcher counts), and the
check against ``reference/<reference>.py``.

Correctness is judged as in ``engines/batcher.py``, over a seeded sample
of the window's finished requests that covers every prefill bucket used:
``token_gap``, the widest gap between a served token's reference logit and
the reference's best at its position, and besides ``token_gap_mean``, the
mean gap over every served token compared.  The program computes in bf16
and routes from bf16 states: where a row's 8th and 9th router
probabilities lie within bf16's rounding it picks another expert than the
float32 reference, and the few tokens that follow such a pick set the
widest gap; a lower precision moves every position, and the mean gap sees
that.  A control is judged by the same :func:`judge` as the program.
"""

from __future__ import annotations

import gc
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import counts, harness, moe_counts, stats, traffic, \
    weights
from benchmarks.chip.engines.batcher import (HANDOFFS, bucket_set,
                                             check_tree, pad_length,
                                             reference_logits, sample,
                                             served_sequence, token_gaps)
from benchmarks.chip.reference import load_reference

EXPERT_STREAM = 1 << 16


def program_config(m: dict, eng: dict):
    """The program's ``ArchConfig`` for configuration ``m``: the registered
    architecture ``eng["arch"]``, refused unless every size, the expert
    share and the routing agree with the file."""
    from repro.configs.base import get_arch

    cfg = get_arch(eng["arch"])
    share = m["expert_share"]
    moe = cfg.moe
    want = {"n_layers": m["num_hidden_layers"], "d_model": m["hidden_size"],
            "n_heads": m["num_attention_heads"],
            "n_kv_heads": m["num_key_value_heads"], "hd": m["head_dim"],
            "vocab_size": m["vocab_size"], "rope_theta": m["rope_theta"],
            "tie_embeddings": m["tie_word_embeddings"],
            "qkv_bias": m["attention_bias"], "qk_norm": True, "ssm": None,
            "param_dtype": m["torch_dtype"], "compute_dtype": m["torch_dtype"],
            "norm": "rmsnorm", "mlp_act": m["hidden_act"],
            "layer_period": None,
            "moe": (share["of"], m["num_experts_per_tok"],
                    m["moe_intermediate_size"], 0, m["decoder_sparse_step"],
                    m["norm_topk_prob"], (share["first"], share["held"]))}
    got = {k: getattr(cfg, k) for k in want if k != "moe"}
    got["moe"] = moe and (moe.n_experts, moe.top_k, moe.d_ff, moe.n_shared,
                          moe.every, moe.norm_topk_prob, moe.held)
    if got != want or share["held"] != m["num_experts"] or \
            m["mlp_only_layers"]:
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise ValueError(f"{eng['arch']} differs from the configuration "
                         f"file (program, file): {bad}")
    return cfg


# ---------------------------------------------------------------- weights
def layer_shapes(m: dict) -> dict:
    """One layer's weights apart from the experts'."""
    d, hd = m["hidden_size"], m["head_dim"]
    q = m["num_attention_heads"] * hd
    kv = m["num_key_value_heads"] * hd
    return {"ln1": (d,), "ln2": (d,), "q_norm": (hd,), "k_norm": (hd,),
            "wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "router": (d, m["expert_share"]["of"])}


def expert_shapes(m: dict) -> dict:
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    return {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


def _normal(key, name: str, shape, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if name in ("ln1", "ln2", "q_norm", "k_norm"):
        return (1.0 + 0.1 * z).astype(dtype)
    return (z / np.sqrt(shape[0])).astype(dtype)


def _draw_layer(m: dict, key_data) -> dict:
    """One layer from its key: the shared weights from a split of it, and
    held expert ``e`` (its published index) from ``fold_in(key,
    EXPERT_STREAM + e)``, so that every share draws the same experts."""
    dtype = jnp.dtype(m["torch_dtype"])
    key = jax.random.wrap_key_data(key_data)
    shapes = layer_shapes(m)
    out = {name: _normal(k, name, shape, dtype) for k, (name, shape) in
           zip(jax.random.split(key, len(shapes)), sorted(shapes.items()))}
    share = m["expert_share"]

    def expert(e):
        ek = jax.random.fold_in(key, EXPERT_STREAM + e)
        es = expert_shapes(m)
        return {name: _normal(k, name, shape, dtype) for k, (name, shape) in
                zip(jax.random.split(ek, len(es)), sorted(es.items()))}

    out.update(jax.lax.map(expert, share["first"]
                           + jnp.arange(share["held"])))
    return out


def draw(m: dict, seed: int):
    """``(layers, head)`` of the whole configuration, drawn on the device in
    one jitted call: layer ``l`` from ``weights.layer_keys``' key ``l`` (the
    scheme of ``weights.py``), one layer after another so that only one
    layer's float32 draws live at once; the head as ``weights.draw_head``
    draws it."""
    n = m["num_hidden_layers"]
    fn = lambda kd, hk: (jax.lax.map(lambda k: _draw_layer(m, k), kd),
                         weights.draw_head(m, hk))
    return jax.jit(fn)(weights.layer_keys(seed, 0, (n,)),
                       weights.head_key(seed))


def to_program(layers: dict, head: dict) -> dict:
    """The benchmark's weights as ``repro.models.lm``'s parameter tree."""
    pos = {"norm1": {"scale": layers["ln1"]},
           "norm2": {"scale": layers["ln2"]},
           "attn": {k: layers[k] for k in
                    ("wq", "wk", "wv", "wo", "q_norm", "k_norm")},
           "moe": {"router": layers["router"].astype(jnp.float32),
                   **{k: layers[k] for k in ("w_gate", "w_up", "w_down")}}}
    return {"embed": head["embed"], "positions": [pos],
            "final_norm": {"scale": head["final_norm"]},
            "lm_head": head["lm_head"]}


# ----------------------------------------------------------------- counts
class StepCounter:
    """Counts the work of each traced step from the requests that gained a
    token in it (prefills admitted at true lengths, decode contexts) and
    the routed load the batcher counted in that step."""

    def __init__(self, m: dict, peak: dict, batcher):
        self.m, self.peak, self.batcher = m, peak, batcher
        self.c = {"steps": 0, "decode_tokens": 0, "decode_flops": 0.0,
                  "decode_bytes": 0.0, "decode_roofline_s": 0.0,
                  "prefills": 0, "prefill_true_tokens": 0,
                  "prefill_flops": 0.0, "moe_pairs": 0, "moe_experts_hit": 0,
                  "moe_gemm_roofline_s": 0.0}

    def __call__(self, grew: List[harness.Served]) -> None:
        c, m, load = self.c, self.m, self.batcher.last_load
        ctx = [len(s.plan.prompt) + len(s.times) - 1 for s in grew]
        new = [len(s.plan.prompt) for s in grew if len(s.times) == 1]
        pairs, hit = load["moe_pairs"], load["moe_experts_hit"]
        f = moe_counts.decode_flops(m, ctx, pairs)
        b = moe_counts.decode_step_bytes(m, ctx, hit)
        c["steps"] += 1
        c["decode_tokens"] += len(ctx)
        c["decode_flops"] += f
        c["decode_bytes"] += b
        c["decode_roofline_s"] += counts.roofline_s(f, b, self.peak)
        c["moe_pairs"] += pairs
        c["moe_experts_hit"] += hit
        c["moe_gemm_roofline_s"] += counts.roofline_s(
            moe_counts.expert_flops(m, pairs),
            moe_counts.gemm_bytes(m, pairs, hit), self.peak)
        c["prefills"] += len(new)
        c["prefill_true_tokens"] += sum(new)
        c["prefill_flops"] += moe_counts.prefill_flops(
            m, new, load["moe_prefill_pairs"])

    def totals(self) -> Dict[str, float]:
        out = dict(self.c)
        out["model_flops"] = out["decode_flops"] + out["prefill_flops"]
        return out


def _counted_batcher():
    """``ContinuousBatcher`` that keeps, in ``last_load``, what its routing
    counters gained in its last step."""
    from repro.serve.scheduler import ContinuousBatcher

    class Counted(ContinuousBatcher):
        KEYS = ("moe_pairs", "moe_experts_hit", "moe_prefill_pairs")

        def step(self) -> None:
            before = [self.stats[k] for k in self.KEYS]
            super().step()
            self.last_load = {k: self.stats[k] - b
                              for k, b in zip(self.KEYS, before)}

    return Counted


# ----------------------------------------------------------- correctness
def check(m, seed, picked, limit: float, max_len: int, rows: int,
          controls=()):
    """``engines/batcher.check`` on this configuration's weights, keeping
    every served token's gap.  Returns ``(gaps, tokens, readings)``: the
    gaps of the served tokens, each request under the handoff that fits it
    better; the tokens compared; and, for each precision in ``controls``,
    the gaps of the tokens that the reference computed in that precision
    would put first at the same positions."""
    ref = load_reference(m)
    layers, head = draw(m, seed)
    gaps, n_tok = [], 0
    readings = {q: [] for q in controls}
    for s in picked:
        out = [int(t) for t in s.req.out][:rows]
        best, best_h = None, None
        for handoff in HANDOFFS:
            seq, pos = served_sequence(s.plan.prompt, out, handoff)
            length = pad_length(len(seq), max_len)
            lg = reference_logits(ref, m, layers, head, seq, pos, length,
                                  rows)
            g = token_gaps(lg, out)
            if best is None or g.max() < best.max():
                best, best_h = g, (seq, pos, lg)
            if g.max() <= limit:
                break
        gaps.append(best)
        n_tok += len(out)
        for q in controls:
            seq, pos, lg = best_h
            lq = reference_logits(ref, m, layers, head, seq, pos,
                                  pad_length(len(seq), max_len), rows, q)
            top = np.asarray(jnp.argmax(lq, axis=-1))[:len(out)]
            readings[q].append(token_gaps(lg, top))
    join = lambda xs: np.concatenate(xs) if xs else np.zeros(0)
    return join(gaps), n_tok, {q: join(v) for q, v in readings.items()}


def gap_numbers(gaps: np.ndarray, check_cfg: dict) -> dict:
    """The compared numbers read from the served tokens' gaps."""
    top = float(gaps.max()) if gaps.size else 0.0
    mean = float(gaps.mean()) if gaps.size else 0.0
    return {"token_gap_mean": {"value": mean,
                               "limit": check_cfg["limit_token_gap_mean"]},
            "token_gap": {"value": top,
                          "limit": check_cfg["limit_token_gap"]}}


def judge(compared: dict) -> bool:
    """Every compared number within its limit."""
    return all(c["value"] >= c["limit"] if c.get("at_least")
               else c["value"] <= c["limit"] for c in compared.values())


# ------------------------------------------------------------------- run
def build(cell: dict, seed: int, plan: List[traffic.Planned], log):
    """The batcher with the benchmark's weights, every shape that ``plan``
    uses compiled: each prefill bucket, the slot insert and the decode
    step, run once with the longest prompt of each bucket."""
    from repro.serve.scheduler import Request

    m, eng = cell["model"], cell["engine"]
    cfg = program_config(m, eng)
    t0 = time.perf_counter()
    with harness.compile_clock() as xla:
        params = to_program(*draw(m, seed))
        batcher = _counted_batcher()(cfg, n_slots=eng["slots"],
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
    counter = StepCounter(m, peak, batcher)
    occupancy: Dict[str, float] = {}
    make = lambda p: Request(rid=p.rid, prompt=p.prompt, max_new=p.max_new)
    t_len = eng["trace_seconds"]
    t_from = max(0.0, seconds / 2 - t_len / 2)
    routed0 = dict(batcher.stats)
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
    routed = {k: v - routed0[k] for k, v in batcher.stats.items()}
    if routed["steps"]:
        per = routed["steps"] * m["num_hidden_layers"]
        log(f"routing after set-up: {routed['steps']} decode steps; a layer"
            f" a step routed {routed['moe_pairs'] / per:.3f} pairs to "
            f"{routed['moe_experts_hit'] / per:.3f} of {m['num_experts']} "
            f"held experts; prefills routed {routed['moe_prefill_pairs']} "
            f"pairs")
    e2e: Dict[str, float] = {"setup_s": setup_s}
    ttft = [(s.times[0] - s.plan.due) * 1e3 for s in window if s.times]
    itl = [(b - a) * 1e3 for s in window for a, b in zip(s.times, s.times[1:])]
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
    del batcher, counter.batcher
    gc.collect()

    check_cfg = tr["check"]
    picked = sample(served, seed, check_cfg["min_tokens"])
    limit = check_cfg["limit_token_gap"]
    t0 = time.perf_counter()
    with harness.compile_clock() as xla:
        gaps, n_tok, control_gaps = check(m, seed, picked, limit,
                                          eng["max_len"],
                                          traffic.output_bound(tr), controls)
    used = bucket_set([len(s.plan.prompt) for s in finished])
    checked = bucket_set([len(s.plan.prompt) for s in picked])
    log(f"check: {len(picked)} requests, {n_tok} served tokens, prefill "
        f"buckets {checked} of {used}, against the reference in "
        f"{time.perf_counter() - t0:.2f} s, {xla['n']} compiles "
        f"({xla['s']:.2f} s)")
    wrong_len = sum(len(s.req.out) != s.plan.max_new for s in finished)
    unfinished = len(window) - len(finished)
    compared = {
        **gap_numbers(gaps, check_cfg),
        "tokens_checked": {"value": n_tok, "limit": check_cfg["min_tokens"],
                           "at_least": True},
        "buckets_checked": {"value": len(checked), "limit": len(used),
                            "at_least": True},
        "unfinished": {"value": unfinished, "limit": 0},
        "wrong_length": {"value": wrong_len, "limit": 0},
    }
    correct = judge(compared)
    # a control in the program's place, judged alike; its reading of the
    # first compared number goes to calibrate.py
    first = next(iter(compared))
    readings = {}
    for q, g in control_gaps.items():
        got = dict(compared, **gap_numbers(g, check_cfg))
        readings[q] = got[first]["value"]
        log(f"control {q}: " + ", ".join(
            f"{k} {got[k]['value']!r}" for k in gap_numbers(g, check_cfg))
            + f", mismatched {int(np.count_nonzero(g))} of {g.size}, "
            f"correct {judge(got)}")
    log(f"program: mismatched {int(np.count_nonzero(gaps))} of {gaps.size}")
    return harness.Outcome(
        attempted=len(window), failed=unfinished, setup_s=setup_s, e2e=e2e,
        compared=compared, correct=correct, memory_peak_bytes=mem,
        counts=counter.totals(), reduced=tracer.reduced, controls=readings)

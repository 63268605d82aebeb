"""Continuous batching scheduler: determinism under co-scheduling, slot
reuse, drain guarantees, and the spans, program names and counters a
profiler trace and an operator read (CPU, smoke-size model)."""

from __future__ import annotations

import glob
import shutil
import tempfile

import jax
import numpy as np
import pytest


from repro.configs.base import smoke_config
from repro.serve import scheduler
from repro.serve.scheduler import ContinuousBatcher, Request
from repro.serve.engine import ServeEngine


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_config("llama3.2-3b")
    eng = ServeEngine(cfg, max_len=64)
    return cfg, eng


def _mk_requests(cfg, n, rng):
    reqs = []
    for i in range(n):
        sp = int(rng.integers(3, 12))
        prompt = rng.integers(0, cfg.vocab_size, (sp,)).astype(np.int32)
        reqs.append(Request(rid=i, prompt=prompt, max_new=5))
    return reqs


def test_continuous_matches_solo(setup):
    """A request's tokens are identical co-scheduled vs alone."""
    cfg, eng = setup
    rng = np.random.default_rng(1)
    reqs = _mk_requests(cfg, 5, rng)

    # solo runs (one slot, one request at a time)
    solo = []
    for r in reqs:
        rq = Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new)
        cb = ContinuousBatcher(cfg, n_slots=1, max_len=64,
                               params=eng.params)
        cb.submit(rq)
        cb.run_until_drained()
        solo.append(rq.out)

    # co-scheduled on 3 slots (forces queueing + slot reuse)
    co_reqs = [Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new)
               for r in reqs]
    cb = ContinuousBatcher(cfg, n_slots=3, max_len=64, params=eng.params)
    for rq in co_reqs:
        cb.submit(rq)
    cb.run_until_drained()

    for rq, want in zip(co_reqs, solo):
        assert rq.done
        assert rq.out == want, (rq.rid, rq.out, want)


def test_slot_reuse_and_utilization(setup):
    cfg, eng = setup
    rng = np.random.default_rng(2)
    reqs = _mk_requests(cfg, 7, rng)
    cb = ContinuousBatcher(cfg, n_slots=2, max_len=64, params=eng.params)
    for r in reqs:
        cb.submit(r)
    cb.run_until_drained()
    assert all(r.done for r in reqs)
    assert cb.stats["prefills"] == 7
    # 7 requests through 2 slots => slots were reused
    st = cb.stats
    assert st["slot_busy_ticks"] / (st["steps"] * cb.n_slots) > 0.5


def test_eos_frees_slot_early(setup):
    cfg, eng = setup
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
    # run once to find the first emitted token, then use it as "eos"
    r0 = Request(rid=0, prompt=prompt, max_new=4)
    cb = ContinuousBatcher(cfg, n_slots=1, max_len=64, params=eng.params)
    cb.submit(r0)
    cb.run_until_drained()
    eos = r0.out[0]
    r1 = Request(rid=1, prompt=prompt, max_new=4)
    cb = ContinuousBatcher(cfg, n_slots=1, max_len=64, params=eng.params,
                           eos=eos)
    cb.submit(r1)
    cb.run_until_drained()
    assert r1.out == [eos] and r1.done


def test_insert_slot_writes_one_slot_in_place(setup):
    """The insert takes the batch cache donated: the cache it was given is
    gone after, and the new one is the old one with the slot's rows (and
    length) replaced, every other slot bit for bit."""
    cfg, eng = setup
    cb = ContinuousBatcher(cfg, n_slots=3, max_len=64, params=eng.params)
    fill = lambda c, v: jax.tree.map(lambda a: a + np.asarray(v, a.dtype), c)
    cache = fill(cb.cache, 1)
    one = fill(jax.tree.map(lambda a: a[:, :1] if a.ndim > 1 else a[:1],
                            cb.cache), 7)
    before = jax.tree.map(np.array, cache)               # copies, not views
    new = scheduler.insert_slot(cache, one, np.int32(1))
    assert all(a.is_deleted() for a in jax.tree.leaves(cache))
    for b, n in zip(jax.tree.leaves(before), jax.tree.leaves(new)):
        n = np.asarray(n)
        if n.ndim == 1:                                  # length (B,)
            np.testing.assert_array_equal(n, [1, 7, 1])
        else:                                            # (P, B, ...)
            np.testing.assert_array_equal(n[:, [0, 2]], b[:, [0, 2]])
            assert (n[:, 1] == 7).all()


# ------------------------------------------------------------ observability
# three requests through two slots, all submitted at once: A and B are
# admitted at step 1, A retires at step 2, C takes its slot at step 3, B
# retires at step 3 and C at step 4
SCRIPT = [(5, 2), (20, 3), (9, 2)]          # (prompt length, max_new)


def _script(cfg, rng):
    return [Request(rid=10 + i, max_new=new, prompt=rng.integers(
        0, cfg.vocab_size, (n,)).astype(np.int32))
        for i, (n, new) in enumerate(SCRIPT)]


@pytest.fixture(scope="module")
def traced(setup):
    """The scripted requests through a 2-slot batcher under a CPU profiler
    trace, after a first pass of the script compiled every program.
    Returns the requests, what ``stats`` counted in the traced pass, the
    trace's host events as (name, start_s, end_s, stats), and the HLO
    modules its operations ran in."""
    cfg, eng = setup
    cb = ContinuousBatcher(cfg, n_slots=2, max_len=64, params=eng.params)
    for r in _script(cfg, np.random.default_rng(0)):
        cb.submit(r)
    cb.run_until_drained()
    before = dict(cb.stats)
    reqs = _script(cfg, np.random.default_rng(4))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            for r in reqs:
                cb.submit(r)
            cb.run_until_drained()
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb")[0]
        pd = jax.profiler.ProfileData.from_file(path)
        events, modules = [], set()
        for plane in pd.planes:
            for line in plane.lines:
                for e in line.events:
                    st = dict(e.stats)
                    if "hlo_module" in st:
                        modules.add(st["hlo_module"])
                    if plane.name.startswith("/host:"):
                        events.append((e.name, e.start_ns * 1e-9,
                                       (e.start_ns + e.duration_ns) * 1e-9,
                                       st))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    counted = {k: v - before[k] for k, v in cb.stats.items()}
    return reqs, counted, events, modules


def _named(events, name):
    return [e for e in events if e[0] == name]


def test_program_names(traced):
    """Every operation of the traced steps ran in one of the three named
    programs; none is an anonymous lambda, ``jit_fn`` or an eager op."""
    modules = traced[3]
    assert modules == {"jit_decode_step", "jit_prefill", "jit_insert_slot"}


def test_spans_in_trace(traced):
    """Each span of ``SPANS`` appears as often as the script makes it: a
    submit and an admit per request, a decode, fetch and sample per step."""
    events = traced[2]
    counts = {n: len(_named(events, n)) for n in scheduler.SPANS}
    assert counts == {"batcher.submit": 3, "batcher.admit": 3,
                      "batcher.decode": 4, "batcher.fetch": 4,
                      "batcher.sample": 4}
    live = [(e[3]["live"], e[3]["queued"])
            for e in sorted(_named(events, "batcher.decode"),
                            key=lambda e: e[1])]
    assert live == [(2, 1), (2, 1), (2, 0), (1, 0)]


def test_admit_joins_submit(traced):
    """Each admit joins its request's submit on ``rid`` and carries the
    bucket and the true prompt length."""
    reqs, _, events, _ = traced
    submits = {e[3]["rid"]: e for e in _named(events, "batcher.submit")}
    by_rid = {r.rid: r for r in reqs}
    admits = _named(events, "batcher.admit")
    assert sorted(e[3]["rid"] for e in admits) == sorted(by_rid)
    for name, a0, _, st in admits:
        r = by_rid[st["rid"]]
        assert st["n"] == len(r.prompt)
        assert st["bucket"] == scheduler._buckets(len(r.prompt))
        assert submits[st["rid"]][1] < a0


@pytest.mark.parametrize("span,programs", [
    ("batcher.admit", ("prefill", "insert_slot")),
    ("batcher.decode", ("decode_step",)),
])
def test_programs_nest_in_their_span(traced, span, programs):
    """Each dispatch of a program lies inside the span that calls it, and
    every such span holds the same number of them (one call each)."""
    events = traced[2]
    spans = _named(events, span)
    for prog in programs:
        calls = _named(events, f"PjitFunction({prog})")
        assert calls
        for _, a, b, _ in calls:
            assert any(s0 <= a and b <= s1 for _, s0, s1, _ in spans), prog
        per_span = {sum(s0 <= a and b <= s1 for _, a, b, _ in calls)
                    for _, s0, s1, _ in spans}
        assert len(per_span) == 1, (prog, per_span)


def test_stats_counters_exact(traced):
    reqs, counted, _, _ = traced
    n = [len(r.prompt) for r in reqs]
    assert all(r.done for r in reqs)
    st = dict(counted)
    assert st.pop("queue_wait_s") > 0
    assert st == {"steps": 4, "prefills": 3, "slot_busy_ticks": 7,
                  "queued_ticks": 2, "prefill_tokens": sum(n),
                  "prefill_padded_tokens": 16 + 32 + 16,
                  # a dense model routes nothing
                  "moe_pairs": 0, "moe_experts_hit": 0,
                  "moe_prefill_pairs": 0}


def test_queue_wait_matches_spans(traced):
    """``queue_wait_s`` is the sum over requests of admit start minus
    submit start, as the trace reads them, to 0.1 ms a request."""
    _, counted, events, _ = traced
    submit = {e[3]["rid"]: e[1] for e in _named(events, "batcher.submit")}
    waits = {e[3]["rid"]: e[1] - submit[e[3]["rid"]]
             for e in _named(events, "batcher.admit")}
    # C waits for A's slot: two decode steps longer than A and B
    assert waits[12] > waits[10] and waits[12] > waits[11]
    assert counted["queue_wait_s"] == pytest.approx(sum(waits.values()),
                                                    abs=3e-4)

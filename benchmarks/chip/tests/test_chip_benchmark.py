"""CPU tests of the chip benchmark: the reference against the program at a
small size, the trace reducer on a recorded TPU trace, the operation and
byte counts against hand-worked values, the traffic generator, the open
loop's due-time accounting, a rehearsal of each serving cell through the engine's own
functions, and the faults and the control that ``correct`` has to catch.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
ROOT = CHIP.parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax
import jax.numpy as jnp

from benchmarks.chip import counts, harness, peaks, stats, tracereduce, \
    traffic, weights
from benchmarks.chip.engines import batcher as eng_batcher
from benchmarks.chip.reference import qwen2 as ref

FIXTURE = HERE / "data" / "fixture.xplane.pb"
PEAK = peaks.PEAKS["TPU v5 lite"]
SMOKE_SIZES = dict(hidden_size=64, intermediate_size=128,
                   num_attention_heads=4, num_key_value_heads=2,
                   vocab_size=256, torch_dtype="float32")


def _config(name: str, **over) -> dict:
    m = json.loads((CHIP / "configs" / f"{name}.json").read_text())
    m.update(SMOKE_SIZES, **over)
    return m


@pytest.fixture
def smoke_arch(monkeypatch):
    """Serve ``qwen2-7b`` at the repository's smoke size (float32)."""
    from repro.configs import base

    small = base.smoke_config("qwen2-7b")
    orig = base.get_arch
    monkeypatch.setattr(base, "get_arch",
                        lambda n: small if n == "qwen2-7b" else orig(n))
    return small


SERVING = ["qwen2-7b-d7.chat"]


def _scaled(dist: dict, cap: int) -> dict:
    """``dist`` with its lengths divided so that the longest is ``cap``."""
    top = dist.get("max", dist.get("value"))
    by = -(-top // cap)
    return {k: (max(1, v // by) if k in ("median", "min", "max", "value")
                else v) for k, v in dist.items()}


def _serve_cell(name: str = SERVING[0], rate=None, **check) -> dict:
    """Cell ``name`` at the smoke size: its own traffic file, lengths cut to
    fit 64 positions, 2 layers, 4 slots."""
    m = _config("qwen2-7b-d7", num_hidden_layers=2)
    m["engine"] = dict(m["engine"], slots=4, max_len=64)
    tr = json.loads((CHIP / "traffic" / f"{name}.json").read_text())
    tr.update(warmup_s=0.3, drain_cap_s=10.0,
              prompt=_scaled(tr["prompt"], 32),
              output=_scaled(tr["output"], 16),
              check=dict({"min_tokens": 20, "limit_token_gap": 1e-4},
                         **check))
    if rate is not None:
        tr["rate_per_s"] = rate
    return {"name": name, "chips": 1, "model": m, "engine": m["engine"],
            "traffic": tr}


def _serve(cell, seed=2**31 + 77, controls=()):
    return eng_batcher.run(cell, seed, 2.0, False, PEAK, time.perf_counter(),
                           lambda s: None, controls=controls)


# ------------------------------------------------------------- reference
def test_reference_matches_program_forward(smoke_arch):
    """The plain reference and ``repro.models`` agree in float32 on the
    benchmark's weights: prefill logits and every layer's output."""
    from repro.models import build_model, lm

    m = _config("qwen2-7b-d7", num_hidden_layers=2)
    cfg = eng_batcher.program_config(m, m["engine"])
    layers, head = weights.draw(m, 5, (2,))
    params = eng_batcher.to_program(layers, head)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, 37),
                       jnp.int32)
    want, _ = build_model(cfg).prefill(params, {"tokens": toks[None]}, 64)
    got = ref.logits_at(m, layers, head, toks, jnp.asarray([36]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    h = ref.run_layers(m, layers, ref.embed(head, toks))
    pos = jnp.arange(37)[None]
    x = lm.run_stack(cfg, params["positions"], ref.embed(head, toks)[None],
                     pos)
    np.testing.assert_allclose(np.asarray(x[0]), np.asarray(h), rtol=1e-4,
                               atol=1e-4)


def test_weights_redraw_by_block_bitwise():
    m = _config("qwen2-7b-d7", num_hidden_layers=8)
    whole = jax.jit(lambda k: weights.draw_layers(m, k))(
        weights.layer_keys(9, 0, (4, 2)))
    stage = jax.jit(lambda k: weights.draw_layers(m, k))(
        weights.layer_keys(9, 4, (2,)))
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(stage)):
        np.testing.assert_array_equal(np.asarray(a[2]), np.asarray(b))
    # seeds beyond 32 bits keep their high bits
    assert not np.array_equal(
        np.asarray(weights.layer_keys(7, 0, (1,))),
        np.asarray(weights.layer_keys(7 + 2**32, 0, (1,))))


def test_control_reads_above_the_program():
    """At a small size the program's token gap is 0 (float32 both sides);
    the int8 and fp8 controls put other tokens first."""
    m = _config("qwen2-7b-d7", num_hidden_layers=2, vocab_size=64)
    layers, head = weights.draw(m, 11, (2,))
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 64, 256),
                       jnp.int32)
    rows = jnp.arange(256)
    lg = ref.logits_at(m, layers, head, toks, rows)
    exact = eng_batcher.token_gaps(lg, np.asarray(jnp.argmax(lg, -1)))
    assert exact.max() == 0.0
    for q in ("int8", "fp8"):
        lq = ref.logits_at(m, layers, head, toks, rows, q)
        top = np.asarray(jnp.argmax(lq, -1))
        assert eng_batcher.token_gaps(lg, top).max() > 1e-4, q


# ------------------------------------------------------------ yardstick
def test_counts_hand_worked():
    m = json.loads((CHIP / "configs" / "qwen2-7b-d7.json").read_text())
    # q 3584x3584, k and v 3584x512 each, o 3584x3584, MLP 3 x 3584x18944
    per_layer = 3584 * 3584 * 2 + 3584 * 512 * 2 + 3 * 3584 * 18944
    assert counts.layer_matmul_params(m) == per_layer == 233_046_016
    # one prompt of 1000 tokens: 7 layers, 500500 causal pairs, one head row
    want = (2 * 7 * per_layer * 1000 + 4 * 7 * 3584 * 500_500
            + 2 * 152064 * 3584)
    assert counts.prefill_flops(m, [1000]) == want
    # decode of two sequences holding 10 and 20 positions
    assert counts.decode_flops(m, [10, 20]) == (
        2 * 7 * per_layer * 2 + 4 * 7 * 3584 * 32 + 2 * 2 * 152064 * 3584)
    # bytes: weights (layers with biases and norms, final norm, head) in
    # bf16, two embedding rows, K/V of 11 + 21 positions
    layer_all = per_layer + (3584 + 1024) + 2 * 3584
    weights_b = 2 * (7 * layer_all + 3584 + 152064 * 3584)
    kv_pos = 2 * 2 * 7 * 4 * 128                  # 14336 bytes a position
    assert counts.decode_step_bytes(m, [10, 20]) == (
        weights_b + 2 * 3584 * 2 + kv_pos * 32)
    assert counts.roofline_s(197e12, 0.0, PEAK) == 1.0
    assert counts.roofline_s(0.0, 819e9, PEAK) == 1.0


def test_peaks_refuse_unknown_kind():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


def test_percentile():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(list(range(101)), 95) == 95
    assert stats.percentile([0.0, 10.0], 95) == pytest.approx(9.5)
    assert stats.percentile([5.0, 1.0, 3.0], 0) == 1.0


def test_interval_arithmetic():
    iv = tracereduce.union([(0, 2), (1, 3), (5, 6), (6, 7)])
    assert iv == [(0, 3), (5, 7)]
    assert tracereduce.covered(iv, 1, 6) == 3
    assert tracereduce.gaps(iv, -1, 8) == [(-1, 0), (3, 5), (7, 8)]


def test_reducer_on_recorded_trace():
    """The committed TPU trace (``record_fixture.py`` on a TPU v5e): three
    submits, five steps (the last finds nothing to do), one idle wait."""
    red = tracereduce.reduce(str(FIXTURE), harness.SPANS)
    assert len(red.devices) == 1
    assert red.spans["step"].count == 5
    assert red.spans["submit"].count == 3
    assert red.spans["wait"].count == 1
    spans = sum(s.seconds for s in red.spans.values())
    assert spans <= red.window_s + 1e-9
    # the device was busy only inside the window and mostly inside steps
    assert 0 < red.busy_s() < red.window_s
    assert red.spans["step"].busy_s <= red.busy_s() + 1e-12
    assert red.spans["wait"].seconds >= 0.005
    assert 0 < red.idle_share() < 1
    runs, secs = red.module_time("^jit__lambda$")
    assert runs == 4 and 0 < secs < red.busy_s() + 1e-12    # decode steps
    assert red.module_time("^jit_fn$")[0] == 2          # prefills recorded
    b = tracereduce.breakdown(red)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert any(name == "wait" for name, _ in b["idle_gaps"])
    assert b["idle_gaps"] == sorted(b["idle_gaps"], key=lambda g: -g[1])
    # the reducer's busy time is the union of the device's op events
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(FIXTURE))
    plane = next(p for p in pd.planes if p.name == "/device:TPU:0")
    line = next(ln for ln in plane.lines if ln.name == "XLA Ops")
    lo = min(a for a, _, _ in _spans(pd))
    hi = max(b for _, b, _ in _spans(pd))
    ev = sorted((max(e.start_ns * 1e-9, lo),
                 min((e.start_ns + e.duration_ns) * 1e-9, hi))
                for e in line.events)
    busy, end = 0.0, -1.0
    for a, b in ev:
        if b <= a:
            continue
        if a > end:
            busy += b - a
            end = b
        elif b > end:
            busy += b - end
            end = b
    assert red.busy_s() == pytest.approx(busy, rel=1e-12)


def _spans(pd):
    for p in pd.planes:
        if p.name.startswith("/host:"):
            for ln in p.lines:
                for e in ln.events:
                    if e.name in harness.SPANS:
                        yield (e.start_ns * 1e-9,
                               (e.start_ns + e.duration_ns) * 1e-9, e.name)


# ---------------------------------------------------------------- traffic
def test_traffic_seeded_and_same_work_for_every_seed():
    spec = json.loads(
        (CHIP / "traffic" / "qwen2-7b-d7.chat.json").read_text())
    a = traffic.open_loop(spec, 2**31 + 5, 30.0, 1000)
    b = traffic.open_loop(spec, 2**31 + 5, 30.0, 1000)
    c = traffic.open_loop(spec, 12, 30.0, 1000)
    assert [(p.due, p.max_new, p.prompt.tolist()) for p in a] == \
        [(p.due, p.max_new, p.prompt.tolist()) for p in b]
    win = [p for p in a if p.due >= 0]
    assert len(win) == round(spec["rate_per_s"] * 30.0)
    assert max(p.due for p in win) < 30.0
    key = lambda ps: (sorted(len(p.prompt) for p in ps),
                      sorted(p.max_new for p in ps))
    assert key(a) == key(c)
    assert [p.due for p in a] != [p.due for p in c]
    lens = [len(p.prompt) for p in a]
    assert min(lens) >= 64 and max(lens) <= 2048
    gaps = lambda ps: sorted(np.diff([p.due for p in ps if p.due >= 0]
                                     + [30.0]))
    assert gaps(a) == pytest.approx(gaps(c))


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class _FakeBatcher:
    """One token per live request per step; each step costs ``dt`` on the
    fake clock, and the step at ``stall_at`` costs ``stall`` more."""

    def __init__(self, clock, dt=0.01, stall_at=-1, stall=0.0):
        self.clock, self.dt = clock, dt
        self.stall_at, self.stall = stall_at, stall
        self.live, self.steps = [], 0

    def submit(self, r):
        self.live.append(r)

    def step(self):
        self.clock.t += self.dt + (self.stall if self.steps == self.stall_at
                                   else 0.0)
        self.steps += 1
        for r in self.live:
            r.out.append(0)
            r.done = len(r.out) >= r.max_new
        self.live = [r for r in self.live if not r.done]


def test_open_loop_times_from_due_under_a_stall():
    from repro.serve.scheduler import Request

    plan = [traffic.Planned(i, due, np.zeros(4, np.int32), 3)
            for i, due in enumerate([-0.05, 0.0, 0.1, 0.12, 0.15, 0.9])]
    clock = _FakeClock()
    fb = _FakeBatcher(clock, dt=0.01, stall_at=5, stall=0.5)
    served, t_open = harness.drive_open_loop(
        fb, lambda p: Request(rid=p.rid, prompt=p.prompt, max_new=p.max_new),
        plan, 1.0, 0.05, 5.0, harness.Tracer(False), clock=clock,
        sleep=clock.sleep)
    assert t_open == pytest.approx(0.05)
    assert all(s.finished for s in served) and len(served) == len(plan)
    by = {s.plan.rid: s for s in served}
    # requests 2..4 fall due during the 0.5 s stall: each is submitted
    # after it and its first token is timed from its due time
    for rid in (2, 3, 4):
        s = by[rid]
        assert s.submitted > s.plan.due + 0.3
        assert s.times[0] - s.plan.due > 0.3
    assert by[5].times[0] - by[5].plan.due < 0.05
    assert all(len(s.times) == 3 for s in served)


# ------------------------------------------------------------- rehearsals
@pytest.mark.parametrize("name", SERVING)
def test_serving_cell_rehearsal(smoke_arch, name):
    """Each serving cell's own loop at the smoke size: every request due in
    the window is followed to its last token and judged correct; a second
    run of the seed reads the same."""
    cell = _serve_cell(name)
    out = _serve(cell)
    assert out.correct, out.compared
    assert out.attempted == round(cell["traffic"]["rate_per_s"] * 2.0)
    assert out.failed == 0
    assert out.compared["token_gap"]["value"] == 0.0
    assert {"setup_s", "ttft_p90_ms", "itl_p99_ms"} <= set(out.e2e)
    again = _serve(cell)
    assert again.compared == out.compared


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_serving_faults_fail(smoke_arch, monkeypatch, fault):
    from repro.serve import scheduler

    orig_init = scheduler.ContinuousBatcher.__init__

    def init(self, *a, **k):
        orig_init(self, *a, **k)
        dec = self._decode
        if fault == "state_unchanged":
            self._decode = lambda p, c, t: (dec(p, c, t)[0], c)
        elif fault == "half_batch":
            def half(p, c, t):
                lg, c2 = dec(p, c, t)
                n = lg.shape[0] // 2
                return jnp.concatenate([lg[:n], lg[:lg.shape[0] - n]]), c2
            self._decode = half

    monkeypatch.setattr(scheduler.ContinuousBatcher, "__init__", init)
    if fault == "token_altered":
        orig_tok = scheduler.ContinuousBatcher._slot_logits_token
        calls = {"n": 0}

        def tok(self, row):
            calls["n"] += 1
            t = orig_tok(self, row)
            return (t + 1) % len(row) if calls["n"] % 7 == 0 else t
        monkeypatch.setattr(scheduler.ContinuousBatcher,
                            "_slot_logits_token", tok)
    # enough load that every slot serves requests, and many of them checked
    out = _serve(_serve_cell(rate=200.0, min_tokens=150))
    assert not out.correct
    assert out.compared["token_gap"]["value"] > \
        out.compared["token_gap"]["limit"]


def _decode_control(m, seed, prompt, n, quant, length):
    """``n`` tokens decoded greedily by the reference computed in
    ``quant``: the control, put in the program's place."""
    r = eng_batcher
    layers, head = weights.draw(m, seed, (m["num_hidden_layers"],))
    seq = [int(t) for t in prompt]
    for _ in range(n):
        lg = r.reference_logits(ref, m, layers, head, np.asarray(seq),
                                np.asarray([len(seq) - 1]), length, 1, quant)
        seq.append(int(jnp.argmax(lg[0])))
    return seq[len(prompt):]


def test_control_tokens_fail_the_check(smoke_arch, monkeypatch):
    """The served tokens of every checked request replaced by what the fp8
    control decodes from the same prompt: ``correct`` comes out false."""
    orig = eng_batcher.check
    swapped = []

    def check(m, seed, picked, limit, max_len, rows, controls=()):
        for s in picked:
            s.req.out[:] = _decode_control(m, seed, s.plan.prompt,
                                           len(s.req.out), "fp8", max_len)
            swapped.append(s.plan.rid)
        return orig(m, seed, picked, limit, max_len, rows, controls)

    monkeypatch.setattr(eng_batcher, "check", check)
    out = _serve(_serve_cell())
    assert swapped
    assert not out.correct
    assert out.compared["token_gap"]["value"] > \
        out.compared["token_gap"]["limit"]


def test_sample_covers_every_prefill_bucket():
    """One request from each bucket the window finished in, the longest
    among them, then more until the floor of served tokens is met."""
    from repro.serve.scheduler import Request

    rng = np.random.default_rng(3)
    lens = [70, 90, 200, 300, 600, 700, 1500, 2000, 2048, 1100]
    served = []
    for i, n in enumerate(lens):
        p = traffic.Planned(i, float(i), np.zeros(n, np.int32), 20 + i)
        s = harness.Served(p, Request(rid=i, prompt=p.prompt,
                                      max_new=p.max_new))
        s.times = [0.0] * p.max_new
        served.append(s)
    late = traffic.Planned(99, -1.0, np.zeros(40, np.int32), 5)
    warm = harness.Served(late, None, times=[0.0] * 5)   # warm-up: not due
    for seed in (1, 2**31 + 9):
        pick = eng_batcher.sample(served + [warm], seed, 0)
        buckets = eng_batcher.bucket_set([len(s.plan.prompt) for s in pick])
        assert buckets == [128, 256, 512, 1024, 2048]
        assert len(pick) == 5 and pick[0] is served[8]
        more = eng_batcher.sample(served, seed, 200)
        assert sum(s.plan.max_new for s in more) >= 200
        assert len({id(s) for s in more}) == len(more)
    assert eng_batcher.pad_length(300, 4096) == 512
    assert eng_batcher.pad_length(2561, 4096) == 4096
    assert eng_batcher.pad_length(10, 64) == 64


# ---------------------------------------------------------------- command
def test_command_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(CHIP / "run.py"), "--workload",
                        "qwen2-7b-d7.chat", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], env=env, capture_output=True,
                       text=True, timeout=300, cwd=str(ROOT))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_file_finds_everything_by_name():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        assert (CHIP / "traffic" / f"{w['name']}.json").is_file()
        conf = next(c for c in bench["configs"] if c["name"] == w["config"])
        model = json.loads((ROOT / conf["file"]).read_text())
        assert (CHIP / "engines" / f"{model['engine']['kind']}.py").is_file()
        assert (CHIP / "reference" / f"{model['reference']}.py").is_file()
        assert sorted(model["reduced"]) == sorted(conf["reduced"])
    for m in bench["per_layer"]:
        assert (CHIP / "metrics" / f"{m['name']}.py").is_file()
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))


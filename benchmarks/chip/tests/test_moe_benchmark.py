"""CPU tests of the mixture-of-experts cell's benchmark files: the
reference against ``repro.models.reference`` and the program at a small
size of the configuration's shape, the weight draw's shares, the counts
against hand-worked values, the two ``moe_gemm`` readers on a synthetic
trace reduction with the engine's own counts, and a rehearsal of the cell
through the engine.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q \
        benchmarks/chip/tests/test_moe_benchmark.py
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time
import types
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
ROOT = CHIP.parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax
import jax.numpy as jnp

from benchmarks.chip import harness, moe_counts, peaks, tracereduce, traffic
from benchmarks.chip.engines import moe_batcher as eng
from benchmarks.chip.reference import qwen3_moe as ref

CELL = "qwen3-moe-235b-d8-e16.chat"
CONFIG = "qwen3-moe-235b-d8-e16"
PEAK = peaks.PEAKS["TPU v5 lite"]
# the configuration's shape at a small width: GQA 16:1, head_dim 16,
# 16 experts top-8 of which experts 4-7 are held, float32
SMALL = dict(hidden_size=64, moe_intermediate_size=32, num_attention_heads=16,
             num_key_value_heads=1, head_dim=16, vocab_size=256,
             num_hidden_layers=2, num_experts=4, torch_dtype="float32",
             expert_share={"first": 4, "held": 4, "of": 16})


def _config(**over) -> dict:
    m = json.loads((CHIP / "configs" / f"{CONFIG}.json").read_text())
    m.update(SMALL, **over)
    return m


def _small_arch(m: dict):
    from repro.configs.base import expert_share, get_arch

    base = get_arch("qwen3-moe-235b-a22b")
    share = m["expert_share"]
    cfg = dataclasses.replace(
        base, n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        head_dim=m["head_dim"], vocab_size=m["vocab_size"],
        param_dtype=m["torch_dtype"], compute_dtype=m["torch_dtype"],
        fsdp=False, moe=dataclasses.replace(
            base.moe, n_experts=share["of"], d_ff=m["moe_intermediate_size"]))
    return expert_share(cfg, share["first"], share["held"])


@pytest.fixture
def small_arch(monkeypatch):
    """Serve the configuration's registered architecture at the small
    size."""
    from repro.configs import base

    m = _config()
    small = _small_arch(m)
    orig = base.get_arch
    name = m["engine"]["arch"]
    monkeypatch.setattr(base, "get_arch",
                        lambda n: small if n == name else orig(n))
    return small


# ------------------------------------------------------------- reference
def test_reference_matches_program_and_its_reference(small_arch):
    """The benchmark's reference, the program's own float32 reference and
    the program's prefill agree on the benchmark's weights."""
    from repro.models import build_model, reference

    m = _config()
    cfg = eng.program_config(m, m["engine"])
    layers, head = eng.draw(m, 5)
    params = eng.to_program(layers, head)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, 64),
                       jnp.int32)
    got = ref.logits_at(m, layers, head, toks, jnp.arange(64))
    want = reference.logits(cfg, params, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    last, _ = build_model(cfg).prefill(params, {"tokens": toks[None]}, 64)
    np.testing.assert_allclose(np.asarray(last[0]), np.asarray(got[-1]),
                               rtol=1e-4, atol=1e-5)


def test_control_reads_above_the_program():
    """The fp8 and int8 controls put other tokens first."""
    m = _config(vocab_size=64)
    layers, head = eng.draw(m, 11)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 64, 256),
                       jnp.int32)
    rows = jnp.arange(256)
    lg = ref.logits_at(m, layers, head, toks, rows)
    for q in ("int8", "fp8"):
        top = np.asarray(jnp.argmax(ref.logits_at(m, layers, head, toks,
                                                  rows, q), -1))
        assert eng.token_gaps(lg, top).max() > 1e-4, q


def test_a_share_draws_the_experts_of_the_whole_layer():
    """Expert e draws from its own key: the share holding experts 4-7 holds
    what the layer holding all 16 holds at 4-7, and the same shared
    weights."""
    share = eng.draw(_config(), 3)[0]
    whole = eng.draw(_config(num_experts=16, expert_share={
        "first": 0, "held": 16, "of": 16}), 3)[0]
    for name, a in share.items():
        b = whole[name][:, 4:8] if name.startswith("w_") else whole[name]
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------- yardstick
def test_counts_hand_worked():
    m = json.loads((CHIP / "configs" / f"{CONFIG}.json").read_text())
    d, f, v = 4096, 1536, 151936
    # q 4096x8192, k and v 4096x512 each, o 8192x4096, router 4096x128
    attn = 4096 * 8192 * 2 + 4096 * 512 * 2 + 4096 * 128
    assert moe_counts.attention_params(m) == attn == 71_827_456
    assert moe_counts.expert_params(m) == 3 * d * f == 18_874_368
    # one prompt of 1000 tokens routing 1000 pairs: 8 layers, 500500
    # causal pairs, one head row, 6*d*f a pair
    assert moe_counts.prefill_flops(m, [1000], 1000) == (
        2 * 8 * attn * 1000 + 4 * 8 * 8192 * 500_500 + 2 * v * d
        + 6 * d * f * 1000)
    # decode of contexts 10 and 20 routing 5 pairs
    assert moe_counts.decode_flops(m, [10, 20], 5) == (
        2 * 8 * attn * 2 + 4 * 8 * 8192 * 32 + 2 * 2 * v * d
        + 6 * d * f * 5)
    # bytes: 8 layers of attention, router and norms, 3 experts hit, final
    # norm and head, 2 embedding rows, K/V of 11 + 21 positions
    shared = 2 * (attn + 2 * d + 2 * 128)
    kv_pos = 2 * 2 * 8 * 4 * 128                    # 16384 bytes a position
    assert moe_counts.decode_step_bytes(m, [10, 20], 3) == (
        8 * shared + 2 * 3 * d * f * 3 + 2 * (d + v * d) + 2 * d * 2
        + kv_pos * 32)
    assert moe_counts.gemm_bytes(m, 5, 3) == 2 * (3 * 3 * d * f
                                                  + 5 * 3 * (d + f))


def _served(prompt: int, tokens: int) -> harness.Served:
    p = traffic.Planned(0, 0.0, np.zeros(prompt, np.int32), 100)
    return harness.Served(p, None, times=[0.0] * tokens)


def test_readers_on_a_synthetic_trace():
    """``moe_gemm_ms`` sums the decode program's ``moe_gmm`` operations per
    run of the program, leaving out other programs' and other operations;
    ``moe_gemm_roofline`` divides the engine's counted least time by it."""
    from benchmarks.chip import run

    m = json.loads((CHIP / "configs" / f"{CONFIG}.json").read_text())
    fake = types.SimpleNamespace(last_load={
        "moe_pairs": 80, "moe_experts_hit": 60, "moe_prefill_pairs": 0})
    counter = eng.StepCounter(m, PEAK, fake)
    for _ in range(4):
        counter([_served(500, 3), _served(900, 2)])
    c = counter.totals()
    assert c["steps"] == 4 and c["moe_pairs"] == 320
    least = max(moe_counts.expert_flops(m, 80) / PEAK["bf16_flop_per_s"],
                moe_counts.gemm_bytes(m, 80, 60) / PEAK["hbm_bytes_per_s"])
    assert c["moe_gemm_roofline_s"] == pytest.approx(4 * least)
    ops = {"jit_decode_step/moe_gmm.31": 0.009,
           "jit_decode_step/moe_gmm": 0.003,
           "jit_decode_step/fusion.3": 0.05,
           "jit_prefill/moe_gmm.7": 0.5}
    dev = tracereduce.DeviceStats("/device:TPU:0", 0.1,
                                  {"jit_decode_step": (4, 0.08),
                                   "jit_prefill": (1, 0.5)}, ops, [])
    ctx = types.SimpleNamespace(
        model=m, peak=PEAK, counts=c, programs=m["engine"]["programs"],
        reduced=tracereduce.Reduced(1.0, [dev], {}))
    ms = run.load_reader("moe_gemm_ms")(ctx)
    assert ms == pytest.approx(1e3 * 0.012 / 4)
    share = run.load_reader("moe_gemm_roofline")(ctx)
    assert share == pytest.approx(100 * least / (0.012 / 4))
    assert 0 < share <= 100
    # a program without the kernel reads nothing
    del ops["jit_decode_step/moe_gmm.31"], ops["jit_decode_step/moe_gmm"]
    assert run.load_reader("moe_gemm_ms")(ctx) is None
    assert run.load_reader("moe_gemm_roofline")(ctx) is None


# ------------------------------------------------------------- rehearsal
def _scaled(dist: dict, cap: int) -> dict:
    top = dist.get("max", dist.get("value"))
    by = -(-top // cap)
    return {k: (max(1, v // by) if k in ("median", "min", "max", "value")
                else v) for k, v in dist.items()}


def _cell(rate=6.0, **check) -> dict:
    m = _config()
    m["engine"] = dict(m["engine"], slots=4, max_len=64)
    tr = json.loads((CHIP / "traffic" / f"{CELL}.json").read_text())
    tr.update(warmup_s=0.3, drain_cap_s=10.0, rate_per_s=rate,
              prompt=_scaled(tr["prompt"], 32),
              output=_scaled(tr["output"], 16),
              check=dict({"min_tokens": 20, "limit_token_gap": 1e-4,
                          "limit_token_gap_mean": 1e-5}, **check))
    return {"name": CELL, "chips": 1, "model": m, "engine": m["engine"],
            "traffic": tr}


def test_cell_rehearsal(small_arch):
    """The cell's own loop at the small size: every request due in the
    window is followed to its last token and judged correct, with the
    routing counted in every traced step; the controls read above the
    limit."""
    cell = _cell()
    logged = []
    out = eng.run(cell, 2**31 + 77, 2.0, False, PEAK, time.perf_counter(),
                  logged.append, controls=("fp8",))
    assert out.correct, out.compared
    assert out.failed == 0 and out.attempted == 12
    assert out.compared["token_gap"]["value"] < 1e-4
    assert out.compared["token_gap_mean"]["value"] < 1e-5
    # the control in the program's place fails the same judgement
    assert out.controls["fp8"] > out.compared["token_gap_mean"]["limit"]
    assert any(line.startswith("control fp8:") and
               line.endswith("correct False") for line in logged), logged
    assert {"setup_s", "ttft_p90_ms", "itl_p99_ms"} <= set(out.e2e)


def test_judge_holds_every_number_to_its_limit():
    gaps = np.asarray([0.0, 0.0, 0.3, 0.1])
    got = eng.gap_numbers(gaps, {"limit_token_gap": 0.35,
                                 "limit_token_gap_mean": 0.05})
    assert got["token_gap"]["value"] == pytest.approx(0.3)
    assert got["token_gap_mean"]["value"] == pytest.approx(0.1)
    assert not eng.judge(got)
    got["token_gap_mean"]["limit"] = 0.1
    assert eng.judge(got)
    assert not eng.judge(dict(got, tokens_checked={
        "value": 10, "limit": 300, "at_least": True}))


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_serving_faults_fail(small_arch, monkeypatch, fault):
    """Faults planted in the batcher's decode step fail the cell: the cache
    left as it was, half the slots served the other half's logits, and
    every seventh token altered."""
    from repro.serve import scheduler

    orig_init = scheduler.ContinuousBatcher.__init__

    def init(self, *a, **k):
        orig_init(self, *a, **k)
        dec = self._decode
        if fault == "state_unchanged":
            def kept(p, c, t, live):
                old = jax.tree.map(jnp.copy, c)     # the step donates c
                lg, _, load = dec(p, c, t, live)
                return lg, old, load
            self._decode = kept
        elif fault == "half_batch":
            def half(p, c, t, live):
                lg, c2, load = dec(p, c, t, live)
                n = lg.shape[0] // 2
                return (jnp.concatenate([lg[:n], lg[:lg.shape[0] - n]]), c2,
                        load)
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
    out = eng.run(_cell(rate=200.0, min_tokens=150), 2**31 + 78, 1.0, False,
                  PEAK, time.perf_counter(), lambda s: None)
    assert not out.correct
    assert out.compared["token_gap"]["value"] > \
        out.compared["token_gap"]["limit"]


def test_program_config_refuses_a_file_that_differs(small_arch):
    m = _config()
    with pytest.raises(ValueError):
        eng.program_config(dict(m, expert_share={"first": 0, "held": 4,
                                                 "of": 16}), m["engine"])
    with pytest.raises(ValueError):
        eng.program_config(dict(m, norm_topk_prob=False), m["engine"])


def test_benchmark_names_the_new_cell():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    for name in ("moe_gemm_ms", "moe_gemm_roofline"):
        metric = next(x for x in bench["per_layer"] if x["name"] == name)
        assert metric["workloads"] == [CELL]
        assert (CHIP / "metrics" / f"{name}.py").is_file()

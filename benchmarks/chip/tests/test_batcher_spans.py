"""CPU tests of what the benchmark reads from the batcher's own spans and
named programs, on a second recorded TPU trace (``record_fixture.py`` on a
TPU v5e, after the batcher gained ``scheduler.SPANS`` and the program names
``jit_decode_step``, ``jit_prefill`` and ``jit_insert_slot``), beside the
first one, recorded before.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""

from __future__ import annotations

import importlib.util
import json
import math
import sys
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
ROOT = CHIP.parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import harness, tracereduce

from repro.serve import scheduler

OLD = HERE / "data" / "fixture.xplane.pb"
NEW = HERE / "data" / "fixture_spans.xplane.pb"
# the requests record_fixture.py submits inside its trace: rid, prompt length
RECORDED = {1: 14, 2: 19, 3: 24}
ALIGN_S = 0.5e-3            # host and device clocks agree to this in a trace


def _programs() -> dict:
    m = json.loads((CHIP / "configs" / "qwen2-7b-d7.json").read_text())
    return m["engine"]["programs"]


def _read(metric: str, red):
    path = CHIP / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    ctx = types.SimpleNamespace(reduced=red, programs=_programs(), counts={},
                                model=None, peak=None)
    return mod.read(ctx)


@pytest.fixture(scope="module")
def reduced():
    return {name: tracereduce.reduce(str(path), harness.SPANS)
            for name, path in (("old", OLD), ("new", NEW))}


def _host_events(path, names):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
             dict(e.stats))
            for p in pd.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events if e.name in names]


def test_program_names_in_new_trace(reduced):
    """The recorded programs carry the batcher's stable names, which the
    configuration's patterns find as they found the old ones."""
    old = set(reduced["old"].devices[0].modules)
    new = set(reduced["new"].devices[0].modules)
    assert {"jit__lambda", "jit_fn"} <= old
    assert {"jit_decode_step", "jit_prefill", "jit_insert_slot"} <= new
    assert not {"jit__lambda", "jit_fn"} & new
    # the patterns find a decode run a step and a prefill run an admission
    progs = _programs()
    assert reduced["new"].module_time(progs["decode"])[0] == 4
    assert reduced["new"].module_time(progs["prefill"])[0] == len(RECORDED)


@pytest.mark.parametrize("metric", ["decode_step_ms", "host_ms_per_step",
                                    "idle.decode", "insert_ms"])
def test_trace_readers_on_new_trace(reduced, metric):
    v = _read(metric, reduced["new"])
    assert v is not None and math.isfinite(v) and v > 0, (metric, v)


def test_insert_ms_reads_only_the_named_insert(reduced):
    """The eager insert of the old trace has no program of that name: the
    reader returns nothing there rather than some other program's time."""
    assert _read("insert_ms", reduced["old"]) is None
    runs, secs = reduced["new"].module_time("^jit_insert_slot$")
    assert runs == len(RECORDED)
    assert _read("insert_ms", reduced["new"]) == pytest.approx(
        1e3 * secs / runs)


def test_batcher_spans_in_new_trace():
    """Every span of ``scheduler.SPANS`` is in the trace, as often as the
    recorder's script makes it, nested in the harness span that calls the
    batcher; each admit carries its request's bucket and true length."""
    ev = _host_events(NEW, set(scheduler.SPANS) | set(harness.SPANS))
    count = {n: sum(e[0] == n for e in ev) for n in scheduler.SPANS}
    assert count == {"batcher.submit": 3, "batcher.admit": 3,
                     "batcher.decode": 4, "batcher.fetch": 4,
                     "batcher.sample": 4}
    outer = {"batcher.submit": "submit"}
    for name, a, b, _ in ev:
        if name in scheduler.SPANS:
            host = outer.get(name, "step")
            assert any(o[0] == host and o[1] <= a and b <= o[2]
                       for o in ev), name
    admits = {st["rid"]: st for n, _, _, st in ev if n == "batcher.admit"}
    assert {r: a["n"] for r, a in admits.items()} == RECORDED
    assert all(a["bucket"] == scheduler._buckets(a["n"])
               for a in admits.values())
    assert sorted(st["rid"] for n, _, _, st in ev
                  if n == "batcher.submit") == sorted(RECORDED)


def _device_runs(path, module):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    plane = next(p for p in pd.planes if p.name == "/device:TPU:0")
    line = next(ln for ln in plane.lines if ln.name == "XLA Modules")
    return sorted(e.start_ns * 1e-9 for e in line.events
                  if tracereduce._module_name(e.name) == module)


@pytest.mark.parametrize("span,module", [
    ("batcher.admit", "jit_prefill"),
    ("batcher.admit", "jit_insert_slot"),
    ("batcher.decode", "jit_decode_step"),
])
def test_device_runs_start_inside_their_span(span, module):
    """On the trace's one clock, the k-th run of a program on the device
    starts inside the k-th host span that dispatches it, give or take how
    well the device's clock is aligned with the host's (the decode runs
    read up to 0.15 ms before their span opens)."""
    spans = sorted((a, b) for _, a, b, _ in _host_events(NEW, {span}))
    runs = _device_runs(NEW, module)
    assert len(runs) == len(spans)
    assert all(a - ALIGN_S <= r <= b for (a, b), r in zip(spans, runs))

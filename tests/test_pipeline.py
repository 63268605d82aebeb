"""Polyhedral pipeline (core/pipeline.py): schedules derived from the
Appendix-A automata match a brute-force earliest-start oracle, and the
shard_map execution matches the sequential reference.

The execution test needs >1 device, so it runs in a subprocess with
``--xla_force_host_platform_device_count`` (tests themselves must see 1
device; only subprocesses force more).
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
pytest.importorskip("hypothesis")  # gated: optional test dep
from hypothesis import given, settings, strategies as st

from repro.core import pipeline


@settings(max_examples=30, deadline=None)
@given(
    kinds=st.lists(st.sampled_from(pipeline.EDGE_KINDS), min_size=1,
                   max_size=4),
    n_items=st.integers(1, 8),
)
def test_schedule_matches_bruteforce(kinds, n_items):
    """Three-way: the vectorized frontier-table schedule == the generated
    LCU automata schedule == the explicit-dependency brute force."""
    sched = pipeline.derive_schedule(kinds, n_items)
    want = pipeline.reference_schedule_bruteforce(kinds, n_items)
    np.testing.assert_array_equal(sched.start, want)
    automata = pipeline.derive_schedule_automata(kinds, n_items)
    np.testing.assert_array_equal(automata.start, want)
    np.testing.assert_array_equal(sched.table, automata.table)


def test_pointwise_schedule_is_classic_pipeline():
    """Pointwise edges: stage s starts item t at tick t + s (skew 1)."""
    sched = pipeline.derive_schedule(["pointwise"] * 3, 6)
    for s in range(4):
        for t in range(6):
            assert sched.start[s, t] == t + s
    # steady state: all stages busy -> utilization n/(n+S-1)
    assert sched.utilization() == pytest.approx(6 * 4 / (4 * 9))


def test_full_schedule_degenerates_to_layer_at_a_time():
    """A bidirectional (encoder) edge forces wait-for-last-write."""
    sched = pipeline.derive_schedule(["full"], 4)
    # stage 1 cannot start any item before stage 0 finished item 3 (tick 3)
    assert sched.start[1, 0] == 4
    assert (sched.start[1] == np.arange(4) + 4).all()


def test_causal_schedule_skew():
    """Causal edge: consumer item t needs producer items <= t — same
    frontier as pointwise for a 1-item-per-tick producer."""
    sched = pipeline.derive_schedule(["causal"], 5)
    assert (sched.start[1] == np.arange(5) + 1).all()


def test_makespan_advantage():
    """Pipelined makespan n+S-1 << sequential n*S for deep pipelines."""
    kinds = ["pointwise"] * 7
    n = 16
    sched = pipeline.derive_schedule(kinds, n)
    assert sched.n_ticks == n + 7
    assert sched.n_ticks < n * 8 / 3


_EXEC_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import pipeline

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("stage",))
    n_stages, n_items, dim = 4, 6, 16
    rng = np.random.default_rng(0)
    W = jnp.asarray(rng.normal(size=(n_stages, dim, dim)) / np.sqrt(dim),
                    jnp.float32)
    xs = jnp.asarray(rng.normal(size=(n_items, dim)), jnp.float32)

    def fn(w, x):
        return jnp.tanh(x @ w)

    sched = pipeline.derive_schedule(["pointwise"] * (n_stages - 1), n_items)
    out = pipeline.pipeline_apply([fn] * n_stages, W, xs, sched, mesh)
    want = pipeline.sequential_apply([fn] * n_stages, W, xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    print("PIPELINE_EXEC_OK", sched.n_ticks)
""")


def test_pipeline_execution_matches_sequential():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", _EXEC_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=300)
    assert "PIPELINE_EXEC_OK" in r.stdout, r.stdout + r.stderr

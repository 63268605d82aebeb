"""The paper's dependency compiler driving TPU pipeline parallelism.

Derives pipeline schedules from the Appendix-A ``S`` automata for all three
edge kinds (pointwise / causal / full), prints the schedule tables, then
executes a 4-stage pipeline under shard_map + ppermute and checks it against
the sequential reference.

Run:  PYTHONPATH=src python examples/poly_pipeline.py
(forces 4 host devices; run as its own process)
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402

from repro.core import pipeline  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


def show(kinds, n_items):
    sched = pipeline.derive_schedule(kinds, n_items)
    print(f"edges={kinds} items={n_items} -> makespan {sched.n_ticks} ticks,"
          f" utilization {sched.utilization():.2f}")
    for s, row in enumerate(sched.table):
        cells = " ".join(f"{v:2d}" if v >= 0 else " ." for v in row)
        print(f"  stage{s}: {cells}")


def main():
    print("== schedules derived from the Appendix-A automata ==")
    show(["pointwise"] * 3, 8)      # classic 1-deep pipeline (skew 1/stage)
    show(["causal"] * 3, 8)         # causal attention chunks: same skew
    show(["full", "pointwise"], 6)  # encoder edge degenerates to barrier

    print("\n== execution on a 4-device stage mesh ==")
    mesh = make_mesh((4,), ("stage",))
    n_stages, n_items, dim = 4, 8, 64
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(n_stages, dim, dim)) / np.sqrt(dim),
                    jnp.float32)
    xs = jnp.asarray(rng.normal(size=(n_items, dim)), jnp.float32)
    fn = lambda p, x: jnp.tanh(x @ p)

    sched = pipeline.derive_schedule(["pointwise"] * (n_stages - 1), n_items)
    out = pipeline.pipeline_apply([fn] * n_stages, w, xs, sched, mesh)
    want = pipeline.sequential_apply([fn] * n_stages, w, xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    print("pipelined output == sequential reference "
          f"(makespan {sched.n_ticks} ticks vs {n_stages * n_items} "
          "sequential) — OK")


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Record the small trace that ``tests/test_chip_benchmark.py`` reduces:
a smoke-size qwen2 batcher stepped a few times on one TPU under the
harness's spans and profiler options, with one idle wait.

    python3 benchmarks/chip/record_fixture.py <out.xplane.pb>
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

import run  # noqa: F401  (benchmarks/chip/run.py puts the repo on the path)

import numpy as np

from benchmarks.chip import harness, tracereduce


def main(out: str) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    if jax.devices()[0].platform != "tpu":
        return 1
    from repro.configs.base import smoke_config
    from repro.serve.scheduler import ContinuousBatcher, Request

    b = ContinuousBatcher(smoke_config("qwen2-7b"), n_slots=4, max_len=64,
                          seed=1)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, max_new=4, prompt=rng.integers(
        0, 256, 9 + 5 * i).astype(np.int32)) for i in range(4)]
    b.submit(reqs[0])
    b.run_until_drained()                      # compile outside the trace
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp, profiler_options=harness.profile_options())
    for r in reqs[1:]:
        with jax.profiler.TraceAnnotation("submit"):
            b.submit(r)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("step"):
            b.step()
    with jax.profiler.TraceAnnotation("wait"):
        time.sleep(0.005)
    for _ in range(2):
        with jax.profiler.TraceAnnotation("step"):
            b.step()
    jax.profiler.stop_trace()
    shutil.copy(glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb")[0], out)
    shutil.rmtree(tmp)
    red = tracereduce.reduce(out, harness.SPANS)
    print(red.window_s, red.busy_s(), red.spans, red.devices[0].modules)
    print(tracereduce.breakdown(red))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

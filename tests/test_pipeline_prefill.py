"""Pipelined prefill (launch/pipeline_prefill.py): executing the 2-stage
pod pipeline produces the same last-token hidden states as a sequential
full-stack forward (subprocess, 4 host devices, (2 pod, 1 data, 2 model)
and (2 pod, 2 data, 1 model), the latter with the activation constraint on a
data axis that really splits the batch)."""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import smoke_config
from repro.models import lm
from repro.launch.pipeline_prefill import (make_pipelined_prefill,
                                           stage_config)

cfg = smoke_config("llama3.2-3b")
cfg = dataclasses.replace(cfg, n_layers=4, q_chunk=8)
from repro.launch.mesh import make_mesh
mesh = make_mesh({shape}, ("pod", "data", "model"))

seq_len, batch, n_micro = 16, 4, 2
b_m = batch // n_micro
rng = np.random.default_rng(0)
tokens = rng.integers(0, cfg.vocab_size,
                      (n_micro, b_m, seq_len)).astype(np.int32)

params = lm.init_lm(cfg, jax.random.key(0))
# stage split: periods [0..1] -> stage 0, [2..3] -> stage 1
n_stages = 2
stage_params = jax.tree.map(
    lambda l: l.reshape((n_stages, l.shape[0] // n_stages) + l.shape[1:]),
    params["positions"])
embed = params["embed"][None]

fn, sds, in_sh, sched = make_pipelined_prefill(cfg, mesh, n_micro,
                                               seq_len, batch)
with jax.set_mesh(mesh):
    got = jax.jit(fn, in_shardings=in_sh)(stage_params, embed,
                                          jnp.asarray(tokens))

# reference: sequential full-stack forward per microbatch
scfg = cfg
want = []
for m in range(n_micro):
    x = params["embed"][jnp.asarray(tokens[m])]
    pos = jnp.broadcast_to(jnp.arange(seq_len)[None], (b_m, seq_len))
    h = lm.run_stack(scfg, params["positions"], x, pos)
    want.append(np.asarray(h[:, -1, :]))
want = np.stack(want)

np.testing.assert_allclose(np.asarray(got, np.float32),
                           want.astype(np.float32), rtol=2e-4, atol=2e-4)
assert sched.n_ticks == n_micro + n_stages - 1
print("PIPELINE_PREFILL_OK", sched.utilization())
"""


@pytest.mark.parametrize("shape", [(2, 1, 2), (2, 2, 1)])
def test_pipelined_prefill_matches_sequential(shape):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    script = _SCRIPT.replace("{shape}", repr(shape))
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=900)
    assert "PIPELINE_PREFILL_OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-3000:]

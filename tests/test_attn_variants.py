"""Attention layout variants (§Perf pair A/C) are layout-only: under a real
mesh, `attn_shard="seq"` + `causal_bound` must produce the same numbers as
the default layout (subprocess, 8 host devices, (2 data, 4 model) mesh)."""

from __future__ import annotations

import os
import subprocess
import sys

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import smoke_config
from repro.models import build_model
from repro import sharding as sh

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))

def run_arch(arch, extra=None):
    base = smoke_config(arch)
    # seq path needs s % model == 0 and d_ff/vocab divisible by 4: smoke
    # cfgs have d_ff=128, vocab=256, heads 4*16=64 -> all divide 4.
    base = dataclasses.replace(base, q_chunk=8, **(extra or {}))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, base.vocab_size, (4, 32)),
                         jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}
    outs = {}
    for name, ov in {
        "default": {},
        "seq": {"attn_shard": "seq"},
        "seq_causal": {"attn_shard": "seq", "causal_bound": True},
        "seq_causal_unroll": {"attn_shard": "seq", "causal_bound": True,
                              "static_unroll": True},
    }.items():
        cfg = dataclasses.replace(base, **ov)
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        pspecs = sh.param_specs(cfg, params, mesh)
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            params, pspecs, is_leaf=lambda x: hasattr(x, "shape"))
        with jax.set_mesh(mesh):
            loss, metrics = jax.jit(model.loss)(params, batch)
        outs[name] = float(loss)
    ref = outs["default"]
    for name, val in outs.items():
        assert abs(val - ref) < 1e-4, (arch, name, val, ref)
    return outs

run_arch("qwen2-7b")
# MoE: no-drop capacity so per-group dispatch (seq mode re-groups tokens
# into shard-aligned groups) must be numerically identical to default.
import repro.configs.base as cb
moe_cfg = smoke_config("qwen2-moe-a2.7b")
run_arch("qwen2-moe-a2.7b",
         {"moe": dataclasses.replace(moe_cfg.moe, capacity_factor=8.0)})
print("VARIANTS_OK")
"""


def test_attn_variants_match_default():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "VARIANTS_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]

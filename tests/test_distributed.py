"""Distributed-optimization substrate: compression round-trip bounds +
error-feedback convergence (hypothesis), ring all-reduce == psum (4-device
subprocess), elastic mesh planner invariants, accum step == plain step."""

from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest
pytest.importorskip("hypothesis")  # gated: optional test dep
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.distributed import (CompressionSpec, compress_with_feedback,
                               dequantize_blockwise, init_error_feedback,
                               plan_mesh, quantize_blockwise, topk_densify,
                               topk_sparsify)
from repro.configs.base import get_arch


# ------------------------------------------------------------- quantization
@settings(max_examples=40, deadline=None)
@given(n=st.integers(1, 2048), block=st.sampled_from([16, 64, 256]),
       scale=st.floats(1e-3, 1e3), seed=st.integers(0, 2**31 - 1))
def test_int8_roundtrip_error_bound(n, block, scale, seed):
    """|x - dq(q(x))| <= absmax_block / 254 per element (symmetric int8)."""
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal(n) * scale).astype(np.float32)
    q, s = quantize_blockwise(jnp.asarray(x), block)
    back = np.asarray(dequantize_blockwise(q, s, (n,)))
    n_blocks = -(-n // block)
    xpad = np.pad(x, (0, n_blocks * block - n)).reshape(n_blocks, block)
    bound = np.abs(xpad).max(axis=1, keepdims=True) / 254.0 + 1e-7
    err = np.abs(xpad - np.pad(back, (0, n_blocks * block - n)
                               ).reshape(n_blocks, block))
    assert (err <= bound + 1e-6 * np.abs(xpad)).all()


def test_int8_exact_on_zero_and_constant():
    q, s = quantize_blockwise(jnp.zeros(100), 32)
    assert np.asarray(dequantize_blockwise(q, s, (100,))).sum() == 0
    x = jnp.full((64,), 3.5)
    q, s = quantize_blockwise(x, 32)
    np.testing.assert_allclose(dequantize_blockwise(q, s, (64,)), 3.5,
                               rtol=1e-6)


@settings(max_examples=25, deadline=None)
@given(n=st.integers(4, 512), frac=st.floats(0.01, 0.5),
       seed=st.integers(0, 2**31 - 1))
def test_topk_keeps_largest(n, frac, seed):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(n).astype(np.float32)
    v, i = topk_sparsify(jnp.asarray(x), frac)
    dense = np.asarray(topk_densify(v, i, (n,)))
    k = max(1, int(n * frac))
    kept = np.flatnonzero(dense)
    assert len(kept) <= k
    # every kept magnitude >= every dropped magnitude
    if len(kept) and len(kept) < n:
        dropped = np.setdiff1d(np.arange(n), kept)
        assert np.abs(x[kept]).min() >= np.abs(x[dropped]).max() - 1e-6


def test_error_feedback_accumulates_residual():
    """One compressed step leaves residual = x - C(x); the next step's
    compression target includes it (EF21 invariant)."""
    spec = CompressionSpec(kind="topk", topk_frac=0.5)         # k = 2
    g = {"w": jnp.asarray([4.0, 0.3, 0.2, 0.05])}
    ef = init_error_feedback(g)
    c, ef = compress_with_feedback(g, ef, spec)
    np.testing.assert_allclose(np.asarray(c["w"]), [4, 0.3, 0, 0], atol=1e-6)
    np.testing.assert_allclose(np.asarray(ef["w"]), [0, 0, 0.2, 0.05],
                               atol=1e-6)
    # second step: same grads; the residual promotes coord 2 (0.2+0.2=0.4)
    # over coord 1 (0.3) into the top-2
    c2, _ = compress_with_feedback(g, ef, spec)
    np.testing.assert_allclose(np.asarray(c2["w"]), [4, 0, 0.4, 0],
                               atol=1e-6)


@pytest.mark.parametrize("kind", ["int8", "topk"])
def test_ef_sgd_converges_on_quadratic(kind):
    """Compressed SGD with error feedback drives ||x|| to ~0 on f=0.5||x||^2;
    without EF, top-k stalls on the dropped coordinates."""
    spec = CompressionSpec(kind=kind, topk_frac=0.3, block=16,
                           error_feedback=True)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(32) * 5)
    ef = init_error_feedback({"x": x})
    lr = 0.3
    for _ in range(300):
        g = {"x": x}                              # grad of 0.5||x||^2
        c, ef = compress_with_feedback(g, ef, spec)
        x = x - lr * c["x"]
    assert float(jnp.linalg.norm(x)) < 1e-2


def test_wire_bytes_model():
    spec = CompressionSpec(kind="int8", block=256)
    assert spec.wire_bytes(1024) == 1024 + 4 * 4
    spec = CompressionSpec(kind="topk", topk_frac=0.01)
    assert spec.wire_bytes(10_000) == 8 * 100
    assert CompressionSpec(kind="none").wire_bytes(10) == 40


# ------------------------------------------------------------ elastic plans
@settings(max_examples=60, deadline=None)
@given(n=st.integers(1, 600),
       arch=st.sampled_from(["qwen2-7b", "gemma-2b", "qwen3-moe-235b-a22b",
                             "falcon-mamba-7b"]))
def test_plan_mesh_invariants(n, arch):
    cfg = get_arch(arch)
    plan = plan_mesh(n, cfg)
    assert plan.n_used + plan.n_idle == n
    assert plan.n_used == int(np.prod(plan.mesh_shape))
    assert plan.model_axis >= 1 and plan.n_used >= 1
    # model axis really divides the arch's sharded dims
    if cfg.n_heads:
        assert (cfg.n_heads * cfg.hd) % plan.model_axis == 0
    assert cfg.vocab_size % plan.model_axis == 0
    assert len(plan.mesh_shape) == len(plan.axis_names)


def test_plan_mesh_pod_loss():
    """512 -> 448 (lost 2 hosts' worth): keeps model=16, flattens pods."""
    cfg = get_arch("qwen2-7b")
    full = plan_mesh(512, cfg, pod_size=256)
    assert full.n_pods == 2 and full.mesh_shape == (2, 16, 16)
    degraded = plan_mesh(448, cfg, pod_size=256)
    assert degraded.n_used == 448
    assert degraded.model_axis == 16
    assert degraded.n_idle == 0


def test_plan_mesh_batch_divisibility():
    cfg = get_arch("qwen2-7b")
    plan = plan_mesh(48, cfg, global_batch=256)
    d_total = plan.n_used // plan.model_axis
    assert 256 % d_total == 0


# ------------------------------------------- ring allreduce & resharding
_RING_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.distributed import ring_all_reduce

from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("d",))
x = np.arange(4 * 37, dtype=np.float32).reshape(4, 37) * 0.25

for n_chunks in (1, 3):
    def body(xl):
        return ring_all_reduce(xl[0], "d", n_chunks=n_chunks)[None]
    got = jax.shard_map(body, mesh=mesh, in_specs=P("d"),
                        out_specs=P("d"))(x)
    want = x.sum(0)
    for row in np.asarray(got):
        np.testing.assert_allclose(row, want, rtol=1e-6)
print("RING_OK")
"""


def test_ring_allreduce_equals_psum():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", _RING_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=300)
    assert "RING_OK" in r.stdout, r.stdout + r.stderr


_HIER_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.distributed import CompressionSpec, hierarchical_psum

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("pod", "data"))
x = np.random.default_rng(0).standard_normal((8, 64)).astype(np.float32)

spec = CompressionSpec(kind="int8", block=32)
def body(xl):
    return hierarchical_psum(xl[0], fast_axis="data", slow_axis="pod",
                             spec=spec)[None]
got = jax.shard_map(body, mesh=mesh, in_specs=P(("pod", "data")),
                    out_specs=P(("pod", "data")))(x.reshape(8, 1, 64)[:, 0, :])
want = x.sum(0)
# int8 on the pod hop only: error bounded by quantization of 2 pod payloads
err = np.abs(np.asarray(got)[0] - want)
scale = np.abs(x.sum(0)).max() / 127
assert err.max() < 8 * scale, (err.max(), scale)
print("HIER_OK")
"""


def test_hierarchical_psum_compressed():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", _HIER_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=300)
    assert "HIER_OK" in r.stdout, r.stdout + r.stderr


# ------------------------------------------------------- accum train step
def test_accum_step_matches_plain_step():
    """n_micro gradient accumulation == full-batch step (fp32, tiny model)."""
    from repro.configs.base import smoke_config
    from repro.models import build_model
    from repro.train import TrainState, make_train_step
    from repro.distributed import make_accum_train_step
    from repro.optim import adamw_init

    cfg = smoke_config("llama3.2-3b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    opt = adamw_init(params, "float32")
    state = TrainState(params, opt, jnp.zeros((), jnp.int32))

    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)),
                                   jnp.int32)}

    plain = jax.jit(make_train_step(model))
    accum = jax.jit(make_accum_train_step(model, n_micro=4))
    s1, m1 = plain(state, batch)
    s2, m2 = accum(state, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    l1 = jax.tree.leaves(s1.params)
    l2 = jax.tree.leaves(s2.params)
    for a, b in zip(l1, l2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-6)

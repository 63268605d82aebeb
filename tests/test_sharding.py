"""Sharding rules: every spec must be valid (sharded dims divisible by the
mesh axis) for all 10 archs on both production meshes — checked abstractly,
no devices needed — and one real cell lowers and compiles on the multi-pod
mesh (512 host devices, in a subprocess)."""

from __future__ import annotations

import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh, AxisType, PartitionSpec as P

from repro import sharding as sh
from repro.configs import archs
from repro.configs.base import get_arch, SHAPES, shapes_for
from repro.models import build_model

MESHES = {
    "single": AbstractMesh((16, 16), ("data", "model"),
                           axis_types=(AxisType.Auto,) * 2),
    "multi": AbstractMesh((2, 16, 16), ("pod", "data", "model"),
                          axis_types=(AxisType.Auto,) * 3),
}


def _check_divisible(specs, tree, mesh, where):
    def chk(spec, leaf):
        assert len(spec) <= len(leaf.shape), (where, spec, leaf.shape)
        for i, names in enumerate(spec):
            if names is None:
                continue
            names = names if isinstance(names, tuple) else (names,)
            factor = int(np.prod([mesh.shape[n] for n in names]))
            assert leaf.shape[i] % factor == 0, (
                where, spec, leaf.shape, i, factor)
    jax.tree.map(chk, specs, tree, is_leaf=lambda x: isinstance(x, P))


@pytest.mark.parametrize("mesh_name", list(MESHES))
@pytest.mark.parametrize("arch", archs.ALL)
def test_param_and_opt_specs_valid(arch, mesh_name):
    cfg = get_arch(arch)
    mesh = MESHES[mesh_name]
    model = build_model(cfg)
    psds = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    pspecs = sh.param_specs(cfg, psds, mesh)
    _check_divisible(pspecs, psds, mesh, f"{arch}/params")
    mspecs = sh.opt_specs(cfg, pspecs, psds, mesh)
    _check_divisible(mspecs, psds, mesh, f"{arch}/moments")


@pytest.mark.parametrize("arch", archs.ALL)
def test_cache_and_batch_specs_valid(arch):
    cfg = get_arch(arch)
    mesh = MESHES["single"]
    model = build_model(cfg)
    for shape_name in shapes_for(cfg):
        shape = SHAPES[shape_name]
        csds = jax.eval_shape(lambda s=shape: model.init_cache(
            s.global_batch, s.seq_len, s.seq_len))
        cspecs = sh.cache_specs(cfg, csds, mesh)
        _check_divisible(cspecs, csds, mesh, f"{arch}/{shape_name}/cache")


def test_model_axis_actually_used():
    """The big weights must shard over 'model' (not silently replicate)."""
    cfg = get_arch("qwen2-7b")
    mesh = MESHES["single"]
    model = build_model(cfg)
    psds = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    pspecs = sh.param_specs(cfg, psds, mesh)
    flat = {"/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                     for p in path): spec
            for path, spec in
            jax.tree_util.tree_flatten_with_path(
                pspecs, is_leaf=lambda x: isinstance(x, P))[0]}
    assert any("model" in str(s) for s in flat.values())
    assert "model" in str(flat["embed"])
    mlp_specs = [s for k, s in flat.items() if "mlp" in k]
    assert all("model" in str(s) for s in mlp_specs)


def test_zero1_moments_use_data_axis():
    """Non-FSDP archs: ZeRO-1 moments must pick up the 'data' axis."""
    cfg = get_arch("qwen2-7b")
    assert not cfg.fsdp
    mesh = MESHES["single"]
    model = build_model(cfg)
    psds = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    pspecs = sh.param_specs(cfg, psds, mesh)
    mspecs = sh.opt_specs(cfg, pspecs, psds, mesh)
    n_data = sum("data" in str(s) for s in jax.tree.leaves(
        mspecs, is_leaf=lambda x: isinstance(x, P)))
    n_total = len(jax.tree.leaves(mspecs,
                                  is_leaf=lambda x: isinstance(x, P)))
    assert n_data > n_total * 0.5, (n_data, n_total)


def test_long500k_cache_shards_sequence():
    """B=1 at 500k: the KV cache must shard its sequence axis over data."""
    cfg = get_arch("jamba-1.5-large-398b")
    mesh = MESHES["single"]
    model = build_model(cfg)
    csds = jax.eval_shape(lambda: model.init_cache(1, 524_288, 524_288))
    cspecs = sh.cache_specs(cfg, csds, mesh)
    specs = jax.tree.leaves(cspecs, is_leaf=lambda x: isinstance(x, P))
    kv = [s for s in specs if len(s) == 5]
    assert kv and all(s[2] == "data" for s in kv), kv


def test_model_flops_6nd():
    from repro.launch.specs import model_flops
    cfg = get_arch("llama3.2-3b")
    shape = SHAPES["train_4k"]
    want = 6 * cfg.param_count() * shape.global_batch * shape.seq_len
    assert model_flops(cfg, shape) == pytest.approx(want, rel=1e-6)
    moe = get_arch("qwen3-moe-235b-a22b")
    assert moe.active_param_count() < 0.2 * moe.param_count()
    assert model_flops(moe, shape) == pytest.approx(
        6 * moe.active_param_count() * shape.global_batch * shape.seq_len,
        rel=1e-6)


_COMPILE_CELL = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import jax
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import make_cell

mesh = make_production_mesh(multi_pod=True)
cell = make_cell("llama3.2-3b", "decode_32k", mesh)
with jax.set_mesh(mesh):
    jax.jit(cell.fn, in_shardings=cell.in_shardings,
            donate_argnums=cell.donate).lower(*cell.args).compile()
print("COMPILED", cell.kind, dict(mesh.shape))
"""


def test_one_real_cell_compiles_on_512_devices():
    """Subprocess (device count must not leak into this pytest process)."""
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src")
    r = subprocess.run([sys.executable, "-c", _COMPILE_CELL], env=env,
                       capture_output=True, text=True, timeout=900, cwd=root)
    assert "COMPILED decode {'pod': 2, 'data': 16, 'model': 16}" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-2000:]

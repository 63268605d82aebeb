"""chip_smoke.py rehearsed on the CPU: its CM, LM and four-chip phases at
smoke sizes (Pallas in interpret mode), and its refusal to run without a
TPU."""

from __future__ import annotations

import dataclasses
import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _env(**extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.update(extra)
    return env


def test_import_touches_no_device():
    code = ("import chip_smoke\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, xla_bridge._backends\n"
            "print('NO_BACKEND')\n")
    r = subprocess.run([sys.executable, "-c", code], env=_env(), cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert "NO_BACKEND" in r.stdout, r.stdout + r.stderr


def test_refuses_cpu_backend(capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_script_exits_nonzero_without_tpu():
    r = subprocess.run([sys.executable, "chip_smoke.py"], env=_env(),
                       cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_cm_phase_interpret():
    res = chip_smoke.cm_phase(interpret=True, n_requests=8, log=lambda s: None)
    assert set(res) == {"lenet", "tiny_xfmr"}
    for r in res.values():
        assert r["rows_compared"] >= 8
        assert r["max_abs_err"] <= chip_smoke.CM_ATOL


def test_lm_phase_smoke():
    from repro.configs.base import smoke_config

    res = chip_smoke.lm_phase(smoke_config("qwen2-7b"), n_requests=5,
                              n_slots=2, max_len=64, prompt_range=(8, 40),
                              new_tokens=4, check_len=21, log=lambda s: None)
    assert res["tokens"] == 5 * 4
    assert res["rel_err"] <= res["tol"] == chip_smoke.REL_TOL["float32"]


_FOUR = r"""
import dataclasses, sys
sys.path.insert(0, {root!r})
import chip_smoke
from repro.configs.base import smoke_config
from repro.launch import mesh
# on a 2x2 v5e make_mesh lays the pod axis on device ids 0, 1, 3, 2; the CPU
# keeps the order it is given, so reorder here the same way to hold the
# stagewise reference to the mesh's placement
_make_mesh = mesh.make_mesh
mesh.make_mesh = lambda shape, axes, devices: _make_mesh(
    shape, axes, [devices[i] for i in (0, 1, 3, 2)])
cfg = dataclasses.replace(smoke_config("qwen2-7b"), n_layers=4)
res = chip_smoke.four_chip_phase(cfg, seq_len=16, batch=8, n_micro=4)
assert res["ticks"] == 4 + 4 - 1, res
print("FOUR_OK", res["rel_err"])
"""


def test_four_chip_phase_on_virtual_devices():
    r = subprocess.run(
        [sys.executable, "-c", _FOUR.format(root=ROOT)],
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert "FOUR_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]


def test_depth_cut_keeps_published_widths():
    from repro.configs.base import depth_cut, get_arch

    full = get_arch("qwen2-7b")
    cut = depth_cut("qwen2-7b", 4)
    assert cut.n_layers == 4
    assert dataclasses.replace(cut, n_layers=full.n_layers) == full
    with pytest.raises(ValueError):
        depth_cut("qwen2-7b", 29)


def test_compile_cache_dir(monkeypatch, tmp_path):
    from repro.launch import cache

    monkeypatch.setenv(cache.ENV, str(tmp_path))
    assert cache.compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv(cache.ENV)
    assert cache.compile_cache_dir() == os.path.join(ROOT, ".jax_cache")

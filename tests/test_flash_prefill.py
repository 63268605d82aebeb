"""The serving prefill's attention runs the Pallas flash kernel
(``kernels/flash_attn.py``; its interpreter on the CPU) and gives what the
chunked attention of training gives.

Tolerance: both compute in float32 here and differ only in the order of
their sums (the kernel's online softmax rescales block by block), a few
units of float32 rounding on outputs of order 1: 1e-5 holds them with room.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.models import layers as L


def _cfg():
    """qwen3-moe-235b-a22b's attention at a small width: GQA 16:1 with
    ``qk_norm``, RoPE base 1e6, float32."""
    return dataclasses.replace(
        get_arch("qwen3-moe-235b-a22b"), d_model=64, n_heads=16,
        n_kv_heads=1, head_dim=16, param_dtype="float32",
        compute_dtype="float32", fsdp=False)


def _inputs(cfg, s, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    p = L.init_attention(cfg, k1)
    p["q_norm"] = p["q_norm"] + 0.2 * jax.random.normal(k3, p["q_norm"].shape)
    x = jax.random.normal(k2, (1, s, cfg.d_model), jnp.float32)
    pos = jnp.arange(s)[None]
    return p, x, pos


@pytest.mark.parametrize("s", [16, 512, 1024])
def test_prefill_attention_matches_chunked(s):
    """One block (16, 512) and two blocks a side (1024: the kernel skips the
    key block past the first query block's mask)."""
    cfg = _cfg()
    p, x, pos = _inputs(cfg, s)
    y, (k, v) = jax.jit(lambda p, x, pos: L.attention(
        cfg, p, x, pos, kv_out=True))(p, x, pos)
    want = jax.jit(lambda p, x, pos: L.attention(cfg, p, x, pos))(p, x, pos)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5,
                               rtol=0)
    assert k.shape == v.shape == (1, s, cfg.n_kv_heads, cfg.hd)


def test_prefill_attention_takes_the_flash_kernel_on_one_device():
    cfg = _cfg()
    p, x, pos = _inputs(cfg, 64)

    def jaxpr(kv_out):
        return str(jax.make_jaxpr(lambda p, x, pos: L.attention(
            cfg, p, x, pos, kv_out=kv_out))(p, x, pos))
    assert "flash_attn" in jaxpr(True)
    assert "flash_attn" not in jaxpr(False)


def test_flash_attention_skips_blocks_past_the_causal_mask():
    """A key block wholly after a query block is never read: poisoning it
    moves nothing, while poisoning a block it sees does."""
    from repro.kernels.flash_attn import flash_attention

    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 2, 256, 32)), jnp.float32)
               for _ in range(3))
    first = lambda k, v: np.asarray(flash_attention(
        q, k, v, causal=True, bq=128, bk=128))[:, :, :128]
    base = first(k, v)
    late = v.at[:, :, 128:].set(jnp.nan)
    np.testing.assert_array_equal(first(k, late), base)
    early = v.at[:, :, :128].set(jnp.nan)
    assert np.isnan(first(k, early)).all()

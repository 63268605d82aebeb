"""Per-kernel Pallas validation: shape/dtype sweeps vs the ref.py oracles.

All kernels run in ``interpret=True`` mode (CPU container; TPU is the
target).  Tolerances are f32-accumulation tolerances.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
import pytest
pytest.importorskip("hypothesis")  # gated: optional test dep
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.conv2d import crossbar_conv2d
from repro.kernels.decode_attn import decode_attn
from repro.kernels.flash_attn import flash_attention
from repro.kernels.mamba_scan import selective_scan
from repro.kernels.mxv import crossbar_mxv, crossbar_mxv_int8

RNG = np.random.default_rng(1234)


# ------------------------------------------------------------------ mxv
@pytest.mark.parametrize("b,m,n,bb,bm,bn", [
    (1, 128, 128, 8, 128, 128),
    (8, 256, 384, 8, 128, 128),
    (16, 512, 256, 4, 256, 64),
    (2, 64, 32, 2, 64, 32),        # sub-MXU sizes still correct in interpret
])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_mxv_sweep(b, m, n, bb, bm, bn, dtype):
    w = RNG.normal(size=(m, n)).astype(np.float32)
    wq, sc = ref.quantize_crossbar(w)
    x = jnp.asarray(RNG.normal(size=(b, n)).astype(np.float32), dtype)
    y = crossbar_mxv(x, wq, sc, bb=bb, bm=bm, bn=bn)
    want = ref.crossbar_mxv_ref(x, wq, sc)
    tol = 1e-5 if dtype == np.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("b,m,n", [(4, 128, 128), (8, 256, 512)])
def test_mxv_int8_sweep(b, m, n):
    w = RNG.normal(size=(m, n)).astype(np.float32)
    x = RNG.normal(size=(b, n)).astype(np.float32)
    wq, ws = ref.quantize_crossbar(w)
    xq, xs = ref.quantize_vec(x)
    y = crossbar_mxv_int8(xq, xs, wq, ws)
    want = ref.crossbar_mxv_int8_ref(xq, xs, wq, ws)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-6, atol=1e-6)  # exact int path


@settings(max_examples=10, deadline=None)
@given(b=st.sampled_from([1, 4]), m=st.sampled_from([128, 256]),
       n=st.sampled_from([128, 256]))
def test_mxv_property(b, m, n):
    w = RNG.normal(size=(m, n)).astype(np.float32)
    wq, sc = ref.quantize_crossbar(w)
    x = RNG.normal(size=(b, n)).astype(np.float32)
    y = crossbar_mxv(x, wq, sc)
    want = ref.crossbar_mxv_ref(x, wq, sc)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------------ conv2d
@pytest.mark.parametrize("c,h,w,fl,fh,fw,stride,pad", [
    (3, 8, 8, 8, 3, 3, 1, 1),
    (4, 12, 12, 16, 3, 3, 2, 0),
    (1, 6, 6, 4, 1, 1, 1, 0),
    (2, 9, 7, 8, 3, 3, 1, 2),
])
def test_conv2d_sweep(c, h, w, fl, fh, fw, stride, pad):
    x = RNG.normal(size=(c, h, w)).astype(np.float32)
    wf = RNG.normal(size=(fl, c * fh * fw)).astype(np.float32)
    wq, sc = ref.quantize_crossbar(wf)
    y = crossbar_conv2d(x, wq, sc, stride=stride, pad=pad, fh=fh, fw=fw)
    want = ref.crossbar_conv2d_ref(x, wq, sc, stride, pad, fh, fw)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# -------------------------------------------------------------- flash attn
@pytest.mark.parametrize("b,hq,hkv,sq,sk,d,bq,bk", [
    (1, 4, 4, 128, 128, 64, 64, 64),      # MHA
    (2, 8, 2, 256, 256, 32, 128, 128),    # GQA 4:1
    (1, 4, 1, 128, 128, 64, 64, 32),      # MQA
    (2, 4, 2, 64, 256, 32, 64, 64),       # cross/kv-longer (decode-chunk)
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(b, hq, hkv, sq, sk, d, bq, bk, causal):
    q = RNG.normal(size=(b, hq, sq, d)).astype(np.float32)
    k = RNG.normal(size=(b, hkv, sk, d)).astype(np.float32)
    v = RNG.normal(size=(b, hkv, sk, d)).astype(np.float32)
    y = flash_attention(q, k, v, causal=causal, bq=bq, bk=bk)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16():
    q = jnp.asarray(RNG.normal(size=(1, 4, 128, 64)), jnp.bfloat16)
    k = jnp.asarray(RNG.normal(size=(1, 2, 128, 64)), jnp.bfloat16)
    v = jnp.asarray(RNG.normal(size=(1, 2, 128, 64)), jnp.bfloat16)
    y = flash_attention(q, k, v, causal=True, bq=64, bk=64)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


# ------------------------------------------------------------- decode attn
@pytest.mark.parametrize("b,hq,hkv,s,d,bk,length", [
    (1, 8, 2, 256, 64, 128, 200),
    (4, 4, 4, 512, 32, 128, 512),
    (2, 16, 2, 256, 64, 64, 17),
    # a cache length no multiple of the block: the last block is ragged
    (2, 8, 2, 200, 64, 64, 190),
    (1, 8, 4, 200, 32, 64, 70),
])
def test_flash_decode_sweep(b, hq, hkv, s, d, bk, length):
    """``decode_attn`` on period 1 of a cache stacked over two, laid out
    (P, B, S, Hkv, D) as the model's; ``length`` positions attended, so
    the last is ``length - 1``."""
    q = RNG.normal(size=(b, hq, d)).astype(np.float32)
    k = RNG.normal(size=(b, hkv, s, d)).astype(np.float32)
    v = RNG.normal(size=(b, hkv, s, d)).astype(np.float32)

    def stacked(x):
        x = np.swapaxes(x, 1, 2)                          # (B, S, Hkv, D)
        return jnp.asarray(np.stack([RNG.normal(size=x.shape), x]),
                           jnp.float32)

    y = decode_attn(jnp.asarray(q), stacked(k), stacked(v), 1,
                    jnp.full((b,), length - 1, jnp.int32), bk=bk)
    want = ref.decode_ref(q, k, v, length)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


# -------------------------------------------------------------- mamba scan
@pytest.mark.parametrize("b,l,d,n,bd,bl", [
    (1, 64, 32, 8, 16, 16),
    (2, 128, 64, 16, 32, 64),
    (1, 32, 16, 4, 16, 32),
])
def test_selective_scan_sweep(b, l, d, n, bd, bl):
    u = RNG.normal(size=(b, l, d)).astype(np.float32) * 0.5
    dt = np.abs(RNG.normal(size=(b, l, d))).astype(np.float32) * 0.1
    a = -np.abs(RNG.normal(size=(d, n))).astype(np.float32)
    bb = RNG.normal(size=(b, l, n)).astype(np.float32)
    cc = RNG.normal(size=(b, l, n)).astype(np.float32)
    dsk = RNG.normal(size=(d,)).astype(np.float32)
    y = selective_scan(u, dt, a, bb, cc, dsk, bd=bd, bl=bl)
    want = ref.selective_scan_ref(u, dt, a, bb, cc, dsk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_selective_scan_state_carries_across_chunks():
    """Chunked kernel must match the oracle when L spans several chunks."""
    b, l, d, n = 1, 256, 16, 4
    u = RNG.normal(size=(b, l, d)).astype(np.float32) * 0.3
    dt = np.abs(RNG.normal(size=(b, l, d))).astype(np.float32) * 0.05
    a = -np.abs(RNG.normal(size=(d, n))).astype(np.float32)
    bb = RNG.normal(size=(b, l, n)).astype(np.float32)
    cc = RNG.normal(size=(b, l, n)).astype(np.float32)
    dsk = RNG.normal(size=(d,)).astype(np.float32)
    y = selective_scan(u, dt, a, bb, cc, dsk, bd=16, bl=32)  # 8 chunks
    want = ref.selective_scan_ref(u, dt, a, bb, cc, dsk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


# ----------------------------------------------------- int8 flash decode
@pytest.mark.parametrize("b,hq,hkv,s,d,bk,length", [
    (2, 8, 2, 256, 64, 128, 200),
    (1, 4, 4, 128, 128, 64, 128),
    (3, 6, 2, 512, 32, 128, 1),
])
def test_flash_decode_int8_sweep(b, hq, hkv, s, d, bk, length):
    from repro.kernels.decode_attn_int8 import flash_decode_int8
    q = RNG.normal(size=(b, hq, d)).astype(np.float32)
    k = RNG.normal(size=(b, hkv, s, d)).astype(np.float32) * 2
    v = RNG.normal(size=(b, hkv, s, d)).astype(np.float32)

    def quant(x):
        am = np.abs(x).max(axis=-1, keepdims=True)
        sc = np.where(am > 0, am / 127.0, 1.0).astype(np.float32)
        xq = np.clip(np.round(x / sc), -127, 127).astype(np.int8)
        return jnp.asarray(xq), jnp.asarray(sc)

    k8, ks = quant(k)
    v8, vs = quant(v)
    got = flash_decode_int8(jnp.asarray(q), k8, ks, v8, vs, length, bk=bk)
    want = ref.decode_int8_ref(jnp.asarray(q), k8, ks, v8, vs, length)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_decode_int8_matches_fp_within_quant_noise():
    """The int8 kernel's output tracks the *unquantized* decode closely."""
    from repro.kernels.decode_attn_int8 import flash_decode_int8
    b, hq, hkv, s, d = 2, 8, 2, 256, 64
    q = RNG.normal(size=(b, hq, d)).astype(np.float32)
    k = RNG.normal(size=(b, hkv, s, d)).astype(np.float32)
    v = RNG.normal(size=(b, hkv, s, d)).astype(np.float32)
    am_k = np.abs(k).max(-1, keepdims=True) / 127.0
    am_v = np.abs(v).max(-1, keepdims=True) / 127.0
    k8 = np.clip(np.round(k / am_k), -127, 127).astype(np.int8)
    v8 = np.clip(np.round(v / am_v), -127, 127).astype(np.int8)
    got = flash_decode_int8(jnp.asarray(q), jnp.asarray(k8),
                            jnp.asarray(am_k.astype(np.float32)),
                            jnp.asarray(v8),
                            jnp.asarray(am_v.astype(np.float32)), 256)
    want = ref.decode_ref(jnp.asarray(q), jnp.asarray(k),
                          jnp.asarray(v), 256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0.05, atol=0.05)


def test_interpret_mode_follows_the_backend(monkeypatch):
    """None interprets only on the CPU; an explicit True off the CPU is
    refused rather than run in the interpreter."""
    from repro.kernels.mxv import resolve_interpret

    assert resolve_interpret(None) is True          # this suite runs on CPU
    assert resolve_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret(None) is False
    with pytest.raises(ValueError):
        resolve_interpret(True)

"""Public jit'd entry points for the Pallas kernels.

Each op dispatches between the Pallas kernel (TPU target; ``interpret=True``
emulation on CPU) and the pure-jnp oracle in :mod:`repro.kernels.ref` — the
oracle path is what the LM framework uses under ``jit``/GSPMD at scale, the
kernel path is the TPU hot-spot implementation validated against it.
"""

from __future__ import annotations


from . import ref
from .conv2d import crossbar_conv2d
from .flash_attn import flash_attention
from .mamba_scan import selective_scan
from .mxv import crossbar_mxv, crossbar_mxv_int8

quantize_crossbar = ref.quantize_crossbar
quantize_vec = ref.quantize_vec


def mxv(x, wq, scale, use_kernel: bool = True, **kw):
    if use_kernel:
        return crossbar_mxv(x, wq, scale, **kw)
    return ref.crossbar_mxv_ref(x, wq, scale)


def mxv_int8(xq, xs, wq, ws, use_kernel: bool = True, **kw):
    if use_kernel:
        return crossbar_mxv_int8(xq, xs, wq, ws, **kw)
    return ref.crossbar_mxv_int8_ref(xq, xs, wq, ws)


def conv2d(x, wq, scale, stride=1, pad=0, fh=3, fw=3,
           use_kernel: bool = True, **kw):
    if use_kernel:
        return crossbar_conv2d(x, wq, scale, stride=stride, pad=pad,
                               fh=fh, fw=fw, **kw)
    return ref.crossbar_conv2d_ref(x, wq, scale, stride, pad, fh, fw)


def attention(q, k, v, causal: bool = True, use_kernel: bool = False, **kw):
    if use_kernel:
        return flash_attention(q, k, v, causal=causal, **kw)
    return ref.attention_ref(q, k, v, causal=causal)


def mamba_scan(u, dt, a, b, c, d_skip, use_kernel: bool = False, **kw):
    if use_kernel:
        return selective_scan(u, dt, a, b, c, d_skip, **kw)
    return ref.selective_scan_ref(u, dt, a, b, c, d_skip)


def decode_attention_int8(q, k8, k_scale, v8, v_scale, length,
                          use_kernel: bool = False, **kw):
    """int8-KV flash decode (jit'd wrapper; ref oracle when use_kernel=False)."""
    if use_kernel:
        from .decode_attn_int8 import flash_decode_int8
        return flash_decode_int8(q, k8, k_scale, v8, v_scale, length, **kw)
    from . import ref
    return ref.decode_int8_ref(q, k8, k_scale, v8, v_scale, length)

"""Crossbar MxV Pallas kernel — the CM core's analog matrix-vector unit.

TPU adaptation of the paper's crossbar (§2): the weight matrix lives
*resident* in VMEM as int8 "conductances" with per-row scales (analog
programming modeled as symmetric per-row quantization, cf. paper §3.5 /
[41]).  Activations stream through; the MXU performs the per-block dot.

Layout: x (B, N) @ W (M, N)^T -> y (B, M), y = (x @ q^T) * scale[None, :].
Block tiling is MXU-aligned: (BB, BN) x (BM, BN) -> (BB, BM) accumulated in
an f32 VMEM scratch across the N-block grid axis.

``interpret=None`` (every default here) picks the mode from the backend:
the Pallas interpreter on the CPU, the compiled Mosaic kernel on a TPU.  An
explicit ``interpret=True`` off the CPU is refused, so a device run can never
fall back to the interpreter unnoticed.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """The Pallas mode for ``interpret``: None -> interpret only on the CPU
    backend; True off the CPU raises."""
    backend = jax.default_backend()
    if interpret is None:
        return backend == "cpu"
    if interpret and backend != "cpu":
        raise ValueError(
            f"interpret=True on the {backend!r} backend would run the kernel "
            "in the Pallas interpreter; pass interpret=None")
    return bool(interpret)


def _mxv_kernel(x_ref, wq_ref, scale_ref, o_ref, acc_ref, *, n_blocks: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    w = wq_ref[...].astype(jnp.float32)
    # HIGHEST: the default f32 contraction on a TPU v5e errs by about 1e-2
    # on the CM zoo's crossbars, far above the plane's documented 2e-5
    # atol; fp32 contraction keeps it to f32 accumulation rounding.
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(k == n_blocks - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] * scale_ref[...]).astype(o_ref.dtype)


def _mxv_int8_kernel(xq_ref, xs_ref, wq_ref, ws_ref, o_ref, acc_ref, *,
                     n_blocks: int):
    """Fully-quantized path: int8 activations (DAC) x int8 weights."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        xq_ref[...], wq_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == n_blocks - 1)
    def _finish():
        deq = acc_ref[...].astype(jnp.float32) * xs_ref[...] * ws_ref[...]
        o_ref[...] = deq.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bb", "bm", "bn", "interpret"))
def crossbar_mxv(x: jax.Array, wq: jax.Array, scale: jax.Array,
                 bb: int = 8, bm: int = 128, bn: int = 128,
                 interpret: Optional[bool] = None) -> jax.Array:
    """y = (x @ wq^T) * scale.  x (B, N) f32/bf16, wq (M, N) int8, scale (M,)."""
    b, n = x.shape
    m, n2 = wq.shape
    assert n == n2 and scale.shape == (m,)
    bb, bm, bn = min(bb, b), min(bm, m), min(bn, n)
    assert b % bb == 0 and m % bm == 0 and n % bn == 0, (b, m, n, bb, bm, bn)
    grid = (b // bb, m // bm, n // bn)
    scale2d = scale.reshape(1, m)
    return pl.pallas_call(
        functools.partial(_mxv_kernel, n_blocks=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, bn), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, bn), lambda i, j, k: (j, k)),
            pl.BlockSpec((1, bm), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bb, bm), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, m), x.dtype),
        scratch_shapes=[pltpu.VMEM((bb, bm), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, wq, scale2d)


@functools.partial(jax.jit,
                   static_argnames=("bb", "bm", "bn", "interpret"))
def crossbar_mxv_int8(xq: jax.Array, xs: jax.Array, wq: jax.Array,
                      ws: jax.Array, bb: int = 8, bm: int = 128,
                      bn: int = 128,
                      interpret: Optional[bool] = None) -> jax.Array:
    """Fully-int8 path.  xq (B, N) int8, xs (B,), wq (M, N) int8, ws (M,)."""
    b, n = xq.shape
    m, _ = wq.shape
    bb, bm, bn = min(bb, b), min(bm, m), min(bn, n)
    assert b % bb == 0 and m % bm == 0 and n % bn == 0
    grid = (b // bb, m // bm, n // bn)
    return pl.pallas_call(
        functools.partial(_mxv_int8_kernel, n_blocks=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, bn), lambda i, j, k: (i, k)),
            pl.BlockSpec((bb, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bm, bn), lambda i, j, k: (j, k)),
            pl.BlockSpec((1, bm), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bb, bm), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, m), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, bm), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )(xq, xs.reshape(b, 1), wq, ws.reshape(1, m))


# ---------------------------------------------------- shape-agnostic wrappers
# The blocked kernels require every dimension to divide its block size.  The
# simulator's compute plane streams arbitrary (B, N) activation stacks, so
# these wrappers zero-pad up to block multiples and slice the result back.
# B is additionally bucketed to the next power of two (>= bb): a streaming
# batch then reuses a bounded set of compiled kernels instead of retracing
# per distinct batch size.  Zero padding is exact: padded activation columns
# meet padded weight columns (0 * 0 contributes 0.0 to the f32/int32
# accumulator) and padded rows are discarded.

def _ceil_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _bucket_batch(b: int, bb: int) -> int:
    p = bb
    while p < b:
        p <<= 1
    return p


def _padded_dims(b, n, m, bb, bm, bn):
    bp = _bucket_batch(b, bb)
    np_ = n if n <= bn else _ceil_to(n, bn)
    mp = m if m <= bm else _ceil_to(m, bm)
    return bp, np_, mp


def crossbar_mxv_padded(x, wq, scale, bb: int = 8, bm: int = 128,
                        bn: int = 128,
                        interpret: Optional[bool] = None) -> jax.Array:
    """``crossbar_mxv`` for arbitrary shapes (zero-pad + slice)."""
    x = jnp.asarray(x)
    wq = jnp.asarray(wq)
    scale = jnp.asarray(scale)
    b, n = x.shape
    m = wq.shape[0]
    bp, np_, mp = _padded_dims(b, n, m, bb, bm, bn)
    if (bp, np_) != (b, n):
        x = jnp.pad(x, ((0, bp - b), (0, np_ - n)))
    if (mp, np_) != (m, n):
        wq = jnp.pad(wq, ((0, mp - m), (0, np_ - n)))
    if mp != m:
        scale = jnp.pad(scale, (0, mp - m), constant_values=1.0)
    y = crossbar_mxv(x, wq, scale, bb=bb, bm=bm, bn=bn, interpret=interpret)
    return y[:b, :m]


def crossbar_mxv_int8_padded(xq, xs, wq, ws, bb: int = 8, bm: int = 128,
                             bn: int = 128,
                             interpret: Optional[bool] = None) -> jax.Array:
    """``crossbar_mxv_int8`` for arbitrary shapes (zero-pad + slice)."""
    xq = jnp.asarray(xq)
    xs = jnp.asarray(xs)
    wq = jnp.asarray(wq)
    ws = jnp.asarray(ws)
    b, n = xq.shape
    m = wq.shape[0]
    bp, np_, mp = _padded_dims(b, n, m, bb, bm, bn)
    if (bp, np_) != (b, n):
        xq = jnp.pad(xq, ((0, bp - b), (0, np_ - n)))
    if bp != b:
        xs = jnp.pad(xs, (0, bp - b), constant_values=1.0)
    if (mp, np_) != (m, n):
        wq = jnp.pad(wq, ((0, mp - m), (0, np_ - n)))
    if mp != m:
        ws = jnp.pad(ws, (0, mp - m), constant_values=1.0)
    y = crossbar_mxv_int8(xq, xs, wq, ws, bb=bb, bm=bm, bn=bn,
                          interpret=interpret)
    return y[:b, :m]

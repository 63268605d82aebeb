"""Decode attention over the layer-stacked K/V cache (one new token a slot).

``decode_attn(q, k, v, layer, length, live)`` attends each slot's query
token over positions ``0 .. length[b]`` of period ``layer`` of the cache
``k``/``v`` (P, B, S, Hkv, D), laid out as ``lm.init_cache`` lays it out.
The period index, the lengths and the live mask are scalar-prefetched, so
the index maps DMA blocks straight out of ``k[layer]`` / ``v[layer]``: no
slice of the cache is ever materialised.

Grid (B, S/BK): slot b, block j of BK positions.  A live slot reads only
the blocks that hold its positions <= length: past its last one the index
map repeats that block, so the pipeline fetches nothing new, and the
compute is skipped.  A slot outside ``live`` repeats the block fetched
before it, reads nothing, and returns zeros.  Where S is no multiple of BK,
the last block starts early, over the end of the block before it, and masks
out what that block held.  A block carries all Hkv heads of its positions
(its last two dims are the cache's own, so no layout change is needed), and
each KV head's G = Hq/Hkv query heads form the MXU tile.  BK is at most
1024, and less where the double-buffered K and V blocks would pass a VMEM
budget.  Scores, running max, sum and accumulator are float32, and the
products take float32 operands, as the XLA path's einsums do (at the TPU's
default matmul precision).  The ``pallas_call`` is named ``decode_attn``:
its device operations carry that name in a profiler trace.

:func:`reads_in_place` says for which caches a TPU keeps the row layout the
kernel reads; for others the kernel's operand would be a relaid copy of the
whole cache.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mxv import resolve_interpret

NEG_INF = -1e30
NAME = "decode_attn"
# the K and V blocks, double-buffered, with (Hkv, D) padded to whole tiles
KV_VMEM = 32 * 2**20
# q, the output, the softmax state and the float32 operands of the products
VMEM_SPARE = 16 * 2**20


def reads_in_place(hkv: int, d: int, dtype) -> bool:
    """Whether a TPU lays a (..., S, Hkv, D) cache out in rows, as the
    kernel reads it: D in whole 128-lane tiles, and Hkv in whole 8-row
    tiles or a smaller power of two of at least 32 bits a row.  For other
    shapes (Hkv 1 in bf16, 3, 5, 10, 12; D 64) XLA moves S inward, and the
    kernel's operand would be a copy of the whole cache."""
    rows = hkv * jnp.dtype(dtype).itemsize
    return d % 128 == 0 and (hkv % 8 == 0 or (8 % hkv == 0 and rows >= 4))


def _block(s: int, hkv: int, d: int, itemsize: int, bk: int):
    """The block length, at most ``bk`` and S, and the VMEM its double-
    buffered K and V blocks take with (Hkv, D) padded to whole tiles."""
    sublanes = 32 // itemsize
    row = -(-hkv // sublanes) * sublanes * (-(-d // 128) * 128) * itemsize
    bk = max(1, min(bk, s, KV_VMEM // (4 * row)))
    return bk, 4 * bk * row


def _kernel(layer_ref, len_ref, live_ref, src_ref, lo_ref, hi_ref,
            q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            sm_scale: float, bk: int, seq_len: int, hkv: int):
    b, j = pl.program_id(0), pl.program_id(1)
    live = live_ref[b] != 0

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live & (j <= hi_ref[b]))
    def _attend():
        pos = _start(j, bk, seq_len) + jax.lax.broadcasted_iota(
            jnp.int32, (1, bk), 1)
        keep = (pos >= j * bk) & (pos <= len_ref[b])
        for h in range(hkv):
            k = k_ref[:, h, :].astype(jnp.float32)            # (BK, D)
            v = v_ref[:, h, :].astype(jnp.float32)
            q = q_ref[h].astype(jnp.float32)                  # (G, D)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            s = jnp.where(keep, s, NEG_INF)                   # (G, BK)
            m_prev = m_ref[h]                                 # (G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = jnp.where(live, out, 0.0).astype(o_ref.dtype)


def _start(j, bk: int, seq_len: int):
    """The first position of block ``j``: the last block of a cache whose
    length is no multiple of ``bk`` starts early, over the block before."""
    return jnp.minimum(j * bk, seq_len - bk)


def _fetch_plan(length, live, s: int, bk: int):
    """Per slot: the slot whose blocks its grid row fetches, and the first
    and last block.  A live slot reads blocks 0 .. (its last position) //
    BK; a slot outside ``live`` repeats the block the grid step before it
    fetched (the last live slot's last block, or the first live slot's
    first), so the pipeline fetches nothing for it."""
    idx = jnp.arange(length.shape[0], dtype=jnp.int32)
    last = jnp.minimum(length, s - 1) // bk
    prev = jax.lax.cummax(jnp.where(live, idx, -1))
    src = jnp.where(prev >= 0, prev, jnp.argmax(live)).astype(jnp.int32)
    hi = jnp.where(prev >= 0, last[src], 0).astype(jnp.int32)
    lo = jnp.where(live, 0, hi).astype(jnp.int32)
    return src, lo, hi


@functools.partial(jax.jit, static_argnames=("bk",))
def decode_attn(q, k, v, layer, length, live=None, *, bk: int = 1024):
    """q (B, Hq, D); k/v (P, B, S, Hkv, D); ``layer`` an int32 scalar;
    ``length`` (B,) int32; ``live`` (B,) bool or None (every slot).
    Returns (B, Hq, D) in q's dtype: row b attends over positions
    ``<= length[b]`` of ``k[layer, b]`` (all S where ``length[b] >= S``),
    and is zero where ``live[b]`` is false.  ``bk``, the most positions a
    block holds: 1024 ran slightly faster than 512 on a TPU v5e at both
    served models' head groupings (contexts of 512-1024 positions).
    On the CPU backend the kernel runs in the Pallas interpreter
    (:func:`repro.kernels.mxv.resolve_interpret`)."""
    b, hq, d = q.shape
    _, _, s, hkv, _ = k.shape
    if hq % hkv:
        raise ValueError(f"{hq} query heads do not group over {hkv} KV heads")
    g = hq // hkv
    bk, kv_bytes = _block(s, hkv, d, k.dtype.itemsize, bk)
    length = jnp.asarray(length, jnp.int32)
    live = jnp.ones((b,), bool) if live is None else jnp.asarray(live, bool)

    def q_map(b_, j, *prefetch):
        return b_, 0, 0, 0

    def kv_map(b_, j, layer, length, live, src, lo, hi):
        blk = jnp.minimum(jnp.maximum(j, lo[b_]), hi[b_])
        return layer[0], src[b_], _start(blk, bk, s), 0, 0

    kv_spec = pl.BlockSpec((None, None, pl.Element(bk), pl.Element(hkv),
                            pl.Element(d)), kv_map)
    out = pl.pallas_call(
        functools.partial(_kernel, sm_scale=1.0 / (d ** 0.5), bk=bk,
                          seq_len=s, hkv=hkv),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            in_specs=[pl.BlockSpec((None, hkv, g, d), q_map), kv_spec,
                      kv_spec],
            out_specs=pl.BlockSpec((None, hkv, g, d), q_map),
            grid=(b, pl.cdiv(s, bk)),
            scratch_shapes=[pltpu.VMEM((hkv, g, 1), jnp.float32),
                            pltpu.VMEM((hkv, g, 1), jnp.float32),
                            pltpu.VMEM((hkv, g, d), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=kv_bytes + VMEM_SPARE),
        interpret=resolve_interpret(None),
        name=NAME,
    )(jnp.asarray(layer, jnp.int32).reshape(1), length,
      live.astype(jnp.int32), *_fetch_plan(length, live, s, bk),
      q.reshape(b, hkv, g, d), k, v)
    return out.reshape(b, hq, d)

"""Flash-attention forward Pallas kernel (prefill path).

Online-softmax blockwise attention: grid (B, Hq, Sq/BQ, Sk/BK) with the KV
axis minor-most so the (m, l, acc) VMEM scratch carries across KV blocks.
GQA: query head h reads KV head h // (Hq // Hkv) via the BlockSpec index map.
Causal masking by absolute block offsets; a KV block that lies wholly after
a query block is neither fetched nor multiplied.  The products take the
inputs' dtype and accumulate in float32 (bf16 inputs run on the MXU at its
bf16 rate, and the probabilities meet V in V's dtype).  The
``pallas_call`` is named ``flash_attn``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mxv import resolve_interpret

NEG_INF = -1e30
NAME = "flash_attn"


def _last_kv_block(i, bq: int, bk: int, q_offset: int):
    """The last KV block that query block ``i`` sees under the causal mask."""
    return (q_offset + (i + 1) * bq - 1) // bk


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  causal: bool, sm_scale: float, bq: int, bk: int,
                  n_kv_blocks: int, q_offset: int):
    qi, kv = pl.program_id(2), pl.program_id(3)

    @pl.when(kv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def block():
        q = q_ref[0, 0]                                      # (BQ, D)
        k = k_ref[0, 0]                                      # (BK, D)
        v = v_ref[0, 0]                                      # (BK, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            # Queries are the last Sq positions of the Sk-long stream: query
            # i sits at absolute position i + (Sk - Sq) = i + q_offset.
            iq = q_offset + qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            ik = kv * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(iq >= ik, s, NEG_INF)

        m_prev = m_ref[...]                                  # (BQ, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        pl.when(kv <= _last_kv_block(qi, bq, bk, q_offset))(block)
    else:
        block()

    @pl.when(kv == n_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, bq: int = 128, bk: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q (B, Hq, Sq, D); k/v (B, Hkv, Sk, D); returns (B, Hq, Sq, D).
    ``interpret`` None runs the Pallas interpreter on the CPU backend only
    (:func:`repro.kernels.mxv.resolve_interpret`)."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0
    g = hq // hkv
    bq, bk = min(bq, sq), min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0
    grid = (b, hq, sq // bq, sk // bk)
    sm_scale = 1.0 / (d ** 0.5)
    q_offset = sk - sq

    def kv_map(b_, h, i, j):
        if causal:      # a block past the mask repeats the last one: no fetch
            j = jnp.minimum(j, _last_kv_block(i, bq, bk, q_offset))
        return b_, h // g, j, 0

    return pl.pallas_call(
        functools.partial(_flash_kernel, causal=causal, sm_scale=sm_scale,
                          bq=bq, bk=bk, n_kv_blocks=grid[3],
                          q_offset=q_offset),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d), kv_map),
            pl.BlockSpec((1, 1, bk, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
        name=NAME,
    )(q, k, v)

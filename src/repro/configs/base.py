"""Architecture configuration system.

One ``ArchConfig`` per assigned architecture (exact dims from the assignment
table), plus reduced smoke variants and the per-arch input-shape sets.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MoESpec:
    n_experts: int
    top_k: int
    d_ff: int                      # per-expert intermediate size
    n_shared: int = 0              # shared ("always-on") experts
    shared_d_ff: int = 0
    every: int = 1                 # MoE on layers where i % every == offset
    offset: int = 0
    capacity_factor: float = 1.25
    norm_topk_prob: bool = True    # renormalise the top-k weights to sum 1
    # the contiguous block of published experts this chip holds: experts
    # first_held .. first_held + n_held - 1; n_held 0 => all of them.  The
    # router keeps all n_experts outputs and top_k picks.
    first_held: int = 0
    n_held: int = 0

    @property
    def held(self) -> Tuple[int, int]:
        """(first expert held, number held)."""
        return self.first_held, self.n_held or self.n_experts


@dataclasses.dataclass(frozen=True)
class SSMSpec:
    state: int = 16
    conv: int = 4
    expand: int = 2
    dt_rank: int = 0               # 0 => ceil(d_model / 16)


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                    # dense | moe | hybrid | ssm | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 => d_model // n_heads
    qkv_bias: bool = False
    qk_norm: bool = False
    mlp_act: str = "silu"          # silu => SwiGLU, gelu => GeGLU
    norm: str = "rmsnorm"
    rope_theta: float = 1_000_000.0
    mrope_sections: Optional[Tuple[int, ...]] = None   # qwen2-vl M-RoPE
    tie_embeddings: bool = False
    moe: Optional[MoESpec] = None
    ssm: Optional[SSMSpec] = None
    # layer pattern, one char per position within a repeating period:
    #   'A' attention block, 'M' mamba block.  None => all 'A'.
    layer_period: Optional[str] = None
    encoder_layers: int = 0        # >0 => encoder-decoder
    embed_inputs: bool = False     # vlm/audio: inputs are precomputed embeddings
    # dtype / memory policy
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    adam_dtype: str = "float32"
    fsdp: bool = False             # shard params over 'data' too (ZeRO-3 style)
    remat: bool = True
    q_chunk: int = 512             # blockwise-attention query chunk
    ssm_chunk: int = 256           # chunked associative scan length
    # Decode KV-cache dtype: "compute" stores K/V in compute_dtype; "int8"
    # stores symmetric per-(position, kv-head) int8 with an f32 scale —
    # halves the cache-read traffic that dominates decode.
    kv_dtype: str = "compute"
    # Gradient accumulation: >1 selects the microbatched train step
    # (distributed.overlap.make_accum_train_step) — per-microbatch bucket
    # reductions overlap the next microbatch's backward.
    grad_accum: int = 1
    # Gradient compression applied to the accumulated gradient before the
    # optimizer ("none" | "int8" | "topk") — wire-faithful numerics; the
    # payload accounting lives in distributed.compression.wire_bytes.
    grad_compression: str = "none"
    notes: str = ""

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    @property
    def attn_free(self) -> bool:
        return self.layer_period is not None and "A" not in self.layer_period

    def pattern(self) -> str:
        """Full per-layer pattern string of length n_layers."""
        if self.layer_period is None:
            return "A" * self.n_layers
        period = self.layer_period
        assert self.n_layers % len(period) == 0, (self.name, len(period))
        return period * (self.n_layers // len(period))

    def moe_layer(self, i: int) -> bool:
        return (self.moe is not None
                and i % self.moe.every == self.moe.offset)

    def param_count(self) -> int:
        """Approximate parameter count (embeddings + blocks)."""
        d, v = self.d_model, self.vocab_size
        total = v * d * (1 if self.tie_embeddings else 2)
        pat = self.pattern()
        for i, kind in enumerate(pat):
            if kind == "A":
                qkv = d * (self.n_heads + 2 * self.n_kv_heads) * self.hd
                total += qkv + self.n_heads * self.hd * d
            else:
                ssm = self.ssm or SSMSpec()
                di = ssm.expand * d
                dtr = ssm.dt_rank or -(-d // 16)
                total += 2 * d * di + di * d + ssm.conv * di \
                    + di * (dtr + 2 * ssm.state) + dtr * di + 2 * di
            if self.moe_layer(i):
                m = self.moe
                total += m.held[1] * 3 * d * m.d_ff
                total += m.n_shared * 3 * d * m.shared_d_ff // max(m.n_shared, 1) \
                    if m.n_shared else 0
                total += d * m.n_experts  # router
            else:
                total += 3 * d * self.d_ff
        if self.is_encdec:  # encoder stack + cross-attention
            enc = self.encoder_layers * (
                4 * d * self.n_heads * self.hd + 3 * d * self.d_ff)
            cross = self.n_layers * 4 * d * self.n_heads * self.hd
            total += enc + cross
        return total

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: top-k + shared only)."""
        if self.moe is None:
            return self.param_count()
        d = self.d_model
        m = self.moe
        total = self.param_count()
        n_moe_layers = sum(self.moe_layer(i) for i in range(self.n_layers))
        total -= n_moe_layers * m.n_experts * 3 * d * m.d_ff
        total += n_moe_layers * m.top_k * 3 * d * m.d_ff
        return total


# ------------------------------------------------------------------- shapes
@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str                      # train | prefill | decode


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
}


def shapes_for(cfg: ArchConfig) -> List[str]:
    """Applicable shape cells for an architecture."""
    out = ["train_4k", "prefill_32k", "decode_32k"]
    # long_500k needs sub-quadratic attention: run only for SSM/hybrid.
    if cfg.family in ("ssm", "hybrid"):
        out.append("long_500k")
    return out


_REGISTRY: Dict[str, ArchConfig] = {}


def register(cfg: ArchConfig) -> ArchConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_arch(name: str) -> ArchConfig:
    if name not in _REGISTRY:
        from . import archs  # noqa: F401  (populate registry)
    return _REGISTRY[name]


def all_archs() -> List[str]:
    from . import archs  # noqa: F401
    return sorted(_REGISTRY)


def depth_cut(name: str, n_layers: int) -> ArchConfig:
    """Published config ``name`` at its published widths, cut to
    ``n_layers`` layers (whole periods) — what one chip can hold."""
    cfg = get_arch(name)
    plen = len(cfg.layer_period or "A")
    if not 0 < n_layers <= cfg.n_layers or n_layers % plen:
        raise ValueError(f"{name}: cannot cut {cfg.n_layers} layers to "
                         f"{n_layers} (period {plen})")
    return dataclasses.replace(cfg, n_layers=n_layers)


def expert_share(cfg: ArchConfig, first: int, n_held: int) -> ArchConfig:
    """``cfg`` holding only experts ``first .. first + n_held - 1`` of each
    MoE layer: one chip's share of an expert-parallel group.  Every width,
    the router's outputs and the experts per token stay as published."""
    m = cfg.moe
    if m is None or not (0 <= first and 0 < n_held
                         and first + n_held <= m.n_experts):
        raise ValueError(f"{cfg.name}: cannot hold experts {first}.."
                         f"{first + n_held - 1} of "
                         f"{m.n_experts if m else 0}")
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(m, first_held=first, n_held=n_held))


def smoke_config(name: str) -> ArchConfig:
    """Reduced same-family config for CPU smoke tests."""
    cfg = get_arch(name)
    changes = dict(
        n_layers=len(cfg.layer_period) if cfg.layer_period else 2,
        d_model=64, n_heads=4, n_kv_heads=max(1, min(cfg.n_kv_heads, 2)),
        d_ff=128, vocab_size=256, head_dim=16,
        param_dtype="float32", compute_dtype="float32",
        q_chunk=16, ssm_chunk=8, fsdp=False,
    )
    if cfg.moe is not None:
        changes["moe"] = dataclasses.replace(
            cfg.moe, n_experts=4, top_k=2, d_ff=32,
            shared_d_ff=32 if cfg.moe.n_shared else 0)
    if cfg.ssm is not None:
        changes["ssm"] = dataclasses.replace(cfg.ssm, state=4, dt_rank=8)
    if cfg.encoder_layers:
        changes["encoder_layers"] = 2
    if cfg.mrope_sections is not None:
        changes["mrope_sections"] = (2, 3, 3)
    return dataclasses.replace(cfg, **changes)

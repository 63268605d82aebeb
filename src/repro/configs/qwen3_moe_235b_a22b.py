"""Assigned architecture config (exact dims from the assignment table)."""

import dataclasses

from .base import ArchConfig, MoESpec, depth_cut, expert_share, register

qwen3_moe_235b = register(ArchConfig(
    name="qwen3-moe-235b-a22b", family="moe",
    n_layers=94, d_model=4096, n_heads=64, n_kv_heads=4,
    d_ff=1536, vocab_size=151936, head_dim=128, qk_norm=True,
    moe=MoESpec(n_experts=128, top_k=8, d_ff=1536),
    fsdp=True, adam_dtype="bfloat16",
    notes="128 experts top-8 [hf:Qwen/Qwen3-30B-A3B scaled]; FSDP + bf16 "
          "moments to fit 16GB/chip at 256 chips",
))

# One chip of an 8-chip expert-parallel group (128 experts over 8 chips,
# experts 0-15 here) serving an 8-layer pipeline stage of the 94 layers,
# with attention, the embedding and the LM head held whole.  Every width
# as published.
qwen3_moe_235b_d8_e16 = register(dataclasses.replace(
    expert_share(depth_cut("qwen3-moe-235b-a22b", 8), 0, 16),
    name="qwen3-moe-235b-a22b-d8-e16"))

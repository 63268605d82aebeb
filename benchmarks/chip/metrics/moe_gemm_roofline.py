"""The grouped-matmul kernel's share of its roofline in the decode program:
the least time the chip could take for what the kernel has to do in a step
(``moe_counts.gemm_bytes``: each held expert hit read once, the routed rows
in and out; ``expert_flops``: 6*d*f per routed pair, from the batcher's
routing counters), at the peaks, over the kernel's device time per run of
the program (``moe_gemm_ms``)."""

from benchmarks.chip.metrics.moe_gemm_ms import gemm_seconds


def read(ctx):
    runs, secs = gemm_seconds(ctx)
    steps = ctx.counts.get("steps", 0)
    if not runs or not secs or not steps or \
            "moe_gemm_roofline_s" not in ctx.counts:
        return None
    return 100.0 * (ctx.counts["moe_gemm_roofline_s"] / steps) / (secs / runs)

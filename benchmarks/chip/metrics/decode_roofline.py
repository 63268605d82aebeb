"""The decode program's share of its roofline: the least time the chip
could take for the bytes and operations a step needs (the weights once,
the live slots' K/V; ``counts.decode_step_bytes`` and ``decode_flops``),
over the program's device time per run.  Bound by HBM bandwidth at these
sizes."""


def read(ctx):
    runs, secs = ctx.reduced.module_time(ctx.programs["decode"])
    steps = ctx.counts.get("steps", 0)
    if not runs or not steps:
        return None
    return 100.0 * (ctx.counts["decode_roofline_s"] / steps) / (secs / runs)

"""Device time of the grouped-matmul kernel (``moe_gmm``, the gate, up and
down projections of each expert layer) inside the decode program, per run
of that program, in the traced stretch.  The kernel's operations are found
by their name; a program without the kernel reads nothing."""

import re

KERNEL = re.compile(r"^moe_gmm(\.\d+)?$")


def gemm_seconds(ctx):
    """(decode program runs, seconds of its ``moe_gmm`` operations)."""
    runs, _ = ctx.reduced.module_time(ctx.programs["decode"])
    program = re.compile(ctx.programs["decode"])
    secs = 0.0
    for key, s in ctx.reduced.devices[0].ops.items():
        module, _, op = key.partition("/")
        if program.search(module) and KERNEL.match(op):
            secs += s
    return runs, secs


def read(ctx):
    runs, secs = gemm_seconds(ctx)
    return 1e3 * secs / runs if runs and secs else None

"""Requests holding a batch slot, averaged over the decode steps of the
traced stretch: each gains one token a step.  At a fixed offered load a
faster step holds fewer slots, and a count near the batcher's slots says
that requests are waiting for one."""


def read(ctx):
    steps = ctx.counts.get("steps", 0)
    return ctx.counts["decode_tokens"] / steps if steps else None

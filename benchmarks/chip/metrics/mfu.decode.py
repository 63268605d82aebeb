"""Model operations of every token the traced stretch processed, prompt
and output at true lengths (``counts.prefill_flops``, ``decode_flops``),
over the stretch times the chip's peak bf16 rate."""


def read(ctx):
    flops = ctx.counts.get("model_flops")
    if not flops:
        return None
    return 100.0 * flops / (ctx.reduced.window_s
                            * ctx.peak["bf16_flop_per_s"])

"""Share of the traced stretch in which no operation ran on the chip, in
the decode-heavy cell: 1 - busy / window, from the device trace."""


def read(ctx):
    return 100.0 * ctx.reduced.idle_share()

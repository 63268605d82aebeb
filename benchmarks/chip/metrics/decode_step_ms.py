"""Device time of one run of the decode program, averaged over the runs
in the traced stretch."""


def read(ctx):
    runs, secs = ctx.reduced.module_time(ctx.programs["decode"])
    return 1e3 * secs / runs if runs else None

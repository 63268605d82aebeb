"""Device time of one run of the batcher's slot insert (``jit_insert_slot``:
a prefilled sequence's cache written into its batch slot, out of place, so
the whole cache is read and written), averaged over the runs in the traced
stretch.  A program without that named program reads nothing."""


def read(ctx):
    runs, secs = ctx.reduced.module_time("^jit_insert_slot$")
    return 1e3 * secs / runs if runs else None

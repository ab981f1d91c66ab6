"""Share of the traced window, in percent, in which a cross-chip
collective runs on a chip and no other operation does, averaged over the
chips.  Nothing when the window holds no collective."""


def read(ctx):
    share = ctx.trace.collective_exposed()
    return None if share is None else 100.0 * share

"""Share of the traced window, in percent, in which no operation runs on
the device (1 - union of device-op intervals / window), averaged over the
chips: sweep cells."""


def read(ctx):
    share = ctx.trace.idle_share()
    return None if share is None else 100.0 * share

"""Device milliseconds per unit MU iteration, per chip, in the MU step's
factor algebra: the self time of the ops staged under its ``mu`` scope and
not under ``products`` (``xspace``), over units x iterations run.  Nothing
where no op carries the ``mu`` scope."""
from chipbench import xspace


def read(ctx):
    iters = ctx.counters.get("unit_iterations")
    rec = xspace.window_record(ctx)
    if not iters or rec is None:
        return None
    secs = xspace.Window(rec).scoped_seconds(within=("mu",),
                                             outside=("products",))
    return None if secs is None else 1e3 * secs / iters

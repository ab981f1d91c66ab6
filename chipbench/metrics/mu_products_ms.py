"""Device milliseconds per unit MU iteration, per chip, in the passes over
the stored operand: the self time of the ops staged under the MU step's
``mu`` and ``products`` scopes (``xspace``), over units x iterations run.
Nothing where no op carries those scopes."""
from chipbench import xspace


def read(ctx):
    iters = ctx.counters.get("unit_iterations")
    rec = xspace.window_record(ctx)
    if not iters or rec is None:
        return None
    secs = xspace.Window(rec).scoped_seconds(within=("mu", "products"))
    return None if secs is None else 1e3 * secs / iters

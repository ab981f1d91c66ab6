"""Seconds per sweep in the scheduler's per-k reduction: the program's own
``sched/reduce`` spans (host alignment, silhouettes and the R regression
on the device), summed over the traced window, over the sweeps in it."""


def read(ctx):
    sweeps = ctx.counters.get("sweeps")
    if not sweeps:
        return None
    return ctx.trace.span_seconds("sched/reduce") / sweeps

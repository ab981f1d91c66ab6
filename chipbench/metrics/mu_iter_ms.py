"""Device milliseconds per unit MU iteration: the device time of the
ensemble's unit programs (per chip) over units x iterations run."""


def read(ctx):
    iters = ctx.counters.get("unit_iterations")
    progs = ctx.counters.get("unit_programs")
    if not iters or not progs:
        return None
    secs = ctx.trace.module_seconds(progs)
    if secs <= 0:
        return None
    return 1e3 * secs / iters

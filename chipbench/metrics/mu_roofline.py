"""Share of the roofline in the ensemble's unit programs: the least time
of the MU iterations run (one pass over the stored operand at peak HBM
bandwidth, or the X-sized products at the bf16 peak, whichever is longer;
``roofline.mu_iteration_work``) over the unit programs' device time, per
chip."""


def read(ctx):
    least = ctx.counters.get("least_unit_seconds")
    progs = ctx.counters.get("unit_programs")
    if not least or not progs:
        return None
    secs = ctx.trace.module_seconds(progs)
    if secs <= 0:
        return None
    return 100.0 * least / secs

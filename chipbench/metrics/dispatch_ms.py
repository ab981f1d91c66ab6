"""Milliseconds per unit from the scheduler's call of the unit program to
its return (the program's ``sched/dispatch`` spans on the profiler's clock,
``xspace``), the mean over the traced window's units."""
from chipbench import xspace


def read(ctx):
    rec = xspace.window_record(ctx)
    if rec is None:
        return None
    spans = xspace.Window(rec).spans("sched/dispatch")
    if not spans:
        return None
    return 1e3 * sum(e - s for s, e in spans) / 1e9 / len(spans)

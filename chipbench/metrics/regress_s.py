"""Seconds per sweep in the per-k reduction's R regression: the program's
``reduce/regress`` spans on the profiler's clock (``xspace``), summed over
the traced window, over the sweeps in it."""
from chipbench import xspace


def read(ctx):
    sweeps = ctx.counters.get("sweeps")
    rec = xspace.window_record(ctx)
    if not sweeps or rec is None:
        return None
    spans = xspace.Window(rec).spans("reduce/regress")
    if not spans:
        return None
    return sum(e - s for s, e in spans) / 1e9 / sweeps

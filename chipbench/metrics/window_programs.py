"""Programs compiled or read from the persistent cache inside the traced
window, per sweep: the program tracer's ``xla/compile`` instants on the
profiler's clock (``xspace``; a traced sweep feeds its tracer from
``dist.compat.capture_compiles``).  0 is a reading.  Nothing where no
program span is on the profiler's clock, since then no compile is either."""
from chipbench import xspace


def read(ctx):
    sweeps = ctx.counters.get("sweeps")
    rec = xspace.window_record(ctx)
    if not sweeps or rec is None:
        return None
    w = xspace.Window(rec)
    if not w.host_spans:
        return None
    return len(w.spans(xspace.COMPILE_EVENT)) / sweeps

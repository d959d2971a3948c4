"""Share of the traced window in which the device sat idle while the
session driver staged batches, in %: device-idle time while the innermost
open program span is ``fed.sample`` (host sampling and stacking) or
``fed.h2d`` (the copy to the device), over the window, per chip.  With
``driver_idle_share.train`` it is the part of ``idle_share.train`` that
the program's own spans explain.  No such spans, no number."""

from bench.lib import program_trace as pt


def read(r):
    if r.window_s <= 0 or not r.trace.devices:
        return None
    p = pt.of(r)
    lo, hi = r.trace.window
    if not pt.spans_in(p, lo, hi, pt.STAGE):
        return None
    idle = pt.idle_ns_by_span(p, lo, hi)
    return pt.window_share(sum(idle.get(n, 0.0) for n in pt.STAGE), r)

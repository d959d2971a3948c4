"""Share of the traced window in which the device sat idle while the
session driver did anything but stage batches, in %: device-idle time
while the innermost open program span is a ``fed.*`` span other than
``fed.sample`` and ``fed.h2d`` (``fed.run``'s own work, ``fed.dispatch``,
``fed.sync``, ``fed.unpack``), over the window, per chip.  No program
spans, no number."""

from bench.lib import program_trace as pt


def read(r):
    if r.window_s <= 0 or not r.trace.devices:
        return None
    p = pt.of(r)
    lo, hi = r.trace.window
    if not pt.spans_in(p, lo, hi):
        return None
    idle = pt.idle_ns_by_span(p, lo, hi)
    return pt.window_share(sum(ns for n, ns in idle.items()
                               if n not in pt.STAGE), r)

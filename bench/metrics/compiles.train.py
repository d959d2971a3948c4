"""Compilations inside the traced window: JAX's own ``backend_compile*``
host events that start in it.  The harness warms every shape first, so
anything here is a compile the measured window paid for.  Read only from
a program whose session spans (``fed.run``) are in the window; otherwise
no number."""

from bench.lib import program_trace as pt


def read(r):
    if r.window_s <= 0 or not r.trace.devices:
        return None
    p = pt.of(r)
    lo, hi = r.trace.window
    if not pt.spans_in(p, lo, hi, ("fed.run",)):
        return None
    return float(sum(1 for _, s, _ in p.compiles if lo <= s < hi))

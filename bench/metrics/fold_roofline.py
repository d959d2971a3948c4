"""Share of its memory roofline the server fold reaches, in %.

Least work per round: the C uploaded (N,) f32 rows read once and the
combined row written once: 4N(C + 1) bytes.  Memory-bound: least time is
those bytes over the chip's HBM bandwidth.  Fold time: the device self
time of the ops in the ``fed.fold`` scope (the combiner and the add into
the server row) in the traced window, per chip.  No such ops, no
number."""

from bench.lib import program_trace as pt


def read(r):
    rounds = r.facts.get("rounds")
    if not rounds or not r.trace.devices:
        return None
    lo, hi = r.trace.window
    ns = pt.scope_ns(pt.of(r), lo, hi, ("fed.fold",)) / max(r.chips, 1)
    if ns <= 0:
        return None
    n, c = r.facts["d_params"], r.facts["cohort"]
    least = 4 * n * (c + 1) * rounds
    return 100.0 * least / r.peaks["hbm_bytes_per_s"] / (ns / 1e9)

"""Share of its memory roofline the whole top-k selection reaches, in %.

Least work per round, as for ``topk_roofline``: each of the C (N,) f32
delta rows read once and its mask written at one bit an element:
C(4N + N/8) bytes.  Memory-bound: least time is those bytes over the
chip's HBM bandwidth.  Selection time: the device self time of every op
in the ``fed.select`` scope in the traced window, per chip: both Pallas
passes and the 31-pass bisection between them, so this reads at most
``topk_roofline``.  No such ops, no number."""

from bench.lib import program_trace as pt


def read(r):
    rounds = r.facts.get("rounds")
    if not rounds or not r.trace.devices:
        return None
    lo, hi = r.trace.window
    ns = pt.scope_ns(pt.of(r), lo, hi, ("fed.select",)) / max(r.chips, 1)
    if ns <= 0:
        return None
    n, c = r.facts["d_params"], r.facts["cohort"]
    least = c * (4 * n + n / 8) * rounds
    return 100.0 * least / r.peaks["hbm_bytes_per_s"] / (ns / 1e9)

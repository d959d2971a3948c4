"""Share of its memory roofline the Pallas top-k selection reaches, in %.

Least work per round: each of the C (N,) f32 delta rows read once and its
mask written as one bit per element: C * (4N + N/8) bytes.  The
selection is memory-bound, so its least time is those bytes over the
chip's HBM bandwidth.  Kernel time: the summed device time of the top-k
kernel events (names in bench/kernels.json) in the traced window, per
chip.  The 31-pass bisection between the two kernels runs as XLA ops and
is not in that time.  No events, no number."""

from bench.lib import trace as tr


def read(r):
    lo, hi = r.trace.window
    ns = sum(tr.summed_ns(tr.matching(evs, r.kernels["topk"]), lo, hi)
             for evs in r.trace.devices.values()) / max(r.chips, 1)
    rounds = r.facts.get("rounds")
    if ns <= 0 or not rounds:
        return None
    n = r.facts["d_params"]
    least = r.facts["cohort"] * (4 * n + n / 8) * rounds
    return 100.0 * least / r.peaks["hbm_bytes_per_s"] / (ns / 1e9)

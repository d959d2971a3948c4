"""Share of its memory roofline the Pallas int8 codec reaches, in %.

Least work per round: ``codec_transport``'s (C, N) f32 rows read once
and their reconstructed f32 rows written once, plus one f32 scale a row:
C * (8N + 4) bytes.  Memory-bound: least time is those bytes over the
chip's HBM bandwidth.  Kernel time: the summed device time of the
quantize and dequantize kernel events (names in bench/kernels.json) in
the traced window, per chip.  No events, no number."""

from bench.lib import trace as tr


def read(r):
    lo, hi = r.trace.window
    ns = sum(tr.summed_ns(tr.matching(evs, r.kernels["int8"]), lo, hi)
             for evs in r.trace.devices.values()) / max(r.chips, 1)
    rounds = r.facts.get("rounds")
    if ns <= 0 or not rounds:
        return None
    n = r.facts["d_params"]
    least = r.facts["cohort"] * (8 * n + 4) * rounds
    return 100.0 * least / r.peaks["hbm_bytes_per_s"] / (ns / 1e9)

"""Share of its memory roofline the user-row store reaches, in %.

Least work per round: the gather reads and the scatter writes the C
cohort rows of the D store (N), the optimizer store (2N + 1: Adam's two
moments and its step count, the layout ``d_opt_flat_layout`` gives) and,
with a lossy codec under error feedback, the residual store (N), plus C
``last_round`` stamps: 8C(N + N_opt + N_res) + 8C bytes.  Memory-bound:
least time is those bytes over the chip's HBM bandwidth.  Store time:
the device self time of the ops in the ``fed.store_gather``,
``fed.store_scatter`` and ``fed.window_mask`` scopes in the traced
window, per chip.  No such ops, no number."""

from bench.lib import program_trace as pt


def read(r):
    rounds = r.facts.get("rounds")
    if not rounds or not r.trace.devices:
        return None
    lo, hi = r.trace.window
    ns = pt.scope_ns(pt.of(r), lo, hi, pt.STORE) / max(r.chips, 1)
    if ns <= 0:
        return None
    n, c = r.facts["d_params"], r.facts["cohort"]
    lossy = r.traffic["codec"] != "none" and r.traffic["error_feedback"]
    rows = n + (2 * n + 1) + (n if lossy else 0)
    least = (8 * c * rows + 8 * c) * rounds
    return 100.0 * least / r.peaks["hbm_bytes_per_s"] / (ns / 1e9)

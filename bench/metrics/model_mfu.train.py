"""Model FLOP utilization of the model's own compute, in %: the FLOPs
one approach-1 round needs (``federation.round_flops``, from the
configuration's layer shapes) times the rounds of the traced window, over
the device self time of the ops in the ``fed.fakes``, ``fed.d_update``
and ``fed.g_update`` scopes, summed over the chips, at the chip's bf16
peak.  Where ``mfu.train`` divides by the whole window, this divides by
the time the model's forward and backward passes take.  No such ops, no
number."""

from bench.lib import program_trace as pt


def read(r):
    rounds, per_round = r.facts.get("rounds"), r.facts.get("flops_per_round")
    if not rounds or not per_round or not r.trace.devices:
        return None
    lo, hi = r.trace.window
    ns = pt.scope_ns(pt.of(r), lo, hi, pt.MODEL)
    if ns <= 0:
        return None
    return 100.0 * per_round * rounds / (ns / 1e9 * r.peaks["bf16_flops"])

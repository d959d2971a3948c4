"""Whole-round model FLOP utilization, in %: the FLOPs one approach-1
round needs (``federation.round_flops``, from the configuration's layer
shapes) times the rounds completed in the traced window, over the
window's length, the chips and the chip's bf16 peak."""


def read(r):
    rounds, per_round = r.facts.get("rounds"), r.facts.get("flops_per_round")
    if not rounds or not per_round or r.window_s <= 0 or not r.trace.devices:
        return None
    return 100.0 * per_round * rounds / (r.window_s * r.chips
                                         * r.peaks["bf16_flops"])

"""Model FLOP utilisation of the training round: the FLOPs that the rounds
run in the traced window require (``bench/flops.py``: 6 x matmul weights
plus causal attention per token, no recompute), over the window's seconds
times the chips times each chip's bf16 peak. Percent."""


def read(x):
    rounds = x.counts.get("rounds")
    if not rounds or x.trace is None or x.trace.window_s <= 0:
        return None
    return 100.0 * rounds * x.counts["round_flops"] / (
        x.trace.window_s * x.chips * x.peaks["bf16_flop_s"])

"""The decode step's share of the chip's roofline: for each decode step
run in the traced window, the least time the chip could take for it, the
larger of its FLOPs over the bf16 peak and its bytes over the HBM peak
(``bench/flops.py``: every matmul weight and norm once at the narrower of
the stored and the compute dtype, the embedding rows of its tokens, and
the K/V of each decoding row up to its position), summed over the steps
and divided by the window's seconds. Percent."""
from bench import flops


def read(x):
    steps = x.counts.get("decode_steps")
    t = x.trace
    if not steps or t is None or t.window_s <= 0:
        return None
    cfg, pk = x.config, x.peaks
    least = 0.0
    for pos in steps:
        least += max(flops.decode_flops(cfg, pos) / pk["bf16_flop_s"],
                     flops.decode_bytes(cfg, pos, x.counts["weight_bytes"],
                                        x.counts["kv_bytes"])
                     / pk["hbm_bytes_s"])
    return 100.0 * least / (t.window_s * x.chips)

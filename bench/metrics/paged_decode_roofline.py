"""The paged decode-attention kernel's share of its roofline: the K/V bytes
its calls in the traced window must read (every layer, each decoding row's
cached tokens up to its position, from the harness's own request records),
over the HBM peak, divided by the kernel's device time in the window (its
operations are those whose name holds ``paged_decode``). Percent. Nothing
is read where no such operation ran."""
from bench import flops
from bench import trace as tr


def _is_kernel(name: str) -> bool:
    return "paged_decode" in name.lower()


def read(x):
    steps = x.counts.get("decode_steps")
    t = x.trace
    if not steps or t is None or not t.devices:
        return None
    ns = sum(tr.matching_ns(d.ops, t.window, _is_kernel) for d in t.devices)
    if ns <= 0:
        return None
    kv = sum(flops.kv_bytes(x.config, pos, x.counts["kv_bytes"])
             for pos in steps)
    return 100.0 * kv / x.peaks["hbm_bytes_s"] / (ns / 1e9)

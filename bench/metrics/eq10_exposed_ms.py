"""Eq. 10's exchange across chips that nothing hides: per device, the time
of collective operations (all-reduce, reduce-scatter, all-gather,
collective-permute, all-to-all, with their start and done halves) that no
other operation of the same device overlaps, averaged over the devices,
per round of the traced window. Milliseconds. Nothing is read where the
window holds no collective."""
from bench import trace as tr


def read(x):
    t, rounds = x.trace, x.counts.get("rounds")
    if t is None or not t.devices or not rounds:
        return None
    if not any(tr.count_matching(d.ops, t.window, tr.is_collective)
               for d in t.devices):
        return None
    ns = sum(tr.exposed_ns(d, t.window) for d in t.devices) / len(t.devices)
    return ns / rounds / 1e6

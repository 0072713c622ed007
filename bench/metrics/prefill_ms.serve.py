"""Device time of admission per admitted request: the programs that
prefill a group of prompts and scatter a request's prefilled K/V into the
paged cache (``jit_scatter``), summed over the traced window's device
planes, over the requests admitted in it. Milliseconds.

The engine jits ``functools.partial(prefill, cfg)``, which has no name of
its own, so its program is ``jit__unknown``; ``jit_prefill`` is what it
becomes once the program names it. Nothing is read unless the window ran
the prefill program exactly as often as the serving loop counted batched
prefills, and the scatter once per admitted request: a renamed prefill,
or another unnamed program on the path, leaves the metric out instead of
changing what it counts."""
from bench import trace as tr

PREFILL = ("jit_prefill", "jit__unknown")
SCATTER = ("jit_scatter",)


def _is_admission(name: str) -> bool:
    return name in PREFILL + SCATTER


def read(x):
    n = x.counts.get("admitted")
    groups = x.counts.get("prefill_groups")
    t = x.trace
    if not n or not groups or t is None or not t.devices:
        return None
    for d in t.devices:
        if (tr.count_matching(d.modules, t.window, lambda m: m in PREFILL)
                != groups
                or tr.count_matching(d.modules, t.window,
                                     lambda m: m in SCATTER) != n):
            return None
    ns = sum(tr.matching_ns(d.modules, t.window, _is_admission)
             for d in t.devices)
    return ns / n / 1e6

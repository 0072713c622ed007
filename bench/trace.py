"""Reduction of a profiler trace to the intervals the per-layer metrics read.

A traced run writes an ``.xplane.pb`` file. ``load`` keeps three things of
it, all in nanoseconds on the trace's own clock:

* per device plane (``/device:TPU:<n>``): the operations of its
  ``XLA Ops`` line and the programs of its ``XLA Modules`` line, each as
  ``(start, end, name)``;
* the host spans the harness opened with ``jax.profiler.TraceAnnotation``
  (names that start with ``bench.``);
* the measured window: the ``bench.window`` span;
* the host spans the program opened through ``repro.obs.span`` (names
  that start with ``serve.`` or ``train.``), with their args, for the
  per-layer readers (``Trace.program_spans``). Idle labels, the breakdown
  and the outline use the harness's spans alone.

The functions below reduce those intervals: busy time is the union of a
device's operation intervals inside the window, a collective's exposed time
is the part of it that no other operation of the same device overlaps, and
an idle gap is a stretch of the window with no operation, labelled by the
innermost host span that covers its middle.
"""
from __future__ import annotations

import dataclasses
import re
import warnings
from typing import (Callable, Dict, Iterable, List, NamedTuple, Optional,
                    Sequence, Tuple)

Interval = Tuple[float, float, str]

SPAN_PREFIX = "bench."
PROGRAM_PREFIXES = ("serve.", "train.")
WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE_MARKS = ("all-reduce", "reduce-scatter", "all-gather",
                    "collective-permute", "all-to-all")


@dataclasses.dataclass
class Device:
    name: str
    ops: List[Interval]
    modules: List[Interval]


class Span(NamedTuple):
    """A program span: start and end on the trace's clock, name, args."""
    start: float
    end: float
    name: str
    args: Dict


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    spans: List[Interval]
    window: Tuple[float, float]
    program_spans: List[Span] = dataclasses.field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


KERNEL = re.compile(r"\b([A-Za-z_][A-Za-z0-9_]*(?:_kernel|paged_decode)"
                    r"[A-Za-z0-9_]*)\b")


def op_name(text: str) -> str:
    """The short name of an operation event, whose name on a TPU plane is
    the whole HLO instruction: ``%fusion.12 = ... fusion(...)`` gives
    ``fusion.12``. A Pallas call also carries its kernel's function name:
    ``_paged_decode_kernel:custom-call.3``."""
    head = text.split(" = ", 1)[0].lstrip("%")
    if "custom-call" in head or "custom_call" in text:
        m = KERNEL.search(text)
        if m:
            return f"{m.group(1)}:{head}"
    return head


def module_name(text: str) -> str:
    """``jit_prefill(1234)`` gives ``jit_prefill``."""
    return text.split("(", 1)[0]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` written by ``jax.profiler.trace``."""
    from jax.profiler import ProfileData
    devices: List[Device] = []
    spans: List[Interval] = []
    program: List[Span] = []
    with warnings.catch_warnings():   # jaxlib's stats type lacks __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if DEVICE_PLANE.match(plane.name):
                lines = {line.name: line for line in plane.lines}
                devices.append(Device(
                    plane.name,
                    ops=_intervals(lines.get(OPS_LINE), op_name),
                    modules=_intervals(lines.get(MODULES_LINE),
                                       module_name)))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(SPAN_PREFIX):
                            spans.append((ev.start_ns, ev.start_ns
                                          + ev.duration_ns, ev.name))
                        elif ev.name.startswith(PROGRAM_PREFIXES):
                            program.append(Span(
                                ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name, dict(ev.stats)))
    devices.sort(key=lambda d: int(DEVICE_PLANE.match(d.name).group(1)))
    return from_parts(devices, spans, program)


def from_parts(devices: List[Device], spans: List[Interval],
               program_spans: Iterable[Span] = ()) -> Trace:
    """A ``Trace`` from intervals; the window is the ``bench.window`` span,
    or the whole extent of the device operations where there is none.
    ``program_spans`` are kept as they are, ordered by start and end."""
    win = [s for s in spans if s[2] == WINDOW_SPAN]
    if win:
        window = (win[0][0], win[0][1])
    else:
        every = [iv for d in devices for iv in d.ops + d.modules]
        window = ((min(iv[0] for iv in every), max(iv[1] for iv in every))
                  if every else (0.0, 0.0))
    return Trace(devices, sorted(spans), window,
                 sorted(program_spans, key=lambda s: (s.start, -s.end)))


def _intervals(line, short) -> List[Interval]:
    if line is None:
        return []
    names: Dict[str, str] = {}
    out = []
    for ev in line.events:
        text = ev.name
        name = names.get(text)
        if name is None:
            name = names[text] = short(text)
        out.append((ev.start_ns, ev.start_ns + ev.duration_ns, name))
    return sorted(out)


def leaves(ivs: Sequence[Interval]) -> List[Interval]:
    """The operations that hold no other operation of the same line (a
    ``while`` or ``conditional`` event spans the ops of its body), and every
    collective, which compute may overlap."""
    ivs = sorted(ivs, key=lambda iv: (iv[0], -iv[1]))
    out = []
    for i, iv in enumerate(ivs):
        nxt = ivs[i + 1] if i + 1 < len(ivs) else None
        if nxt is not None and nxt[0] < iv[1] and nxt[1] <= iv[1] \
                and not is_collective(iv[2]):
            continue
        out.append(iv)
    return out


# -- interval arithmetic --------------------------------------------------------

def clip(ivs: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    out = []
    for s, e, n in ivs:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e, n))
    return out


def union(ivs: Iterable[Interval]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e, _ in sorted(ivs):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def length(merged: Sequence[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in merged)


def subtract(a: Sequence[Tuple[float, float]],
             b: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The parts of the merged intervals ``a`` that the merged ``b`` leave
    uncovered."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# -- reductions ----------------------------------------------------------------

def is_collective(name: str) -> bool:
    low = name.lower()
    return any(m in low for m in COLLECTIVE_MARKS)


def busy_ns(dev: Device, window: Tuple[float, float]) -> float:
    """Union of the device's operation intervals inside the window."""
    return length(union(clip(dev.ops, *window)))


def exposed_ns(dev: Device, window: Tuple[float, float],
               pred: Callable[[str], bool] = is_collective) -> float:
    """Time of the operations matching ``pred`` that no other operation of
    the device overlaps, inside the window."""
    ops = leaves(clip(dev.ops, *window))
    coll = union(iv for iv in ops if pred(iv[2]))
    rest = union(iv for iv in ops if not pred(iv[2]))
    return length(subtract(coll, rest))


def matching_ns(ivs: Iterable[Interval], window: Tuple[float, float],
                pred: Callable[[str], bool]) -> float:
    """Union of the intervals whose name matches ``pred``, inside the
    window."""
    return length(union(iv for iv in clip(ivs, *window) if pred(iv[2])))


def count_matching(ivs: Iterable[Interval], window: Tuple[float, float],
                   pred: Callable[[str], bool]) -> int:
    return sum(1 for iv in clip(ivs, *window) if pred(iv[2]))


def idle_gaps(dev: Device, window: Tuple[float, float]
              ) -> List[Tuple[float, float]]:
    return subtract([window], union(clip(dev.ops, *window)))


def label_of(t: float, spans: Sequence[Interval]) -> str:
    """The innermost (shortest) harness span that covers instant ``t``."""
    best: Optional[Interval] = None
    for s, e, n in spans:
        if s <= t < e and n != WINDOW_SPAN \
                and (best is None or e - s < best[1] - best[0]):
            best = (s, e, n)
    return best[2][len(SPAN_PREFIX):] if best else "untraced"


def idle_by_label(trace: Trace, top: int = 10) -> List[List]:
    """Idle seconds of the window by what the host was doing, averaged over
    the devices, largest first."""
    if not trace.devices:
        return []
    acc: Dict[str, float] = {}
    for dev in trace.devices:
        for s, e in idle_gaps(dev, trace.window):
            lab = label_of((s + e) / 2, trace.spans)
            acc[lab] = acc.get(lab, 0.0) + (e - s) / 1e9
    n = len(trace.devices)
    rows = sorted(((k, v / n) for k, v in acc.items()), key=lambda r: -r[1])
    return [[k, v] for k, v in rows[:top]]


def top_ops(trace: Trace, top: int = 10) -> List[List]:
    """Device seconds by operation name inside the window, averaged over the
    devices, largest first."""
    if not trace.devices:
        return []
    acc: Dict[str, float] = {}
    for dev in trace.devices:
        for s, e, n in leaves(clip(dev.ops, *trace.window)):
            acc[n] = acc.get(n, 0.0) + (e - s) / 1e9
    n = len(trace.devices)
    rows = sorted(((k, v / n) for k, v in acc.items()), key=lambda r: -r[1])
    return [[k, v] for k, v in rows[:top]]


def mean_busy_s(trace: Trace) -> float:
    if not trace.devices:
        return 0.0
    return sum(busy_ns(d, trace.window) for d in trace.devices) \
        / len(trace.devices) / 1e9


def outline(trace: Trace, top: int = 40) -> dict:
    """For reading a trace by hand: per device, the programs and the leaf
    operations that took most time, by name without its numeric suffix,
    with their counts; and the host spans' total time."""
    def group(ivs):
        acc: Dict[str, List[float]] = {}
        for s, e, n in clip(ivs, *trace.window):
            key = re.sub(r"\.\d+$", "", n)
            a = acc.setdefault(key, [0.0, 0])
            a[0] += (e - s) / 1e9
            a[1] += 1
        rows = sorted(acc.items(), key=lambda kv: -kv[1][0])[:top]
        return [[k, v[0], v[1]] for k, v in rows]
    spans: Dict[str, float] = {}
    for s, e, n in clip(trace.spans, *trace.window):
        spans[n] = spans.get(n, 0.0) + (e - s) / 1e9
    return {"window_s": trace.window_s,
            "devices": [{"name": d.name, "modules": group(d.modules),
                         "ops": group(leaves(d.ops))}
                        for d in trace.devices],
            "spans": spans}

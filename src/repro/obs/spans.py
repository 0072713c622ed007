"""Program spans on the profiler's clock.

``span(name, **ids)`` opens a ``jax.profiler.TraceAnnotation``: a host
span that lands in the same ``.xplane.pb`` as the device's operations, so
an idle gap on the device can be read against what the host was doing in
it. ``ids`` ride along as the event's args (a request's ``rid``, its
``slot``). A span records only while a profiler session is active
(``jax.profiler.trace`` or ``jax.profiler.start_server``); with none, it
costs one constructor call and records nothing, so it needs no switch of
its own.

Program spans are named ``serve.<phase>``; the benchmark's own spans keep
``bench.``.
"""
from __future__ import annotations

import jax


def span(name: str, **ids):
    """A host span ``name`` with ``ids`` as its args; use as ``with``."""
    return jax.profiler.TraceAnnotation(name, **ids)

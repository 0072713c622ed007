"""Observability subsystem: structured telemetry for rounds, worker
assessment, and serving.

Typed events (``obs/events.py``) flow into a ``Telemetry`` sink
(``obs/sinks.py``): ``NullSink`` (default, hot path untouched),
``RingSink`` (in-memory), ``JsonlSink`` (background-writer JSONL —
summarize a recorded run with ``tools/obs_report.py``). Producers:
``Trainer.run(telemetry=)`` (RoundTrace, WorkerAssessment,
MembershipChange), ``AsyncCheckpointer`` (CheckpointSave),
``ContinuousEngine(telemetry=)`` (ServeSample), ``HotSwapBridge``
(HotSwap).

``span`` (``obs/spans.py``) writes host spans into a profiler trace on the
device's clock; ``ContinuousEngine.step`` opens its ``serve.*`` spans
through it.
"""
from repro.obs.events import (CheckpointSave, HotSwap, MembershipChange,
                              PHASE_NAMES, RoundTrace, ServeSample,
                              WorkerAssessment, event_from_record, to_record,
                              summarize_policy_state)
from repro.obs.sinks import (JsonlSink, NULL, NullSink, RingSink, Telemetry,
                             read_events)
from repro.obs.spans import span

__all__ = [
    "CheckpointSave", "HotSwap", "JsonlSink", "MembershipChange", "NULL",
    "NullSink", "PHASE_NAMES", "RingSink", "RoundTrace", "ServeSample",
    "Telemetry", "WorkerAssessment", "event_from_record", "read_events",
    "span", "summarize_policy_state", "to_record",
]

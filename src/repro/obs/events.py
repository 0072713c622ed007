"""Typed telemetry event records.

One dataclass per observable fact, each carrying a ``kind`` tag and a
host wall-clock stamp ``t_wall``. Events round-trip through JSONL:
``event.to_record()`` is a plain-JSON dict (numpy arrays become lists)
and ``event_from_record`` rebuilds the typed event from it, so a
recorded run can be re-analysed with the same types the live sinks saw
(``tools/obs_report.py`` does exactly that).

The schema is deliberately flat — every field is a scalar, a short list,
or a ``{phase: seconds}`` dict — so a JSONL line stays greppable and the
reporter never needs the repo's pytree machinery.

``RoundTrace`` phase names (``PHASE_NAMES``) mirror the structure of one
WASGD round: host staging of the round batch, the tau local steps
(lax.scan), the Judge/energy -> theta policy forward, the aggregation
schedule's reduce phase(s) (``reduce_scatter``/``all_gather`` for 2-phase
schedules, ``reduce`` for 1-phase), the overlap seam thunk, and the
Eq. 10 finalize + state assembly. Phases are only populated when the
Trainer runs the phase-fenced instrumented step (``detail="phased"``);
runs the instrumented step cannot decompose (pipelined rounds, baseline
rules) report a fenced ``total_s`` only (``detail="fused"``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import numpy as np

PHASE_NAMES = ("host_staging", "local_steps", "judge", "reduce",
               "reduce_scatter", "overlap", "all_gather", "finalize")


def _now() -> float:
    return time.time()


def _jsonable(v):
    if isinstance(v, np.ndarray):
        return v.tolist()
    if isinstance(v, np.generic):
        return v.item()
    if isinstance(v, dict):
        return {k: _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    return v


@dataclasses.dataclass
class RoundTrace:
    """Device-accurate timing breakdown of one training round.

    ``phases`` maps phase names (see ``PHASE_NAMES``) to seconds; each
    phase is fenced with ``jax.block_until_ready`` before its timer
    stops, so the numbers measure compute, not dispatch. ``total_s`` is
    the fenced wall time of the whole device round (excluding
    ``host_staging_s``, which is the host-side batch pull + staging)."""
    kind = "round_trace"
    round: int
    total_s: float
    host_staging_s: float = 0.0
    phases: Dict[str, float] = dataclasses.field(default_factory=dict)
    detail: str = "phased"          # "phased" | "fused"
    p: Optional[int] = None         # live worker count
    t_wall: float = dataclasses.field(default_factory=_now)


@dataclasses.dataclass
class WorkerAssessment:
    """Per-round worker assessment: the paper's central signal.

    ``theta`` is the Eq. 10 weight vector the round aggregated with,
    ``energies`` the per-worker accumulated energies (h) the Judge
    scored, ``active`` the Alg. 4 activity mask (None on sync rounds),
    ``policy_state`` a small summary of the stateful policy's carried
    state (leaf count + L2), not the state itself."""
    kind = "worker_assessment"
    round: int
    theta: List[float]
    energies: List[float]
    theta_entropy: float
    active: Optional[List[bool]] = None
    policy: str = ""
    policy_state: Optional[Dict[str, Any]] = None
    t_wall: float = dataclasses.field(default_factory=_now)


@dataclasses.dataclass
class ServeSample:
    """One ``ContinuousEngine.step()`` scheduling round.

    ``ttft_s`` holds time-to-first-token for the requests admitted this
    step: submit -> their first tokens read back on the host, at the end
    of this step's chunk (``Request.t_first - Request.t_submit``; the
    admission is not fenced). ``e2e_s`` holds submit-to-finish latency
    for requests that finished this step. ``itl_s`` is the chunk's mean
    inter-token latency (fenced chunk wall / decode-loop iterations)."""
    kind = "serve_sample"
    chunk_s: float
    steps: int
    tokens: int
    itl_s: float
    n_running: int
    queue_depth: int
    admitted: int
    finished: int
    blocks_free: int
    blocks_total: int
    occupancy: float
    ttft_s: List[float] = dataclasses.field(default_factory=list)
    e2e_s: List[float] = dataclasses.field(default_factory=list)
    t_wall: float = dataclasses.field(default_factory=_now)


@dataclasses.dataclass
class MembershipChange:
    """A committed ``WorkerSet`` resize at a round boundary."""
    kind = "membership_change"
    round: int
    old_p: int
    new_p: int
    generation: int = 0
    t_wall: float = dataclasses.field(default_factory=_now)


@dataclasses.dataclass
class CheckpointSave:
    """One completed async sharded checkpoint write. ``duration_s``
    covers the device-to-host gather plus the shard writes, measured on
    the writer thread (the part that rides the next rounds' device
    time)."""
    kind = "checkpoint_save"
    path: str
    round: int
    duration_s: float
    nbytes: int
    t_wall: float = dataclasses.field(default_factory=_now)


@dataclasses.dataclass
class HotSwap:
    """One train-to-serve ``HotSwapBridge`` swap with its staleness
    record (rounds since the engine last saw fresh params, tokens served
    under the stale copy, L2 drift the swap closed)."""
    kind = "hot_swap"
    round: int
    rounds_since_last: Optional[int]
    tokens_under_prev: int
    param_drift_l2: float
    in_flight: int
    t_wall: float = dataclasses.field(default_factory=_now)


EVENT_TYPES = {cls.kind: cls for cls in
               (RoundTrace, WorkerAssessment, ServeSample, MembershipChange,
                CheckpointSave, HotSwap)}


def to_record(event) -> Dict[str, Any]:
    """Event -> plain-JSON dict (one JSONL line's payload)."""
    rec = {"kind": event.kind}
    for f in dataclasses.fields(event):
        rec[f.name] = _jsonable(getattr(event, f.name))
    return rec


def event_from_record(rec: Dict[str, Any]):
    """Inverse of ``to_record``. Unknown kinds raise (a run recorded by
    a newer schema should fail loud, not be silently dropped); unknown
    FIELDS of a known kind are dropped, so minor schema growth stays
    readable."""
    rec = dict(rec)
    kind = rec.pop("kind")
    cls = EVENT_TYPES.get(kind)
    if cls is None:
        raise ValueError(f"unknown telemetry event kind {kind!r}; "
                         f"known: {sorted(EVENT_TYPES)}")
    names = {f.name for f in dataclasses.fields(cls)}
    return cls(**{k: v for k, v in rec.items() if k in names})


def summarize_policy_state(pstate) -> Optional[Dict[str, Any]]:
    """Small host-side summary of a policy's carried state: leaf count
    and total L2. ``None`` for the empty (stateless) state."""
    leaves = [np.asarray(x) for x in _leaves(pstate)]
    if not leaves:
        return None
    l2 = float(np.sqrt(sum(float(np.sum(np.square(x.astype(np.float64))))
                           for x in leaves)))
    return {"n_leaves": len(leaves), "l2": l2}


def _leaves(tree):
    if isinstance(tree, dict):
        for v in tree.values():
            yield from _leaves(v)
    elif isinstance(tree, (tuple, list)):
        for v in tree:
            yield from _leaves(v)
    elif tree is not None:
        yield tree

"""Elastic worker membership: the ``WorkerSet`` lifecycle.

WASGD's decentralized weighting (Eq. 10) has no center variable — unlike
EASGD's elastic link to a master, nothing in the math requires fixed
membership, and Alg. 4 already drops stragglers per round. This module
grows that into true elasticity: the worker count ``p`` is a
**round-boundary-mutable property** of a ``WorkerSet``, and a
``resize(new_p)`` event re-shards every per-worker structure in the
system:

* the worker-stacked parameter tree and its mirrored optimizer state
  (``core/aggregate.resize_worker_leaves`` — survivors bitwise-preserved,
  newcomers adopt the aggregate, the Alg. 4 late-join state);
* the worker-assessment policy state (``WeightPolicy.expand_state`` —
  EMA/time/anneal state survives membership changes, newcomers re-init
  from the aggregate);
* the Alg. 4 activity mask (``core/async_device.resize_active_mask`` —
  newcomers join active, a shrink can never empty the active set);
* the per-worker loss-energy accumulator (newcomers start at 0 — it
  resets every round anyway).

The slot contract everywhere: worker ``i`` keeps slot ``i`` for
``i < min(old_p, new_p)``; a shrink kills the tail slots, a grow appends
newcomers at the tail. That keeps every resize a slice-or-concat — no
permutation bookkeeping — and makes "kill worker j" expressible as a
shrink after rotating j to the tail, which the chaos schedule does not
need: which slot dies is irrelevant to convergence, only how many live.

``MembershipSchedule`` scripts the events for a run
(``Trainer.run(membership_schedule=)``), and ``make_chaos_schedule``
generates a seeded kill/revive walk for chaos testing. Checkpoints
(``checkpoint/io.py``) record ``p`` in their manifest; a restore under a
different ``p`` routes through this module's resize machinery.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import aggregate as agg
from repro.core.aggregate import _axes_is_leaf, resize_worker_leaves


# ---------------------------------------------------------------------------
# The WorkerSet lifecycle
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MembershipEvent:
    """One recorded membership change: ``old_p -> new_p`` at ``round``."""
    round: Optional[int]
    old_p: int
    new_p: int


class WorkerSet:
    """Live worker membership: ``p`` as a mutable-at-round-boundary value.

    The set only changes through ``resize`` — every change is validated
    (``p >= 1``), bumps the ``generation`` counter (so downstream caches
    keyed on membership can invalidate), and lands in the event ``log``.
    """

    def __init__(self, p: int):
        if int(p) < 1:
            raise ValueError(f"a WorkerSet needs p >= 1, got {p}")
        self._p = int(p)
        self.generation = 0
        self.log: List[MembershipEvent] = []

    @property
    def p(self) -> int:
        return self._p

    def resize(self, new_p: int, round: Optional[int] = None
               ) -> MembershipEvent:
        """Commit a membership change at a round boundary."""
        new_p = int(new_p)
        if new_p < 1:
            raise ValueError(f"resize needs new_p >= 1, got {new_p}")
        event = MembershipEvent(round, self._p, new_p)
        if new_p != self._p:
            self._p = new_p
            self.generation += 1
        self.log.append(event)
        return event

    def __repr__(self):
        return f"WorkerSet(p={self._p}, generation={self.generation})"


# ---------------------------------------------------------------------------
# Membership schedules (scripted events + the chaos generator)
# ---------------------------------------------------------------------------

class MembershipSchedule:
    """Round-indexed worker counts: ``events[r] = p`` takes effect at the
    START of round ``r`` (a round boundary — mid-round membership is
    exactly what the round abstraction exists to exclude). ``p_of(r)`` is
    the worker count round ``r`` runs with: the latest event at or before
    ``r``, else ``p0``.
    """

    def __init__(self, p0: int, events: Optional[Dict[int, int]] = None):
        if int(p0) < 1:
            raise ValueError(f"MembershipSchedule needs p0 >= 1, got {p0}")
        self.p0 = int(p0)
        events = dict(events or {})
        for r, p in events.items():
            if int(r) < 0:
                raise ValueError(f"membership event at negative round {r}")
            if int(p) < 1:
                raise ValueError(
                    f"membership event at round {r} asks for p={p}; every "
                    f"round needs >= 1 worker")
        self.events = {int(r): int(p) for r, p in events.items()}
        self._boundaries = sorted(self.events)

    def p_of(self, r: int) -> int:
        p = self.p0
        for b in self._boundaries:
            if b > r:
                break
            p = self.events[b]
        return p

    def max_p(self, n_rounds: int) -> int:
        return max([self.p0] + [p for r, p in self.events.items()
                                if r < n_rounds])

    def __repr__(self):
        ev = ", ".join(f"{r}->{p}" for r, p in sorted(self.events.items()))
        return f"MembershipSchedule(p0={self.p0}, {{{ev}}})"


def make_chaos_schedule(p0: int, rounds: int, seed: int = 0,
                        event_prob: float = 0.4, min_p: int = 1,
                        max_p: Optional[int] = None) -> MembershipSchedule:
    """A seeded kill/revive walk over the worker count.

    Each round boundary flips a coin (``event_prob``); on an event the
    worker count takes a +-1 or +-2 step, clamped to ``[min_p, max_p]``
    (``max_p`` defaults to ``2 * p0``) and biased back toward ``p0`` so
    long runs oscillate around the nominal fleet size instead of drifting.
    """
    if max_p is None:
        max_p = 2 * p0
    if not (1 <= min_p <= p0 <= max_p):
        raise ValueError(
            f"need 1 <= min_p <= p0 <= max_p, got {min_p}/{p0}/{max_p}")
    rng = np.random.default_rng(seed)
    events: Dict[int, int] = {}
    p = p0
    for r in range(1, rounds):
        if rng.random() >= event_prob:
            continue
        step = int(rng.integers(1, 3))
        direction = -1 if p > p0 else (1 if p < p0 else
                                       (1 if rng.random() < 0.5 else -1))
        new_p = int(np.clip(p + direction * step, min_p, max_p))
        if new_p != p:
            events[r] = new_p
            p = new_p
    return MembershipSchedule(p0, events)


# ---------------------------------------------------------------------------
# Re-sharding the per-worker state across a resize
# ---------------------------------------------------------------------------

def resize_comm_state(comm_state: Any, new_p: int, policy=None) -> Any:
    """Re-shard a wasgd/wasgd+ ``comm_state`` across a membership resize.

    Handles the three shapes the wasgd rules produce (train/step.py
    ``init_comm_state``): ``()`` (stateless sync), a bare ``(p,)`` bool
    activity mask (stateless on_device), and the ``{"active", "policy"}``
    dict (stateful on_device). A bare stateful-policy state (stateful
    sync) routes through ``policy.expand_state``. Baseline rules' comm
    state (EASGD's center, MWU's log-weights) is tied to their own
    fixed-membership math and is rejected.
    """
    from repro.core.async_device import resize_active_mask

    if isinstance(comm_state, tuple) and not comm_state:
        return ()
    if isinstance(comm_state, dict) and set(comm_state) == {"active",
                                                            "policy"}:
        pstate = comm_state["policy"]
        if policy is not None:
            pstate = policy.expand_state(pstate, new_p)
        return {"active": resize_active_mask(comm_state["active"], new_p),
                "policy": pstate}
    is_mask = (hasattr(comm_state, "dtype")
               and jnp.asarray(comm_state).dtype == jnp.bool_
               and jnp.asarray(comm_state).ndim == 1)
    if is_mask:
        return resize_active_mask(comm_state, new_p)
    if policy is not None and isinstance(comm_state, dict):
        return policy.expand_state(comm_state, new_p)
    raise ValueError(
        "membership resize supports the wasgd/wasgd+ comm_state shapes "
        "((), activity mask, policy state, {'active', 'policy'}); rules "
        "with a center/master variable (easgd, mwu) have no elastic "
        f"re-shard (got {type(comm_state).__name__})")


def _params_structure(axes: Dict):
    return jax.tree_util.tree_structure(axes, is_leaf=_axes_is_leaf)


def _resize_params_like(tree: Any, axes: Dict, new_p: int) -> Any:
    """Worker-axis resize of a params-structured tree: worker leaves are
    sliced/grown (newcomers = survivor mean), shared leaves pass through."""
    def visit(x, ax):
        if not agg.is_worker_leaf(ax):
            return x
        old_p = x.shape[0]
        if new_p <= old_p:
            return x[:new_p]
        fill = jnp.broadcast_to(
            x.astype(jnp.float32).mean(axis=0)[None],
            (new_p - old_p,) + x.shape[1:]).astype(x.dtype)
        return jnp.concatenate([x, fill], axis=0)

    return jax.tree.map(visit, tree, axes, is_leaf=_axes_is_leaf)


def map_opt_state(opt_state: Any, axes: Dict, on_params, on_other) -> Any:
    """Rebuild an optimizer state with ``on_params`` applied to each of its
    params-structured subtrees and ``on_other`` to anything else.

    Optimizer state in this substrate is element-wise over the params
    (optim/optimizers.py), so it either IS params-structured (momentum
    buffers, ``_tree_zeros``), is empty (plain SGD), or is a container
    (NamedTuple/tuple) whose fields are each params-structured or scalar
    (AdamW's ``(mu, nu, count)``).
    """
    target = _params_structure(axes)

    def visit(sub):
        if isinstance(sub, tuple) and not sub:
            return sub
        if jax.tree_util.tree_structure(sub) == target:
            return on_params(sub)
        if hasattr(sub, "_fields"):                    # NamedTuple
            return type(sub)(*(visit(getattr(sub, f)) for f in sub._fields))
        if isinstance(sub, (tuple, list)):
            return type(sub)(visit(v) for v in sub)
        return on_other(sub)

    return visit(opt_state)


def resize_opt_state(opt_state: Any, axes: Dict, new_p: int) -> Any:
    """Re-shard optimizer state across a membership resize: worker leaves
    resize with survivor-mean newcomer rows — a joiner inherits the fleet's
    aggregate momentum/moments rather than restarting cold; scalars (step
    counts) are fleet state and pass through.
    """
    def scalar(sub):
        if hasattr(sub, "ndim") and jnp.asarray(sub).ndim == 0:
            return sub
        raise ValueError(
            f"don't know how to re-shard optimizer state of type "
            f"{type(sub).__name__} across a membership resize; expected "
            f"(), a params-structured tree, or a container of those")

    return map_opt_state(
        opt_state, axes, lambda t: _resize_params_like(t, axes, new_p),
        scalar)


def resize_train_state(state, axes: Dict, new_p: int, policy=None,
                       theta: Optional[jax.Array] = None,
                       comm_state: Any = "__resize__"):
    """Re-shard a full ``TrainState`` across a membership resize.

    Params resize through ``core/aggregate.resize_worker_leaves`` (newcomers
    adopt the aggregate — optionally the ``theta``-weighted one), the
    optimizer state mirrors them, the energy accumulator grows with zeros
    (it resets every round), and the comm state routes through
    ``resize_comm_state`` (pass a pre-resized ``comm_state`` to override,
    e.g. when the Trainer threads it through ``init_comm_state(prev=)``).
    The round counter ``step`` is fleet state and carries over.
    """
    old_energy = state.energy
    old_p = old_energy.shape[0]
    if new_p <= old_p:
        energy = old_energy[:new_p]
    else:
        energy = jnp.concatenate(
            [old_energy, jnp.zeros((new_p - old_p,), old_energy.dtype)])
    if isinstance(comm_state, str) and comm_state == "__resize__":
        comm_state = resize_comm_state(state.comm_state, new_p,
                                       policy=policy)
    return state._replace(
        params=resize_worker_leaves(state.params, axes, new_p, theta=theta),
        opt_state=resize_opt_state(state.opt_state, axes, new_p),
        energy=energy,
        comm_state=comm_state,
    )

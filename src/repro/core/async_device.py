"""On-device asynchronous WASGD+ (paper Alg. 4) through the backend registry.

``core/async_sim.py`` reproduces Alg. 4's *scheduling semantics* as a
host-side numpy event simulation; this module runs the same p-of-(p+b)
round as ONE jitted program on the worker mesh axis. Each worker's activity
is a traced ``(w,)`` boolean mask:

    local tau steps -> loss energies -> masked Boltzmann theta
        (``weights.masked_compute_theta``: stragglers' theta is exactly 0)
    -> Eq. 10 aggregate over the ACTIVE workers, through any composed
       ``schedule:codec`` spec of the two-axis API (core/backends.py)
    -> straggler late-join: inactive workers adopt the aggregate
       m = sum_j theta_j x_j when they arrive (Alg. 4 line 20).

Because the stragglers' theta is zero they contribute nothing to the
reduce, so exclusion needs no gather/compaction — the whole round stays
SPMD and the mask can change every round without recompilation.

Under the two-axis API the async family is NOT a separate set of backends
anymore: every composed spec applies the late-join mask in its ``finalize``
when ``ctx.active`` is set (``None`` = all-active, degenerating to the
synchronous update). The legacy names stay as registry aliases —

``async_einsum``     -> ``einsum``        (meshless reference; the
                                          in-registry twin of the host sim)
``async_shard_map``  -> ``shard_map:f32`` (masked psum under shard_map)
``async_rs_ag``      -> ``rs_ag``         (masked reduce-scatter + FMA +
                                          all-gather, ring payload from the
                                          codec / ``ctx.comm_dtype``)

— and ``async_backend_name`` now maps ANY resolvable spec to its Alg. 4
form, so the async regime composes with the payload axis
(``"hierarchical:int8"`` under a straggler mask is a valid round). Since
the v2 fused kernel that includes ``pallas_wagg``: the activity mask is
applied inside the kernel's VMEM pass, so the on-device round can select
``pallas_wagg:<codec>`` like any other spec. The host simulation stays the
semantic oracle: ``tests/test_async_device.py`` injects the same
``StragglerSchedule`` into both paths and requires leaf-for-leaf parity.

Worker assessment comes from the policy axis (core/weights.py): the
drivers take ``policy=`` spec strings / ``WeightPolicy`` objects (legacy
``strategy``/``a_tilde`` stay as bitwise aliases), with stateful policy
state threading across rounds. ``run_parallel_sgd_on_device(
measure_times=True)`` additionally derives the Alg. 4 activity mask from
MEASURED per-device round times — no ``StepTimeModel`` or precomputed
schedule — and feeds the measurements to time-consuming policy stages
(``time_aware``) via ``observe_times``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import backends
from repro.core import weights as weights_mod
from repro.core.aggregate import _axes_is_leaf
from repro.core.async_sim import (AsyncResult, StepTimeModel,
                                  StragglerSchedule, make_schedule)
from repro.core.weights import masked_compute_theta
from repro.parallel.sharding import auto_mesh, replicated, worker_split

ASYNC_BACKENDS = ("async_einsum", "async_shard_map", "async_rs_ag")

# legacy sync backend -> its Alg. 4 (masked + late-join) alias
_ASYNC_OF = {"einsum": "async_einsum", "shard_map": "async_shard_map",
             "rs_ag": "async_rs_ag"}


def async_backend_name(name: str) -> str:
    """Map a (possibly synchronous) backend name/spec to its Alg. 4 form.

    Legacy names keep their ``async_*`` aliases; any other resolvable
    ``schedule[:codec]`` spec is already mask-capable (the composed
    ``finalize`` applies the late-join whenever ``ctx.active`` is set), so
    it maps to its own canonical spec — e.g. ``"quantized"`` ->
    ``"einsum:int8"``, ``"hierarchical:int8"`` -> itself, ``"pallas_wagg"``
    -> ``"pallas_wagg:f32"`` (the v2 fused kernel applies the mask in its
    VMEM pass). Schedules registered with ``supports_mask=False`` still
    raise — there is no Alg. 4 round without a late-join path.
    """
    if name in ASYNC_BACKENDS:
        return name
    if name in _ASYNC_OF:
        return _ASYNC_OF[name]
    try:
        sched, codec = backends.resolve_spec(name)
    except KeyError:
        raise ValueError(
            f"aggregation backend {name!r} has no async (Alg. 4) "
            f"counterpart; use a composed 'schedule:codec' spec, one of "
            f"{sorted(_ASYNC_OF)}, or {sorted(ASYNC_BACKENDS)}")
    if not getattr(backends._SCHEDULES[sched], "supports_mask", True):
        raise ValueError(
            f"aggregation schedule {sched!r} has no async (Alg. 4) "
            f"counterpart (no masked/late-join path); use the "
            f"einsum/shard_map/rs_ag schedules")
    return backends.canonical_spec(name)


def validate_active_rounds(active: np.ndarray, rounds: Optional[int] = None):
    """Reject straggler schedules containing an all-False round.

    ``masked_compute_theta`` documents that an all-False mask yields NaNs
    (the softmax of an all ``-inf`` row) rather than silently inventing
    weights, and the driver's per-round loss (the mean over the active
    workers) is the mean of an empty slice — NaN again. Both poison the
    entire downstream loss history, so a schedule with an empty round is a
    config error caught loudly HERE, at injection time, not a numerical
    curiosity discovered rounds later. Used by
    ``run_parallel_sgd_on_device`` and ``Trainer.run(straggler_schedule=)``.
    """
    active = np.asarray(active, bool)
    if rounds is not None:
        active = active[:rounds]
    empty = np.flatnonzero(~active.any(axis=-1))
    if empty.size:
        raise ValueError(
            f"straggler schedule has no active worker in round(s) "
            f"{empty.tolist()}: an all-straggler round has no Alg. 4 "
            f"aggregate to late-join (masked theta would be NaN and the "
            f"round loss the mean of an empty slice); every round needs "
            f">= 1 active worker")


def resize_active_mask(active, new_p: int) -> jnp.ndarray:
    """Rebuild the Alg. 4 activity mask after a membership resize
    (core/membership.py): worker ``i`` keeps slot ``i`` for
    ``i < min(old_p, new_p)`` — a straggler that was excluded stays
    excluded — a shrink drops the tail slots, and newcomers join ACTIVE
    (they hold the aggregate, the freshest state in the fleet). A shrink
    that would leave no active worker is the same config error as an
    all-straggler round and raises ``no_active_error`` at the resize, not
    as NaNs rounds later.
    """
    if new_p < 1:
        raise ValueError(f"resize needs new_p >= 1, got {new_p}")
    active = jnp.asarray(active).astype(bool)
    old_p = active.shape[0]
    if new_p <= old_p:
        out = active[:new_p]
        weights_mod._reject_concrete_all_false(out)
        return out
    return jnp.concatenate([active, jnp.ones((new_p - old_p,), bool)])


# ---------------------------------------------------------------------------
# Masked Eq. 10 + late-join over a tree (compat entry point)
# ---------------------------------------------------------------------------

def _resolve_active(theta: jax.Array, active: Optional[jax.Array]):
    if active is None:
        return jnp.ones(theta.shape, bool)
    return active.astype(bool)


# schedule keyword of the pre-two-axis API -> composed backend name
_SCHEDULE_NAMES = {"einsum": "einsum", "all_reduce": "shard_map:f32",
                   "rs_ag": "rs_ag"}


def weighted_aggregate_async(params: Dict, axes: Dict, theta: jax.Array,
                             active: Optional[jax.Array], beta,
                             mesh=None, schedule: str = "all_reduce",
                             comm_dtype=jnp.float32) -> Dict:
    """Apply the masked Eq. 10 + late-join to all worker leaves.

    ``schedule``: "einsum" (meshless), "all_reduce" (masked psum under
    shard_map) or "rs_ag" (reduce-scatter + FMA + all-gather). Thin compat
    wrapper over the composed backends — the collectives are the SAME
    leaves as the synchronous path with the late-join mask riding
    ``ctx.active``: stragglers carry theta == 0, so the reduce already
    excludes them, and inactive workers adopt the aggregate m (analytically
    equal to sum_j theta_j [(1-beta)x_j + beta*m]).
    """
    if schedule not in _SCHEDULE_NAMES:
        raise ValueError(f"unknown async schedule {schedule!r}; "
                         f"known: {sorted(_SCHEDULE_NAMES)}")
    ctx = backends.AggregationContext(
        mesh=mesh, comm_dtype=comm_dtype,
        active=_resolve_active(theta, active))
    return backends.aggregate_with(_SCHEDULE_NAMES[schedule], params, axes,
                                   theta, beta, ctx=ctx)


# ---------------------------------------------------------------------------
# One compiled Alg. 4 round + the driver loop
# ---------------------------------------------------------------------------

def _resolve_backend(backend: str, ctx):
    name = async_backend_name(backend)
    backend_obj = backends.get_backend(name)
    if getattr(backend_obj, "needs_mesh", False) and ctx.mesh is None:
        raise ValueError(
            f"async aggregation backend {name!r} places explicit "
            f"collectives and needs ctx.mesh (AggregationContext(mesh=...))")
    return backend_obj


def _resolve_policy(policy, strategy: str, a_tilde: float):
    """``policy`` spec/object wins; ``None`` aliases the legacy knobs to
    their (stateless, bitwise-identical) kernel policy. The legacy arg is
    kernel-checked first — ``strategy="ema"`` must keep raising the
    unknown-strategy error, not silently build a stateful pipeline."""
    if policy is None:
        weights_mod.validate_config_spec(strategy)
        return weights_mod.parse_policy(strategy, default_a=a_tilde)
    return weights_mod.as_policy(policy, default_a=a_tilde)


def _on_auto_mesh(ctx):
    if ctx.mesh is None:
        return ctx
    return dataclasses.replace(ctx, mesh=auto_mesh(ctx.mesh))


def _mesh_shardings(mesh):
    """(worker-split, replicated) shardings for a round on ``mesh``, or
    None without one. Every params and batch leaf of these rounds is
    worker-stacked, so one split sharding covers each tree; pinning it on
    both sides of the donated jit keeps input and output buffers the same
    size on every device."""
    if mesh is None:
        return None
    return worker_split(mesh), replicated(mesh)


def build_async_round(grad_fn: Callable, axes: Dict, *, lr: float,
                      beta: float = 0.9, a_tilde: float = 1.0,
                      strategy: str = "boltzmann",
                      policy=None,
                      backend: str = "async_shard_map",
                      ctx: Optional[backends.AggregationContext] = None,
                      jit: bool = True) -> Callable:
    """Build one jitted p-of-(p+b) round.

    Stateless policy (the default ``strategy``/``a_tilde`` aliases):
    ``round_fn(params, batch, active) -> (params, losses, theta)``.
    Stateful policy (``policy="ema(0.9)|..."``): the policy state threads
    through the round —
    ``round_fn(params, batch, active, pstate)
        -> (params, losses, theta, pstate)``
    (``round_fn.stateful`` tells the caller which signature it got).

    The local steps, the masked policy theta, the Eq. 10 aggregate, and the
    straggler late-join all trace together — ``active`` is a ``(w,)`` bool
    input, so a new straggler set per round costs no recompilation.
    ``backend`` accepts any composed ``schedule:codec`` spec (or a legacy
    ``async_*`` alias).

    ``grad_fn(params_stacked, batch) -> (losses (w,), grads_stacked)`` —
    the same contract as ``async_sim.run_parallel_sgd``.
    """
    ctx = _on_auto_mesh(backends.DEFAULT_CONTEXT if ctx is None else ctx)
    backend_obj = _resolve_backend(backend, ctx)
    pol = _resolve_policy(policy, strategy, a_tilde)
    w_axes = jax.tree.map(lambda ax: ("worker",) + tuple(ax), axes,
                          is_leaf=_axes_is_leaf)

    def _advance(params, batch, active, pstate):
        losses, grads = grad_fn(params, batch)
        params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        theta, pstate = pol(losses, active, pstate)
        params = backend_obj.aggregate(
            params, w_axes, theta, beta,
            ctx=dataclasses.replace(ctx, active=active))
        return params, losses, theta, pstate

    if pol.stateful:
        def round_fn(params, batch, active, pstate):
            return _advance(params, batch, active, pstate)
    else:
        def round_fn(params, batch, active):
            return _advance(params, batch, active, ())[:3]

    if jit:
        sh = _mesh_shardings(ctx.mesh)
        if sh is None:
            round_fn = jax.jit(round_fn, donate_argnums=(0,))
        else:
            split, rep = sh
            n_in = 4 if pol.stateful else 3
            round_fn = jax.jit(
                round_fn, donate_argnums=(0,),
                in_shardings=(split, split) + (rep,) * (n_in - 2),
                out_shardings=(split,) + (rep,) * (n_in - 1))
    round_fn.stateful = pol.stateful
    return round_fn


def build_split_async_round(grad_fn: Callable, axes: Dict, *, lr: float,
                            beta: float = 0.9,
                            policy="boltzmann",
                            backend: str = "async_einsum",
                            ctx: Optional[backends.AggregationContext]
                            = None,
                            jit: bool = True) -> Tuple[Callable, Callable]:
    """The round split at the host's measurement point (measured-time mode).

    ``measure_times=True`` needs the host in the loop BETWEEN the local
    steps and the aggregation — the activity mask of Alg. 4 line 16 (the
    first p arrivals) is derived from each worker's measured completion of
    its local steps, so the fused single-program round of
    ``build_async_round`` is split into two jitted programs:

        ``local_fn(params, batch) -> (params, losses)``
            tau local steps, no collectives — per-device completion of
            THIS program is what ``measure_round_times`` observes;
        ``agg_fn(params, losses, active, pstate)
            -> (params, theta, pstate)``
            masked policy theta + Eq. 10 aggregate + straggler late-join.
    """
    ctx = _on_auto_mesh(backends.DEFAULT_CONTEXT if ctx is None else ctx)
    backend_obj = _resolve_backend(backend, ctx)
    pol = weights_mod.as_policy(policy)
    w_axes = jax.tree.map(lambda ax: ("worker",) + tuple(ax), axes,
                          is_leaf=_axes_is_leaf)

    def local_fn(params, batch):
        losses, grads = grad_fn(params, batch)
        params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return params, losses

    def agg_fn(params, losses, active, pstate):
        theta, pstate = pol(losses, active, pstate)
        params = backend_obj.aggregate(
            params, w_axes, theta, beta,
            ctx=dataclasses.replace(ctx, active=active))
        return params, theta, pstate

    if jit:
        sh = _mesh_shardings(ctx.mesh)
        if sh is None:
            local_fn = jax.jit(local_fn, donate_argnums=(0,))
            agg_fn = jax.jit(agg_fn, donate_argnums=(0,))
        else:
            # losses stay split: measure_round_times reads each device's
            # own rows as they arrive.
            split, rep = sh
            local_fn = jax.jit(local_fn, donate_argnums=(0,),
                               in_shardings=(split, split),
                               out_shardings=(split, split))
            agg_fn = jax.jit(agg_fn, donate_argnums=(0,),
                             in_shardings=(split, split, rep, rep),
                             out_shardings=(split, rep, rep))
    return local_fn, agg_fn


def measure_round_times(x: jax.Array, w: int) -> np.ndarray:
    """Measured per-device completion times of a worker-stacked output.

    Blocks each addressable shard of ``x`` (device order) and records the
    host clock as its data arrives: on a real mesh, a device's shards
    become ready when THAT device finishes its program, so the recorded
    instants are per-device arrival upper-bounds (monotone in block order —
    a shard blocked later can only report later). Workers sharing a device
    (w/p > 1 copies, or a single host device) share its time; downstream
    tie-breaks are by worker index, matching the stable first-p-arrivals
    rule. This is the measured signal that replaces the host
    ``StepTimeModel``.
    """
    t0 = time.perf_counter()
    times = np.full((w,), np.nan)
    shards = sorted(x.addressable_shards, key=lambda s: s.device.id)
    for sh in shards:
        jax.block_until_ready(sh.data)
        dt = time.perf_counter() - t0
        idx = sh.index[0] if sh.index else slice(None)
        times[idx] = dt
    if np.isnan(times).any():              # non-addressable rows (multi-host)
        times = np.where(np.isnan(times), np.nanmax(times), times)
    return times


def run_parallel_sgd_on_device(grad_fn: Callable, params0: Dict, axes: Dict,
                               batches, *, n_workers: int, backups: int,
                               tau: int, rounds: int, lr: float,
                               time_model: Optional[StepTimeModel] = None,
                               schedule: Optional[StragglerSchedule] = None,
                               measure_times: bool = False,
                               a_tilde: float = 1.0, beta: float = 0.9,
                               strategy: str = "boltzmann",
                               policy=None,
                               synchronous: bool = False,
                               backend: str = "async_shard_map",
                               ctx: Optional[backends.AggregationContext]
                               = None) -> AsyncResult:
    """On-device drop-in for ``async_sim.run_parallel_sgd``.

    Same scheduling semantics (inject the same ``schedule`` for parity),
    but every round executes as one jitted SPMD program through a composed
    aggregation spec. ``AsyncResult.params`` is the final worker-stacked
    parameter tree the parity harness compares leaf-for-leaf against the
    host simulation's.

    ``policy`` (spec string or ``WeightPolicy``) selects the worker-
    assessment policy; stateful policy state threads across the jitted
    rounds. ``None`` keeps the legacy ``strategy``/``a_tilde`` kernels.

    ``measure_times=True`` drives Alg. 4 line 16 from MEASURED per-device
    round times instead of any host-side model: no ``time_model`` or
    ``schedule`` is needed. The round splits at the measurement point
    (``build_split_async_round``) — after each round's local steps the
    host records every device's completion (``measure_round_times``), the
    first ``n_workers`` arrivals form the aggregation set, and the measured
    times are fed to the policy (``observe_times`` — the ``time_aware``
    stage weights workers by real speed). ``AsyncResult.round_times`` holds
    the measurements; ``wall`` is the sum of the per-round gate times
    (the p-th measured arrival).
    """
    w = n_workers + backups
    pol = _resolve_policy(policy, strategy, a_tilde)
    params = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (w,) + x.shape), params0)

    if measure_times:
        if schedule is not None or time_model is not None:
            raise ValueError(
                "measure_times=True derives the activity schedule from "
                "measured per-device round times; don't pass time_model= "
                "or schedule= as well")
        local_fn, agg_fn = build_split_async_round(
            grad_fn, axes, lr=lr, beta=beta, policy=pol, backend=backend,
            ctx=ctx)
        pstate = pol.init_state(w)
        losses_hist, times_hist = [], []
        wall = 0.0
        dropped = 0
        for r in range(rounds):
            batch = next(batches)                  # (w, tau*b_local, ...)
            params, losses = local_fn(params, batch)
            times = measure_round_times(losses, w)
            order = np.argsort(times, kind="stable")
            active = np.zeros((w,), bool)
            active[order[:n_workers]] = True       # first p arrivals
            wall += float(times[order[n_workers - 1]])
            dropped += int(backups)
            pstate = pol.observe_times(pstate, jnp.asarray(times))
            params, _, pstate = agg_fn(params, losses, jnp.asarray(active),
                                       pstate)
            losses_hist.append(float(np.asarray(losses)[active].mean()))
            times_hist.append(times)
        return AsyncResult(np.asarray(losses_hist), wall, dropped, params,
                           np.asarray(times_hist))

    if schedule is None:
        if time_model is None:
            raise ValueError("pass either time_model= or schedule= "
                             "(or measure_times=True)")
        schedule = make_schedule(time_model, rounds=rounds, tau=tau,
                                 n_workers=n_workers, backups=backups,
                                 synchronous=synchronous)
    validate_active_rounds(schedule.active, rounds=rounds)
    round_fn = build_async_round(grad_fn, axes, lr=lr, beta=beta,
                                 a_tilde=a_tilde, strategy=strategy,
                                 policy=pol, backend=backend, ctx=ctx)
    pstate = pol.init_state(w)

    losses_hist = []
    for r in range(rounds):
        batch = next(batches)                      # (w, tau*b_local, ...)
        active = jnp.asarray(schedule.active[r])
        if round_fn.stateful:
            params, losses, _, pstate = round_fn(params, batch, active,
                                                 pstate)
        else:
            params, losses, _ = round_fn(params, batch, active)
        losses_np = np.asarray(losses)
        losses_hist.append(float(losses_np[schedule.active[r]].mean()))

    wall = float(schedule.round_wall[:rounds].sum())
    dropped = int((~schedule.active[:rounds]).sum())
    return AsyncResult(np.asarray(losses_hist), wall, dropped, params)

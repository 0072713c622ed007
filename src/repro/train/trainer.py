"""Host-side training loop: rounds, order search, checkpointing.

The device side (one WASGD round) is ``train/step.py``; the Trainer drives
it with batches whose per-worker sample order comes from the paper's
``Judge``/``OrderGen`` search (core/order.py), and feeds the round's Judge
scores back into the order state.
"""
from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import TrainConfig, WASGDConfig
from repro.core import replicate_workers
from repro.core.aggregate import worker_axes
from repro.core.membership import (MembershipSchedule, WorkerSet,
                                   map_opt_state, resize_train_state)
from repro.core.order import OrderState
from repro.core.weights import policy_from_config
from repro.data.pipeline import RoundPrefetcher
from repro.obs import (NULL, MembershipChange, RoundTrace, WorkerAssessment,
                       summarize_policy_state)
from repro.optim import make_optimizer
from repro.parallel.sharding import (auto_mesh, num_workers, replicated,
                                     tree_shardings, worker_split)
from repro.train.state import TrainState, init_state
from repro.train.step import build_train_step, init_comm_state, wasgd_rule
from repro.train import step as step_mod


def _wasgd_rule_for(tcfg, mesh=None, overlap=None):
    """Sync Eq. 10 rule, or the Alg. 4 masked rule when the config selects
    ``async_mode="on_device"`` (the mask rides in ``state.comm_state``).
    ``overlap`` is the compute thunk threaded between the aggregation
    schedule's collective phases (train/step.py)."""
    if tcfg.wasgd.async_mode == "on_device":
        return step_mod.async_wasgd_rule(tcfg.wasgd, mesh=mesh,
                                         overlap=overlap)
    return step_mod.wasgd_rule(tcfg.wasgd, mesh=mesh, overlap=overlap)


def _state_shardings(mesh, shapes: TrainState, axes: Dict,
                     n_workers: int) -> TrainState:
    """Where a ``TrainState`` lives on ``mesh``: the params by their logical
    axes (``parallel/sharding.py``; the worker axis goes to the mesh's
    worker axes), optimizer moments like the params they follow, and the
    rest (round counter, energies, comm state) replicated."""
    shards = num_workers(mesh)
    if n_workers % shards:
        raise ValueError(
            f"{n_workers} workers do not divide over the mesh's {shards} "
            f"worker shards")
    rep = replicated(mesh)
    params = tree_shardings(mesh, shapes.params, axes)

    def replicate(tree):
        return jax.tree.map(lambda _: rep, tree)

    return TrainState(
        step=rep, params=params,
        opt_state=map_opt_state(shapes.opt_state, axes, lambda _: params,
                                replicate),
        energy=rep, comm_state=replicate(shapes.comm_state))


RULES = {
    "wasgd": _wasgd_rule_for,
    "wasgd+": _wasgd_rule_for,
    "spsgd": lambda tcfg, mesh=None, overlap=None: step_mod.spsgd_rule(),
    "easgd": lambda tcfg, mesh=None, overlap=None:
        step_mod.easgd_rule(alpha=0.9 / 16),
    "omwu": lambda tcfg, mesh=None, overlap=None: step_mod.mwu_rule(),
    "mmwu": lambda tcfg, mesh=None, overlap=None: step_mod.mwu_rule(),
    "seq": lambda tcfg, mesh=None, overlap=None: step_mod.no_comm_rule(),
}


class Trainer:
    def __init__(self, loss_fn, params: Dict, axes: Dict, tcfg: TrainConfig,
                 n_workers: int, rule: str = "wasgd",
                 replicate: bool = True, jit: bool = True,
                 easgd_alpha: Optional[float] = None, mesh=None,
                 overlap=None, pipeline: Optional[str] = None):
        """``mesh`` feeds the aggregation-backend context — required when
        ``tcfg.wasgd`` selects a schedule that places explicit collectives
        (``shard_map``/``rs_ag``, incl. legacy ``sharded_aggregate=True``).
        With a mesh the state lives on it: every worker-stacked params leaf
        (and its optimizer moments) has its worker rows split over the
        mesh's worker axes by the logical-axis rules (``_state_shardings``),
        each round's batch is staged split the same way, and the jitted
        round pins those shardings on
        its inputs and outputs so the donated state aliases buffer for
        buffer. ``resize`` and ``resume`` cannot keep that placement yet and
        raise under a mesh. ``overlap`` (nullary compute thunk; may return
        any pytree) rides between the schedule's collective phases; its
        per-round result lands in ``history[r]["overlap"]``.

        ``pipeline="parity" | "speculative"`` software-pipelines the round
        (``train/step.py``): ``run`` wraps the batch iterator in a
        double-buffered ``RoundPrefetcher`` so round ``r+1``'s host staging
        and first worker-major microbatch ride the aggregation schedule's
        phase-gap seam during round ``r``'s communication. ``"parity"`` is
        bitwise-identical to the unpipelined trainer; ``"speculative"``
        additionally runs the next round's Judge/energy forward on
        pre-aggregate params (stale by one Eq. 10 step, measured per round
        in ``history[r]["spec_dev"]`` / ``["spec_bound"]``). Only the
        wasgd/wasgd+ rules thread the seam. NOTE: with an
        ``OrderedDataset``, the prefetcher's generator runs up to
        ``RoundPrefetcher.run_ahead()`` (= depth + 2, default 4) rounds
        ahead, so pass ``boundary_delay=RoundPrefetcher.run_ahead()`` to
        keep OrderGen's per-segment decision aligned with the recorded
        Judge scores."""
        self.tcfg = tcfg
        self.workers = WorkerSet(n_workers)
        self.rule_name = rule
        self.pipeline = pipeline
        if pipeline is not None and rule not in ("wasgd", "wasgd+"):
            raise ValueError(
                f"pipeline={pipeline!r} threads the seam thunk through the "
                f"wasgd/wasgd+ rules only (got rule={rule!r})")
        self.optimizer = make_optimizer(
            tcfg.optimizer, tcfg.learning_rate, tcfg.momentum,
            tcfg.weight_decay)

        expert_copies = getattr(tcfg, "expert_copies", False)
        self.axes = worker_axes(axes, expert_copies) if replicate else axes

        def build(params):
            """The initial TrainState."""
            if replicate:
                params, _ = replicate_workers(params, axes, n_workers,
                                              expert_copies=expert_copies)
            opt_state = self.optimizer.init(params)
            comm_state = init_comm_state(rule, params, self.axes, n_workers,
                                         wcfg=tcfg.wasgd)
            return init_state(params, opt_state, n_workers, comm_state)

        self._loss_fn = loss_fn
        self._mesh = None if mesh is None else auto_mesh(mesh)
        if mesh is None:
            self.state: TrainState = build(params)
        else:
            # Built by one jit straight into its shardings: the p worker
            # copies never exist together on one device.
            self._state_sh = _state_shardings(
                self._mesh, jax.eval_shape(build, params), self.axes,
                n_workers)
            self.state = jax.jit(build, out_shardings=self._state_sh)(params)
        self._overlap = overlap
        self._jit = jit
        self._easgd_alpha = easgd_alpha
        self._ckpt = None                      # lazy AsyncCheckpointer
        self._telemetry = NULL                 # set per run(telemetry=)
        self._phased_cache: Dict[int, Any] = {}
        self._build_step()
        self.history: list = []

    @property
    def n_workers(self) -> int:
        """The live worker count — a round-boundary-mutable property of the
        ``WorkerSet`` (changes only through ``resize``)."""
        return self.workers.p

    def _build_step(self):
        """(Re)build the jitted round step for the current membership. The
        step closes over ``n_workers`` (batch reshape, mask shapes), so
        every ``resize`` swaps it; built steps are memoized per worker
        count — a chaos schedule that revisits a ``p`` reuses that jit
        wrapper (and its compilation cache) instead of recompiling."""
        if not hasattr(self, "_step_cache"):
            self._step_cache = {}
        cached = self._step_cache.get(self.n_workers)
        if cached is not None:
            self._step, self._primer = cached
            return
        if self.rule_name == "easgd" and self._easgd_alpha is not None:
            rule_fn = step_mod.easgd_rule(self._easgd_alpha)
        else:
            rule_fn = RULES[self.rule_name](self.tcfg, mesh=self._mesh,
                                            overlap=self._overlap)
        step = build_train_step(self._loss_fn, self.optimizer, self.axes,
                                self.tcfg.wasgd, self.n_workers,
                                rule=rule_fn, pipeline=self.pipeline)
        self._primer = getattr(step, "primer", None)
        if self._jit and self._mesh is not None:
            step = self._jit_on_mesh(step)
        elif self._jit:
            step = jax.jit(step, donate_argnums=(0,))
            if self._primer is not None:
                self._primer = jax.jit(self._primer)
        self._step = step
        self._step_cache[self.n_workers] = (self._step, self._primer)

    def _jit_on_mesh(self, step):
        """Jit the round with the state's shardings pinned on both sides:
        batches, the staged next microbatch and the pipeline carry are
        worker-major and split like the state; metrics come back
        replicated."""
        mesh, state_sh = self._mesh, self._state_sh
        split, rep = worker_split(mesh), replicated(mesh)
        if self._primer is None:
            return jax.jit(step, donate_argnums=(0,),
                           in_shardings=(state_sh, split),
                           out_shardings=(state_sh, rep))
        self._primer = jax.jit(self._primer,
                               in_shardings=(state_sh.params, split),
                               out_shardings=split)
        return jax.jit(step, donate_argnums=(0,),
                       in_shardings=(state_sh, split, split, split),
                       out_shardings=(state_sh, rep, split))

    def _stage(self, batch):
        """Put one round's host batch on the devices that consume it."""
        if self._mesh is None:
            return jax.device_put(batch)
        return jax.device_put(batch, worker_split(self._mesh))

    def _no_mesh(self, what: str):
        if self._mesh is not None:
            raise NotImplementedError(
                f"{what} under a mesh would gather the worker-split state "
                f"onto one device; it is not supported yet")

    def _policy_for_resize(self):
        if self.rule_name not in ("wasgd", "wasgd+"):
            return None
        pol = policy_from_config(self.tcfg.wasgd)
        return pol if pol.stateful else None

    def resize(self, new_p: int, round: Optional[int] = None):
        """Commit a membership change at a round boundary: re-shard the
        worker-stacked train state (survivors keep their slots bitwise,
        newcomers adopt the aggregate — core/membership.py), re-shard the
        comm state through ``init_comm_state(prev=)``, and rebuild the
        jitted step for the new shapes. Returns the ``MembershipEvent``
        (or None when ``new_p`` is already the live count)."""
        if self.rule_name not in ("wasgd", "wasgd+"):
            raise ValueError(
                f"elastic membership is a wasgd/wasgd+ capability — rule "
                f"{self.rule_name!r} pins worker count at construction")
        new_p = int(new_p)
        if new_p == self.n_workers:
            return None
        self._no_mesh("resize")
        comm = init_comm_state(self.rule_name, self.state.params, self.axes,
                               new_p, wcfg=self.tcfg.wasgd,
                               prev=self.state.comm_state)
        self.state = resize_train_state(self.state, self.axes, new_p,
                                        policy=self._policy_for_resize(),
                                        comm_state=comm)
        old_p = self.n_workers
        event = self.workers.resize(new_p, round=round)
        self._build_step()
        if event is not None and self._telemetry.enabled:
            self._telemetry.emit(MembershipChange(
                round=round if round is not None else -1,
                old_p=old_p, new_p=new_p,
                generation=getattr(self.workers, "generation", 0)))
        return event

    # -- sharded, resumable checkpoints -----------------------------------

    def _topology(self, round: int) -> Dict:
        """The membership record a sharded checkpoint carries: enough for a
        restore to rebuild the saved state's shapes (``p``), place itself in
        the run (``round``), and verify the rule/policy/comm-state structure
        it is being restored into."""
        from repro.checkpoint.io import _flatten
        return {
            "p": self.n_workers,
            "round": int(round),
            "rule": self.rule_name,
            "policy": self.tcfg.wasgd.policy,
            "comm_state": sorted(_flatten({"cs": self.state.comm_state})),
        }

    def save_checkpoint(self, path: str, round: int):
        """Async sharded save of the FULL train state (params, optimizer
        state, energy, comm state — not the params-only legacy format). The
        call returns after an on-device snapshot; serialization rides the
        next rounds' device time (checkpoint/io.AsyncCheckpointer)."""
        from repro.checkpoint import AsyncCheckpointer
        if self._ckpt is None:
            self._ckpt = AsyncCheckpointer(telemetry=self._telemetry)
        self._ckpt.save(path, self.state, meta={"round": int(round)},
                        topology=self._topology(round))

    def resume(self, path: str, allow_cast: bool = False) -> int:
        """Restore a checkpoint into this trainer and return the round to
        resume at. A sharded checkpoint saved under a DIFFERENT worker count
        restores at its recorded ``p`` (the manifest topology shapes the
        template) and then resizes to this trainer's live membership — the
        saved survivors land bitwise in their slots, extra slots are filled
        by the resize machinery's late-join rule."""
        from repro.checkpoint import restore, saved_topology
        self._no_mesh("resume")
        info = saved_topology(path)
        topo = info["topology"]
        saved_p = int(topo.get("p", self.n_workers))
        if topo.get("rule") is not None and topo["rule"] != self.rule_name:
            raise ValueError(
                f"checkpoint was saved by rule {topo['rule']!r}; this "
                f"trainer runs {self.rule_name!r}")
        pol = self._policy_for_resize()
        like = self.state
        if saved_p != self.n_workers:
            if self.rule_name not in ("wasgd", "wasgd+"):
                raise ValueError(
                    f"checkpoint p={saved_p} != trainer p={self.n_workers} "
                    f"and rule {self.rule_name!r} has no elastic resize")
            like = resize_train_state(self.state, self.axes, saved_p,
                                      policy=pol)
        restored, meta = restore(path, like, allow_cast=allow_cast)
        if saved_p != self.n_workers:
            restored = resize_train_state(restored, self.axes,
                                          self.n_workers, policy=pol)
        self.state = restored
        return int(topo.get("round", meta.get("round", 0)))

    # -- telemetry ---------------------------------------------------------

    def _phased_step(self):
        """The phase-fenced instrumented round for the current membership
        (memoized per worker count, like ``_build_step``), or None when
        this run cannot decompose into phases — pipelined rounds and the
        baseline rules fall back to a coarse fenced RoundTrace. Only
        consulted when a real telemetry sink is attached; the default
        path never builds (or pays for) it."""
        if self.rule_name not in ("wasgd", "wasgd+") \
                or self.pipeline is not None or not self._jit:
            return None
        fn = self._phased_cache.get(self.n_workers)
        if fn is None:
            fn = step_mod.build_phased_train_step(
                self._loss_fn, self.optimizer, self.axes, self.tcfg.wasgd,
                self.n_workers, mesh=self._mesh, overlap=self._overlap)
            self._phased_cache[self.n_workers] = fn
        return fn

    def _emit_round(self, tele, r: int, rec: Dict, total_s: float,
                    host_staging_s: float, phase_times) -> None:
        """Emit the round's RoundTrace + WorkerAssessment. Called after
        the metrics readback (outside any transfer guard), so the host
        conversions here add no transfers the fused path would not do."""
        tele.emit(RoundTrace(
            round=r, total_s=total_s, host_staging_s=host_staging_s,
            phases=dict(phase_times) if phase_times is not None else {},
            detail="phased" if phase_times is not None else "fused",
            p=self.n_workers))
        theta = rec.get("theta")
        h = rec.get("h")
        active = rec.get("active")
        pstate = None
        if self.rule_name in ("wasgd", "wasgd+"):
            cs = self.state.comm_state
            if isinstance(cs, dict):
                pstate = cs.get("policy")
            elif self.tcfg.wasgd.async_mode != "on_device":
                pstate = cs
        tele.emit(WorkerAssessment(
            round=r,
            theta=(np.ravel(theta).astype(float).tolist()
                   if theta is not None else []),
            energies=(np.ravel(h).astype(float).tolist()
                      if h is not None else []),
            theta_entropy=float(rec.get("theta_entropy", 0.0)),
            active=([bool(x) for x in np.ravel(active)]
                    if active is not None else None),
            policy=self.tcfg.wasgd.policy or self.tcfg.wasgd.strategy,
            policy_state=summarize_policy_state(pstate)))

    def run(self, batches: Iterator[Dict], n_rounds: int,
            order_state: Optional[OrderState] = None,
            segment_fn: Optional[Callable[[int], int]] = None,
            log_every: int = 0, metrics_path: Optional[str] = None,
            checkpoint_every: int = 0,
            checkpoint_path: Optional[str] = None,
            straggler_schedule=None,
            membership_schedule: Optional[MembershipSchedule] = None,
            resume_from: Optional[str] = None,
            serve_hook: Optional[Callable[[int, Dict, Dict], Any]] = None,
            serve_every: int = 1,
            transfer_guard: Optional[str] = None,
            telemetry=None) -> Dict:
        """``batches`` is a round-batch iterator, or an ``OrderedDataset``
        instance — passing the dataset itself lets a pipelined run VALIDATE
        that its OrderGen decisions are deferred past the prefetcher's
        run-ahead (``boundary_delay``), and defaults ``order_state`` /
        ``segment_fn`` from the dataset.

        ``straggler_schedule`` (async_mode="on_device" only): a
        ``StragglerSchedule`` or ``(rounds, w)`` bool array covering all
        ``n_rounds``; round ``r``'s activity mask is injected into
        ``state.comm_state`` before the step, so the jitted Alg. 4 round
        excludes that round's stragglers.

        ``membership_schedule`` makes the run ELASTIC: at each round
        boundary where the schedule's ``p_of(r)`` differs from the live
        ``WorkerSet``, the trainer resizes (``Trainer.resize``), the
        OrderedDataset re-shards its per-worker index rows, and the round
        generator (and prefetcher, when pipelined) restarts at round ``r``
        with the new worker count. Requires ``batches`` to be the
        ``OrderedDataset`` itself — a bare iterator bakes in a fixed ``p``.
        Mutually exclusive with ``straggler_schedule`` (whose mask table is
        a fixed ``(rounds, p)``); transient stragglers within a fixed
        membership are that path, membership changes are this one.

        ``checkpoint_every``/``checkpoint_path`` save the FULL train state
        every N rounds as a sharded, topology-aware checkpoint
        (``checkpoint_path/round_{r+1}``), asynchronously — serialization
        rides the following rounds. ``resume_from`` restores such a
        checkpoint (``Trainer.resume``) and continues at its recorded
        round; a checkpoint from a different worker count resizes into this
        trainer's membership on the way in.

        ``serve_hook(round, params, axes)`` is called every ``serve_every``
        rounds after the step with the live worker-stacked params — the
        train-to-serve bridge (``serve.HotSwapBridge`` extracts the beta=1
        consensus and hot-swaps it into a running engine, recording per-swap
        staleness).

        ``transfer_guard`` (debug): a ``jax.transfer_guard`` level
        (``"log"`` / ``"disallow"``, see jax docs) applied around each
        jitted step call. Round batches are explicitly ``jax.device_put``
        first — iterator batches are host arrays and their per-round h2d
        staging is expected — so the guard only fires on implicit
        transfers INSIDE the round (the ``.item()``/``np.*``-in-hot-path
        family; ``tools/trace_audit.py`` runs the same check over the
        backend grid). Metrics are read back after the guard exits.

        ``telemetry`` is a ``repro.obs`` sink (``RingSink``/``JsonlSink``;
        default ``NullSink`` = off). With a real sink attached the run
        emits per-round ``RoundTrace`` (wasgd/wasgd+ unpipelined rounds run
        the phase-fenced instrumented step — per-phase device-accurate
        breakdown; pipelined rounds and baseline rules report a fenced
        total only) and ``WorkerAssessment`` events, plus
        ``MembershipChange`` on elastic resizes and ``CheckpointSave`` from
        the async checkpoint writer. With the default ``NullSink`` every
        instrumentation site short-circuits: no fences, no host readbacks,
        bitwise-identical params (tests/test_obs.py)."""
        from repro.data.pipeline import OrderedDataset
        ds = None
        if isinstance(batches, OrderedDataset):
            ds = batches
            if self.pipeline is not None \
                    and ds.boundary_delay < RoundPrefetcher.run_ahead():
                raise ValueError(
                    f"pipelined run: the prefetcher's generator runs up to "
                    f"{RoundPrefetcher.run_ahead()} rounds ahead of score "
                    f"recording, but this OrderedDataset commits OrderGen "
                    f"decisions after boundary_delay={ds.boundary_delay} "
                    f"rounds — its keep-or-reshuffle would read truncated "
                    f"Judge scores; build it with boundary_delay="
                    f"RoundPrefetcher.run_ahead()")
            if order_state is None and segment_fn is None:
                order_state, segment_fn = ds.order, ds.segment_of_round
        elif self.pipeline is not None and order_state is not None:
            import warnings
            warnings.warn(
                "pipelined run over a bare iterator with an order_state: "
                "the Trainer cannot verify the generator defers its "
                "OrderGen decisions past the prefetch run-ahead "
                f"({RoundPrefetcher.run_ahead()} rounds); pass the "
                "OrderedDataset itself (run(ds, ...)) or build it with "
                "boundary_delay=RoundPrefetcher.run_ahead() to avoid "
                "decisions that miss the final rounds' Judge scores",
                stacklevel=2)
        active_rounds = None
        if straggler_schedule is not None:
            if self.tcfg.wasgd.async_mode != "on_device":
                raise ValueError(
                    "straggler_schedule requires "
                    "WASGDConfig(async_mode='on_device')")
            if self.rule_name not in ("wasgd", "wasgd+"):
                # only the Alg. 4 rule reads the mask out of comm_state —
                # fail loud instead of running a fully synchronous baseline
                # labeled as a straggler experiment.
                raise ValueError(
                    f"straggler_schedule is only consumed by the wasgd/"
                    f"wasgd+ rules (got rule={self.rule_name!r})")
            active_rounds = np.asarray(
                getattr(straggler_schedule, "active", straggler_schedule),
                bool)
            if len(active_rounds) < n_rounds:
                raise ValueError(
                    f"straggler_schedule covers {len(active_rounds)} rounds "
                    f"but run() was asked for {n_rounds}; build the "
                    f"schedule with rounds={n_rounds} (silent reuse would "
                    f"correlate the exclusion statistics)")
            from repro.core.async_device import validate_active_rounds
            validate_active_rounds(active_rounds, rounds=n_rounds)
        if membership_schedule is not None:
            if self.rule_name not in ("wasgd", "wasgd+"):
                raise ValueError(
                    f"membership_schedule is a wasgd/wasgd+ capability "
                    f"(got rule={self.rule_name!r})")
            if straggler_schedule is not None:
                raise ValueError(
                    "membership_schedule and straggler_schedule are "
                    "mutually exclusive: the straggler mask table is a "
                    "fixed (rounds, p) — model leaving workers as "
                    "membership events instead")
            if ds is None:
                raise ValueError(
                    "membership_schedule requires run(OrderedDataset, ...) "
                    "— a bare batch iterator bakes in a fixed worker "
                    "count, so its rounds cannot be re-sharded at a "
                    "membership event")
        start = 0
        if resume_from is not None:
            start = self.resume(resume_from)
            if start >= n_rounds:
                raise ValueError(
                    f"checkpoint {resume_from} is at round {start}, at or "
                    f"past n_rounds={n_rounds} — nothing left to run")
            if ds is not None and ds.p != self.n_workers:
                ds.resize(self.n_workers)
        tele = telemetry if telemetry is not None else NULL
        self._telemetry = tele
        obs_on = bool(getattr(tele, "enabled", False))
        if self._ckpt is not None:
            self._ckpt.telemetry = tele
        if ds is not None:
            batches = ds.batches(start_round=start)
        t0 = time.time()
        if transfer_guard is not None:
            _guard = lambda: jax.transfer_guard(transfer_guard)  # noqa: E731
        else:
            _guard = contextlib.nullcontext
        mf = open(metrics_path, "a") if metrics_path else None
        prefetch = None
        if self.pipeline is not None and not isinstance(batches,
                                                        RoundPrefetcher):
            prefetch = RoundPrefetcher(batches, self.n_workers,
                                       self.tcfg.wasgd.tau)
            batches = prefetch
        carry = None
        try:
            for r in range(start, n_rounds):
                if membership_schedule is not None:
                    target = membership_schedule.p_of(r)
                    if target != self.n_workers:
                        self.resize(target, round=r)
                        ds.resize(target)
                        gen = ds.batches(start_round=r)
                        if prefetch is not None:
                            prefetch.resize(target, gen)
                        else:
                            batches = gen
                        carry = None      # re-prime the pipelined seam
                t_host = time.perf_counter() if obs_on else 0.0
                if self.pipeline is not None:
                    batch, next_first = next(batches)
                else:
                    batch = next(batches)
                if active_rounds is not None:
                    # comm_state is the bare (w,) mask for stateless
                    # policies, or {"active": mask, "policy": state} when a
                    # stateful worker-assessment policy rides along — only
                    # the mask is the host's to replace.
                    mask = jnp.asarray(active_rounds[r])
                    cs = self.state.comm_state
                    cs = ({**cs, "active": mask} if isinstance(cs, dict)
                          else mask)
                    self.state = self.state._replace(comm_state=cs)
                if transfer_guard is not None or self._mesh is not None:
                    batch = self._stage(batch)
                    if self.pipeline is not None:
                        next_first = self._stage(next_first)
                host_staging_s = (time.perf_counter() - t_host
                                  if obs_on else 0.0)
                phased = self._phased_step() if obs_on else None
                phase_times = None
                t_step = time.perf_counter() if obs_on else 0.0
                with _guard():
                    if self.pipeline is not None:
                        if carry is None:
                            carry = self._primer(self.state.params, batch)
                        self.state, metrics, carry = self._step(
                            self.state, batch, next_first, carry)
                    else:
                        if phased is not None:
                            self.state, metrics, phase_times = phased(
                                self.state, batch)
                        else:
                            self.state, metrics = self._step(self.state,
                                                             batch)
                if obs_on:
                    if phase_times is None:      # fused program: fence once
                        jax.block_until_ready(self.state)
                    total_s = time.perf_counter() - t_step
                rec = {k: np.asarray(v) for k, v in metrics.items()}
                rec["round"] = r
                if membership_schedule is not None:
                    rec["p"] = self.n_workers
                self.history.append(rec)
                if obs_on:
                    self._emit_round(tele, r, rec, total_s, host_staging_s,
                                     phase_times)
                if order_state is not None:
                    seg = segment_fn(r) if segment_fn else 0
                    order_state.record_scores(seg, rec["scores"])
                if mf is not None:
                    mf.write(json.dumps(
                        {k: (v.tolist() if isinstance(v, np.ndarray) else v)
                         for k, v in rec.items()}) + "\n")
                    mf.flush()
                if serve_hook is not None \
                        and (r + 1) % max(1, serve_every) == 0:
                    serve_hook(r, self.state.params, self.axes)
                if checkpoint_every and checkpoint_path \
                        and (r + 1) % checkpoint_every == 0:
                    self.save_checkpoint(
                        os.path.join(checkpoint_path, f"round_{r+1}"), r + 1)
                if log_every and (r + 1) % log_every == 0:
                    print(f"round {r+1}/{n_rounds} loss={rec['loss']:.4f} "
                          f"theta_entropy={rec['theta_entropy']:.3f}")
        finally:
            if mf is not None:
                mf.close()
            if prefetch is not None:
                prefetch.close()
            if self._ckpt is not None:
                self._ckpt.wait()          # surface async save failures here
        return {"rounds": n_rounds - start, "wall": time.time() - t0,
                "final_loss": float(self.history[-1]["loss"])}

    def losses(self) -> np.ndarray:
        return np.array([h["loss"] for h in self.history])

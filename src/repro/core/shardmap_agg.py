"""Explicit shard_map collectives for the WASGD communication step — the
*phase* primitives behind the mesh schedules of the two-axis aggregation API.

The pjit path (core/aggregate.py) lets XLA derive the worker-axis
all-reduce from ``tensordot(theta, x)``. This module places the same Eq. 10
reduction as explicit ``jax.lax`` collectives under ``shard_map``, one
function per collective phase so schedules (core/backends.py) can sequence
them — and interleave independent compute between them (the ``overlap=``
hook runs between ``reduce_scatter_phase`` and ``all_gather_phase``):

    all_reduce_m_phase   per shard: m = psum(theta_local * payload_local)
    reduce_scatter_phase per shard: slice = psum_scatter(theta-reduced local
                                    partial), payload pinned to a wire dtype
    all_gather_phase     per shard: m = all_gather(slice)

Each phase returns the *aggregate* (or its slices); the worker-local FMA
``(1-beta) x + beta m`` and the Alg. 4 late-join mask are applied by the
schedule's ``finalize`` outside the shard_map regions — pointwise, so the
numbers are identical to the old fused formulation.

``aggregate_leaf_shard_map`` / ``aggregate_leaf_rs_ag`` /
``weighted_aggregate_shard_map`` remain as the fused-entry compatibility
surface, now thin compositions of the phase functions above;
tests/test_dryrun_small.py checks the shard_map path on an 8-device
placeholder mesh.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.aggregate import _axes_is_leaf, fma_late_join, is_worker_leaf


def _worker_axes_in(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def _collective_axis(waxes: Tuple[str, ...]):
    return waxes[-1] if len(waxes) == 1 else waxes


def mesh_worker_shards(mesh: Mesh) -> int:
    """Number of shards the worker dim is split over (p in the rs_ag slices)."""
    p = 1
    for a in _worker_axes_in(mesh):
        p *= mesh.shape[a]
    return p


# ---------------------------------------------------------------------------
# Phase primitives
# ---------------------------------------------------------------------------

def all_reduce_m_phase(payload: jax.Array, theta: jax.Array, mesh: Mesh,
                       reduce_dtype=jnp.float32) -> jax.Array:
    """One-phase psum schedule: (w, ...) payload -> replicated f32 aggregate
    ``m = sum_j theta_j payload_j`` of shape ``payload.shape[1:]``.

    The theta-weighted contraction runs in ``reduce_dtype`` (bf16 halves the
    ring bytes; int payloads are widened first by the caller's codec).
    """
    waxes = _worker_axes_in(mesh)
    ndim = payload.ndim
    spec = P(waxes, *([None] * (ndim - 1)))
    out_spec = P(*([None] * (ndim - 1)))

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(spec, P(waxes)),
                       out_specs=out_spec)
    def run(p_local, t_local):
        contrib = t_local.astype(reduce_dtype).reshape(
            t_local.shape + (1,) * (ndim - 1)) * p_local.astype(reduce_dtype)
        return jax.lax.psum(contrib.sum(axis=0), waxes).astype(jnp.float32)

    return run(payload, theta)


def reduce_scatter_phase(payload: jax.Array, theta: jax.Array, mesh: Mesh,
                         wire_dtype=jnp.float32) -> jax.Array:
    """rs_ag phase 1: (w, n_pad) payload -> (n_pad,) theta-reduced aggregate,
    scattered 1/p-per-shard over the worker mesh axes.

    When the worker dim holds more copies than mesh shards (w/p > 1) the
    local copies are theta-reduced BEFORE the scatter; concatenating them
    into the scatter dim would hand each shard a chunk of the wrong copy.
    The scattered partial rides the ring in ``wire_dtype`` (psum_scatter
    operates on that operand — XLA cannot re-associate the cast away).
    """
    waxes = _worker_axes_in(mesh)
    ax = _collective_axis(waxes)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P(waxes, None), P(waxes)),
                       out_specs=P(waxes))
    def run(p_local, t_local):
        contrib = (t_local.astype(jnp.float32)[:, None]
                   * p_local.astype(jnp.float32)).sum(axis=0) \
            .astype(wire_dtype)
        return jax.lax.psum_scatter(contrib, ax, scatter_dimension=0,
                                    tiled=True)

    return run(payload, theta)


def all_gather_phase(m_scat: jax.Array, mesh: Mesh) -> jax.Array:
    """rs_ag phase 2: scattered (n_pad,) slices -> replicated f32 aggregate.

    RS + AG together move the same ring bytes as one all-reduce; splitting
    them here is what lets the schedule place independent compute (the
    ``overlap=`` thunk) between the two collectives.
    """
    waxes = _worker_axes_in(mesh)
    ax = _collective_axis(waxes)

    # check_vma=False: a tiled all_gather over the full worker axes IS
    # replicated along them, but shard_map's varying-axes check does not
    # infer that.
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P(waxes),
                       out_specs=P(None), check_vma=False)
    def run(m_local):
        return jax.lax.all_gather(m_local, ax,
                                  tiled=True).astype(jnp.float32)

    return run(m_scat)


def flatten_pad(x: jax.Array, p: int) -> Tuple[jax.Array, int]:
    """(w, ...) leaf -> ((w, n_pad), n): flattened trailing dims, padded so
    the rs_ag scatter divides evenly over ``p`` shards."""
    n = 1
    for s in x.shape[1:]:
        n *= s
    flat = x.reshape(x.shape[0], n)
    pad = (-n) % p
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    return flat, n


# ---------------------------------------------------------------------------
# Fused-entry compatibility surface (compositions of the phases above)
# ---------------------------------------------------------------------------

def aggregate_leaf_shard_map(x: jax.Array, theta: jax.Array,
                             beta: float, mesh: Mesh,
                             active: jax.Array = None) -> jax.Array:
    """x: (w, ...) sharded over the worker mesh axes; theta: (w,).

    ``active`` (optional ``(w,)`` bool, may be a tracer) is the Alg. 4
    late-join mask: inactive workers adopt the aggregate m instead of the
    FMA. ``None`` (the synchronous path) places no mask in the program.
    """
    m = all_reduce_m_phase(x, theta, mesh)
    return fma_late_join(x, m, beta, active)


def aggregate_leaf_rs_ag(x: jax.Array, theta: jax.Array, beta: float,
                         mesh: Mesh, comm_dtype=jnp.float32,
                         active: jax.Array = None) -> jax.Array:
    """Reduce-scatter + local FMA + all-gather schedule of Eq. 10.

    Same ring bytes as one all-reduce, but (a) the payload dtype is pinned
    to ``comm_dtype`` (see EXPERIMENTS §Perf H1 Iter 2) and (b) the two
    collective phases are separate programs that neighboring compute can
    overlap with. The f32 default matches the registry's
    ``AggregationContext`` default so both entry points agree.
    """
    orig_shape = x.shape
    flat, n = flatten_pad(x, mesh_worker_shards(mesh))
    m = all_gather_phase(
        reduce_scatter_phase(flat, theta, mesh, wire_dtype=comm_dtype), mesh)
    out = fma_late_join(flat, m, beta, active)
    return out[:, :n].reshape(orig_shape)


def weighted_aggregate_shard_map(params: Dict, axes: Dict, theta: jax.Array,
                                 beta: float, mesh: Mesh,
                                 schedule: str = "all_reduce",
                                 comm_dtype=jnp.float32) -> Dict:
    """schedule: "all_reduce" (psum) or "rs_ag" (reduce-scatter + FMA +
    all-gather with the ring payload pinned to ``comm_dtype``)."""
    if schedule == "all_reduce":
        leaf = aggregate_leaf_shard_map
    else:
        leaf = functools.partial(aggregate_leaf_rs_ag, comm_dtype=comm_dtype)

    def visit(x, ax):
        if is_worker_leaf(ax):
            return leaf(x, theta, beta, mesh)
        return x

    return jax.tree.map(visit, params, axes, is_leaf=_axes_is_leaf)

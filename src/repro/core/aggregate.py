"""Weighted aggregation — the paper's communication step (Eq. 10):

    x_i  <-  (1 - beta) * x_i  +  beta * sum_j theta_j * x_j

applied to every parameter leaf that carries the leading ``worker``
dimension. Leaves without a worker dimension (expert-parallel single copies,
DESIGN.md §4.1) pass through unchanged.

Under SPMD with the worker dimension sharded over ("pod","data") the einsum
lowers to one θ-weighted all-reduce over the worker axis — the TPU-native
equivalent of the paper's send-to-all exchange. Beyond-paper variants:

* ``quantize``      — int8 payload: aggregate in int8 with a per-leaf scale,
                      4x fewer collective bytes, error fed back locally.
* ``sharded``       — reduce-scatter + local FMA + all-gather (same bytes on
                      a ring but exposes overlap; useful with hierarchical).
* Pallas ``wagg``   — fused (1-β)x + β·Σθx single-pass kernel for the local
                      FMA part (kernels/wagg).

These primitives are selected uniformly through the aggregation backend
registry (``core/backends.py``); prefer ``WASGDConfig.backend`` /
``aggregate_with`` over calling the variant kwargs here directly.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp


def is_worker_leaf(axes_leaf) -> bool:
    return isinstance(axes_leaf, tuple) and len(axes_leaf) > 0 \
        and axes_leaf[0] == "worker"


def _axes_is_leaf(x):
    return isinstance(x, tuple) and all(a is None or isinstance(a, str)
                                        for a in x)


def fma_late_join(x: jax.Array, m: jax.Array, beta,
                  active: Optional[jax.Array] = None) -> jax.Array:
    """The worker-local half of Eq. 10: ``(1-beta) x + beta m``, plus the
    Alg. 4 late-join — inactive workers adopt the aggregate ``m`` (their
    theta is 0, so ``m`` already excludes them). ``active=None`` (the
    synchronous path) places no mask in the program at all. Shared by every
    schedule's ``finalize`` (core/backends.py) and the fused shard_map
    entry points (core/shardmap_agg.py)."""
    out = (1.0 - beta) * x.astype(jnp.float32) + beta * m[None]
    if active is not None:
        mask = active.reshape(active.shape + (1,) * (x.ndim - 1))
        out = jnp.where(mask, out, jnp.broadcast_to(m[None], out.shape))
    return out.astype(x.dtype)


def aggregate_leaf(x: jax.Array, theta: jax.Array, beta: float | jax.Array,
                   quantize: bool = False, comm_dtype=jnp.float32,
                   n_pods: int = 1) -> jax.Array:
    """One leaf (w, ...) -> (w, ...).

    ``comm_dtype=bf16`` halves the worker-axis all-reduce payload (the
    tensordot operand is what rides the ring). ``n_pods>1`` splits the
    reduction into a pod-local stage and a tiny cross-pod stage so the DCN
    hop carries pre-reduced partials (hierarchical 2-hop).
    """
    theta = theta.astype(jnp.float32)
    if quantize:
        # int8 aggregation payload with a per-leaf symmetric scale.
        scale = jnp.maximum(jnp.abs(x).max(), 1e-12) / 127.0
        q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
        # reprolint: allow=DT001 -- legacy int8 path; the symmetric scale
        # two lines up makes the narrowing explicit and round-trips to f32
        agg = jnp.tensordot(theta, q.astype(jnp.int8).astype(jnp.float32),
                            axes=1) * scale
    elif n_pods > 1 and x.shape[0] % n_pods == 0:
        w = x.shape[0]
        xr = x.reshape(n_pods, w // n_pods, *x.shape[1:]).astype(comm_dtype)
        tr = theta.reshape(n_pods, w // n_pods)
        partial = jnp.einsum("pw...,pw->p...", xr,
                             tr.astype(comm_dtype))       # pod-local reduce
        agg = partial.astype(jnp.float32).sum(axis=0)     # cross-pod reduce
    else:
        agg = jnp.tensordot(theta.astype(comm_dtype), x.astype(comm_dtype),
                            axes=1).astype(jnp.float32)
    out = (1.0 - beta) * x.astype(jnp.float32) + beta * agg[None]
    return out.astype(x.dtype)


def weighted_aggregate(params: Dict, axes: Dict, theta: jax.Array,
                       beta: float | jax.Array, *, quantize: bool = False,
                       comm_dtype=jnp.float32, n_pods: int = 1,
                       leaf_fn: Optional[Callable] = None) -> Dict:
    """Apply Eq. 10 to all worker leaves of ``params``.

    ``leaf_fn(x, theta, beta)`` overrides the per-leaf computation (used to
    swap in the Pallas ``wagg`` kernel).
    """
    fn = leaf_fn if leaf_fn is not None else (
        lambda x, t, b: aggregate_leaf(x, t, b, quantize=quantize,
                                       comm_dtype=comm_dtype,
                                       n_pods=n_pods))

    def visit(x, ax):
        if is_worker_leaf(ax):
            return fn(x, theta, beta)
        return x

    return jax.tree.map(visit, params, axes,
                        is_leaf=lambda n: _axes_is_leaf(n))


def map_worker_leaves(fn: Callable, params: Dict, axes: Dict) -> Dict:
    def visit(x, ax):
        return fn(x) if is_worker_leaf(ax) else x
    return jax.tree.map(visit, params, axes, is_leaf=_axes_is_leaf)


def worker_in_axes(axes: Dict):
    """vmap ``in_axes`` pytree: 0 for worker leaves, None for shared leaves."""
    return jax.tree.map(lambda ax: 0 if is_worker_leaf(ax) else None, axes,
                        is_leaf=_axes_is_leaf)


def strip_worker_axis(axes: Dict) -> Dict:
    """Logical-axes tree for a single worker's slice (vmap's view)."""
    return jax.tree.map(
        lambda ax: tuple(ax[1:]) if is_worker_leaf(ax) else ax,
        axes, is_leaf=_axes_is_leaf)


def take_worker(params: Dict, axes: Dict, i: int) -> Dict:
    """Extract worker ``i``'s parameter copy (serving / checkpoint export)."""
    return jax.tree.map(
        lambda x, ax: x[i] if is_worker_leaf(ax) else x,
        params, axes, is_leaf=lambda n: _axes_is_leaf(n))


def resize_worker_leaves(params: Dict, axes: Dict, new_p: int,
                         theta: Optional[jax.Array] = None) -> Dict:
    """Grow/shrink every worker-stacked leaf to ``new_p`` rows.

    The membership contract (core/membership.py): worker ``i`` keeps slot
    ``i`` for ``i < min(old_p, new_p)`` — survivors are bitwise-preserved —
    a shrink drops the tail slots, and a grow appends newcomers whose row
    is the **aggregate** ``m = sum_j theta_j x_j`` over the surviving
    workers (``theta=None`` = equal weights): exactly the state an Alg. 4
    late-joiner adopts, so a freshly joined worker starts from the
    consensus model instead of a stale or random copy. Leaves without a
    worker axis (expert-parallel single copies) pass through unchanged.
    """
    if new_p < 1:
        raise ValueError(f"resize needs new_p >= 1, got {new_p}")

    def visit(x, ax):
        if not is_worker_leaf(ax):
            return x
        old_p = x.shape[0]
        if new_p <= old_p:
            return x[:new_p]
        t = (jnp.full((old_p,), 1.0 / old_p, jnp.float32) if theta is None
             else theta.astype(jnp.float32))
        m = jnp.tensordot(t, x.astype(jnp.float32), axes=1)
        newcomers = jnp.broadcast_to(
            m[None], (new_p - old_p,) + x.shape[1:]).astype(x.dtype)
        return jnp.concatenate([x, newcomers], axis=0)

    return jax.tree.map(visit, params, axes, is_leaf=_axes_is_leaf)


def _single_copy(ax, expert_copies: bool) -> bool:
    return not expert_copies and isinstance(ax, tuple) and "experts" in ax


def worker_axes(axes: Dict, expert_copies: bool = False) -> Dict:
    """The axes tree ``replicate_workers`` returns, without the params."""
    return jax.tree.map(
        lambda ax: ax if _single_copy(ax, expert_copies)
        else ("worker",) + tuple(ax), axes, is_leaf=_axes_is_leaf)


def replicate_workers(params: Dict, axes: Dict, n_workers: int,
                      expert_copies: bool = False):
    """Single-copy params -> (w, ...) worker copies (+ updated axes tree).

    Expert leaves stay single-copy (expert-parallel, DESIGN.md §4.1) unless
    ``expert_copies`` — the "worker" expert-sharding policy where experts
    join the weighted aggregation (§Perf, memory permitting)."""
    def rep(x, ax):
        if _single_copy(ax, expert_copies):
            return x
        return jnp.broadcast_to(x[None], (n_workers,) + x.shape)

    new_params = jax.tree.map(rep, params, axes, is_leaf=_axes_is_leaf)
    return new_params, worker_axes(axes, expert_copies)

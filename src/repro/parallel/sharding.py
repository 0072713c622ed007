"""Logical-axis sharding rules.

Parameters and inputs are annotated with *logical* axis names (``"worker"``,
``"heads"``, ``"ffn"`` ...). A rule table maps logical names to physical mesh
axes; ``spec_for`` resolves a tuple of logical names + a concrete shape into a
``PartitionSpec``, silently falling back to replication for any dimension the
mesh axis does not divide evenly (e.g. gemma3's 4 query heads over a 16-way
model axis, or yi's 4 KV heads).

The model code never touches physical axes — swapping the sharding scheme is
a rules-table edit, which is how the §Perf iterations change layouts.
"""
from __future__ import annotations

import math
from typing import Dict, Mapping, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

LogicalAxes = Tuple[Optional[str], ...]
Rule = Union[None, str, Tuple[str, ...]]


# Default rule tables ---------------------------------------------------------

# Training: the WASGD worker axis spans ("pod", "data"); tensor parallelism
# spans "model". Batch inside a worker is NOT sharded (each worker is one
# data-parallel group).
TRAIN_RULES: Dict[str, Rule] = {
    "worker": ("pod", "data"),
    "batch": None,
    "seq": None,
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "ffn": "model",
    "vocab": "model",
    "experts": "data",          # expert-parallel single copy over the worker axis
    "expert_ffn": "model",
    "ssm_heads": "model",
    "ssm_state": None,
    "conv": None,
    "media": None,
    "kv_seq": None,
}

# Serving (no worker axis): batch over ("pod","data"), TP over "model".
SERVE_RULES: Dict[str, Rule] = {
    **TRAIN_RULES,
    "worker": None,
    "batch": ("pod", "data"),
    "experts": "model",         # single-copy serving: EP folds into the TP axis
    "expert_ffn": None,
    # KV caches dominate decode memory: when kv_heads < model-axis size the
    # heads dim falls back to replicated and the head_dim picks up "model"
    # (the PartitionSpec dedupe keeps whichever resolves first).
    "head_dim": "model",
}

# Long-context serving (batch=1): shard the KV-cache/sequence dim over "data"
# (flash-decode partial-softmax combine), batch replicated.
SERVE_LONG_RULES: Dict[str, Rule] = {
    **SERVE_RULES,
    "batch": None,
    "kv_seq": "data",
    "seq": "data",
}


def _axis_size(mesh: Mesh, rule: Rule) -> int:
    if rule is None:
        return 1
    names = (rule,) if isinstance(rule, str) else rule
    size = 1
    for n in names:
        if n in mesh.shape:
            size *= mesh.shape[n]
    return size


def _present(mesh: Mesh, rule: Rule) -> Rule:
    """Drop mesh axes that don't exist on this mesh (e.g. 'pod' single-pod)."""
    if rule is None:
        return None
    names = (rule,) if isinstance(rule, str) else rule
    kept = tuple(n for n in names if n in mesh.shape)
    if not kept:
        return None
    return kept[0] if len(kept) == 1 else kept


def spec_for(
    mesh: Mesh,
    axes: Sequence[Optional[str]],
    shape: Optional[Sequence[int]] = None,
    rules: Optional[Mapping[str, Rule]] = None,
) -> P:
    """Resolve logical axes (+ optional concrete shape) to a PartitionSpec."""
    rules = TRAIN_RULES if rules is None else rules
    out = []
    for i, name in enumerate(axes):
        rule = _present(mesh, rules.get(name)) if name is not None else None
        if rule is not None and shape is not None:
            if shape[i] % _axis_size(mesh, rule) != 0:
                rule = None  # divisibility fallback: replicate this dim
        out.append(rule)
    # PartitionSpec forbids repeated mesh axes; keep the first occurrence.
    seen: set = set()
    cleaned = []
    for rule in out:
        names = () if rule is None else ((rule,) if isinstance(rule, str) else tuple(rule))
        if any(n in seen for n in names):
            cleaned.append(None)
        else:
            seen.update(names)
            cleaned.append(rule)
    return P(*cleaned)


def sharding_for(
    mesh: Mesh,
    axes: Sequence[Optional[str]],
    shape: Optional[Sequence[int]] = None,
    rules: Optional[Mapping[str, Rule]] = None,
) -> NamedSharding:
    return NamedSharding(mesh, spec_for(mesh, axes, shape, rules))


def tree_shardings(mesh: Mesh, shapes_tree, axes_tree, rules=None):
    """Map parallel (ShapeDtypeStruct, axes) pytrees to a NamedSharding tree.

    The shapes tree leads so empty containers (e.g. an SGD optimizer state of
    ``()``) contribute no sharding leaves; axes tuples are picked up by
    ``flatten_up_to`` at the corresponding leaf positions.
    """
    return jax.tree.map(
        lambda s, axes: sharding_for(mesh, axes, s.shape, rules),
        shapes_tree,
        axes_tree,
    )


def num_workers(mesh: Mesh) -> int:
    """WASGD worker count = product of the worker-axis mesh dims."""
    return _axis_size(mesh, _present(mesh, TRAIN_RULES["worker"]))


def auto_mesh(mesh: Mesh) -> Mesh:
    """The same devices and axis names with every axis ``Auto``.

    ``jax.make_mesh`` makes ``Explicit`` axes, whose arrays carry their
    sharding in their type and must agree on it in every op. The training
    round is written for compiler-chosen (``Auto``) shardings, so its
    placement and its ``shard_map`` collectives run on this view; one round
    must not mix the two, since JAX refuses ops over arrays of both.
    Idempotent."""
    auto = (AxisType.Auto,) * len(mesh.axis_names)
    if tuple(mesh.axis_types) == auto:
        return mesh
    return Mesh(mesh.devices, mesh.axis_names, axis_types=auto)


def worker_split(mesh: Mesh) -> NamedSharding:
    """Leading dim split over the mesh's worker axes, the rest replicated."""
    return NamedSharding(auto_mesh(mesh),
                         P(_present(mesh, TRAIN_RULES["worker"])))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(auto_mesh(mesh), P())


def bytes_of(shape: Sequence[int], dtype) -> int:
    return math.prod(shape) * jax.numpy.dtype(dtype).itemsize

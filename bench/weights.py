"""Random weights from a seed, made on the device in one jitted call.

The benchmark, not the program, decides the values, so that the plain
reference can make the very same weights again without taking anything
from the program. Per leaf (in sorted path order) the key is
``fold_in(seed_key, leaf_index)``; by the leaf's logical axes:

* norm scales (one axis, ``embed``): ones;
* the embedding table (first axis ``vocab``): standard normal;
* every other matrix: normal with standard deviation ``fan_in ** -0.5``,
  where ``fan_in`` is the first dimension when the first axis is
  ``embed`` and the product of all but the last dimension otherwise (the
  attention output ``(heads, head_dim, embed)`` and the MLP's down
  projection).
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key for any non-negative seed, of more than 32 bits too."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _leaves(shapes: Dict, axes: Dict, path: Tuple[str, ...] = ()
            ) -> List[Tuple[Tuple[str, ...], jax.ShapeDtypeStruct, tuple]]:
    out = []
    for k in sorted(shapes):
        s, a = shapes[k], axes[k]
        if isinstance(s, dict):
            out.extend(_leaves(s, a, path + (k,)))
        else:
            out.append((path + (k,), s, tuple(a)))
    return out


def _std(shape, ax) -> float:
    if ax and ax[0] == "vocab":
        return 1.0
    fan_in = shape[0] if ax[0] == "embed" else math.prod(shape[:-1])
    return fan_in ** -0.5


def _value(key, shape, ax, dtype):
    if len(shape) == 1:
        return jnp.ones(shape, dtype)
    return (jax.random.normal(key, shape, jnp.float32)
            * _std(shape, ax)).astype(dtype)


def _set(tree: Dict, path: Tuple[str, ...], value) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def make(shapes: Dict, axes: Dict, seed: int, dtype=jnp.float32,
         stack: int = 0, sharding=None) -> Dict:
    """The whole tree in one jitted call, placed by ``sharding`` (default:
    the first device). With ``stack`` every leaf gets a leading axis of
    that many identical copies (one per worker)."""
    leaves = _leaves(shapes, axes)

    def gen(key):
        tree: Dict = {}
        for i, (path, s, ax) in enumerate(leaves):
            v = _value(jax.random.fold_in(key, i), s.shape, ax, dtype)
            if stack:
                v = jnp.broadcast_to(v[None], (stack,) + tuple(s.shape))
            _set(tree, path, v)
        return tree

    if sharding is None:
        sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    return jax.jit(gen, out_shardings=sharding)(seed_key(seed))


def leaf_value(shapes: Dict, axes: Dict, key: jax.Array,
               path: Tuple[str, ...], dtype=jnp.float32) -> jax.Array:
    """One leaf of ``make``'s tree from ``seed_key(seed)``; traceable, so a
    jitted caller takes the key as an argument and compiles once for every
    seed."""
    for i, (p, s, ax) in enumerate(_leaves(shapes, axes)):
        if p == path:
            return _value(jax.random.fold_in(key, i), s.shape, ax, dtype)
    raise KeyError(path)


def paths(shapes: Dict, axes: Dict) -> List[Tuple[str, ...]]:
    return [p for p, _, _ in _leaves(shapes, axes)]

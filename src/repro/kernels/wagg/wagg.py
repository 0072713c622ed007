"""Pallas TPU kernel: fused WASGD weighted aggregation (Eq. 10), v2.

    out[i, :] = (1 - beta) * x[i, :] + beta * sum_j theta[j] * q[j, :]

over a worker-stacked parameter block x: (p, N). A naive XLA lowering does
(reduce -> broadcast -> two muls -> add) with three HBM round trips over the
full parameter set — and with a quantizing codec, encode/decode are further
separate XLA programs with their own round trips. This kernel streams each
(p, block_n) tile through VMEM once and fuses, in the same pass:

* **codec decode** — ``payload`` may be the codec's wire tiles (int8-carried
  int4/int8, or bf16); they are widened to f32 *in VMEM* and accumulated in
  f32. The per-leaf scalar scale (the codec ``aux``) is folded into theta by
  the ops wrapper, so integer tiles ride in untouched. ``payload=None``
  means the payload IS x (the f32 codec) and x is read once, not twice.
* **the Eq. 10 FMA** — ``(1-beta) x + beta m`` against the ORIGINAL x.
* **the Alg. 4 activity mask** — ``active`` (p,) selects the late-join rows
  (stragglers adopt the aggregate m; their theta is already 0 so m excludes
  them). ``active=None`` places no mask in the program at all.

The worker dimension p rides along in full per tile, so the MXU-free VPU
reduction over p stays in registers.

Tiling: grid over N in ``block_n`` VMEM tiles. ``auto_block_n`` guards the
VMEM budget: for large p the default ``block_n`` would over-allocate
(p * block_n * bytes/col), so the block is halved until the working set
fits instead of failing at compile time.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import default_interpret

# Per-kernel-invocation VMEM working-set budget. Real TPU cores have ~16 MiB
# of VMEM; half of it leaves room for double buffering of the streamed tiles.
VMEM_BUDGET_BYTES = 8 * 1024 * 1024

_MIN_BLOCK_N = 128


def _default_interpret() -> bool:
    # Shared policy (kernels/runtime.py): compiled on TPU, interpret
    # elsewhere. The old signature default hardcoded True, silently pinning
    # direct TPU callers to interpret mode.
    return default_interpret()


def auto_block_n(p: int, block_n: int, bytes_per_col: int,
                 budget: int = VMEM_BUDGET_BYTES) -> int:
    """Shrink ``block_n`` until the (p, block_n) tile working set fits VMEM.

    ``bytes_per_col`` is the footprint per worker row and column of
    everything resident per tile: the double-buffered x, out and payload
    streams plus the kernel's f32 temporaries (``tile_bytes_per_col``).
    VMEM lays rows out in 8-row sublane tiles, so p is rounded up to a
    multiple of 8. The block halves until the set fits ``budget`` or the
    128-column floor, instead of being refused by the compiler for large p.
    """
    rows = -(-p // 8) * 8
    bn = block_n
    while bn > _MIN_BLOCK_N and rows * bn * bytes_per_col > budget:
        bn //= 2
    if bn != block_n:               # a partial block must be lane-aligned
        bn = max(_MIN_BLOCK_N, bn - bn % 128)
    return bn


def tile_bytes_per_col(payload_itemsize: int = 0) -> int:
    """Per-row, per-column VMEM bytes of one grid step: x and out in f32,
    double-buffered, plus a separate payload stream (double-buffered) and
    its f32 widening in the kernel body."""
    per = 2 * (4 + 4)
    if payload_itemsize:
        per += 2 * payload_itemsize + 4
    return per


def _wagg_kernel(*refs, beta: float, masked: bool, separate_payload: bool):
    it = iter(refs)
    theta = next(it)[...]                         # (p, 1) f32, scale folded
    active = next(it)[...] if masked else None    # (p, 1) f32 0/1
    q_ref = next(it) if separate_payload else None
    x_ref = next(it)
    o_ref = next(it)
    x = x_ref[...].astype(jnp.float32)            # (p, bn)
    src = q_ref[...].astype(jnp.float32) if separate_payload else x
    # VPU reduction over workers: lane-broadcast multiply, sublane sum.
    m = jnp.sum(theta * src, axis=0, keepdims=True)          # (1, bn)
    out = (1.0 - beta) * x + beta * m
    if masked:
        # Alg. 4 late-join: straggler rows adopt the aggregate wholesale.
        out = jnp.where(active != 0, out, m)
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("beta", "block_n", "interpret"))
def wagg_fused(x: jax.Array, theta: jax.Array, beta: float,
               payload: Optional[jax.Array] = None,
               active: Optional[jax.Array] = None,
               block_n: int = 8192,
               interpret: Optional[bool] = None) -> jax.Array:
    """Fused decode + Alg. 4 mask + Eq. 10 over a (p, N) block.

    ``x``: (p, N) originals (any float dtype; the FMA runs in f32).
    ``theta``: (p,) effective weights — for a quantizing codec the per-leaf
    scale is already folded in (``theta * scale``), so ``payload`` tiles are
    consumed as-is. ``payload``: (p, N) codec wire tiles (int8/bf16/...), or
    ``None`` when the payload is x itself. ``active``: (p,) 0/1 mask (any
    numeric dtype), or ``None`` for the maskless program. Returns (p, N) in
    ``x.dtype``.
    """
    interpret = _default_interpret() if interpret is None else interpret
    p, n = x.shape
    separate = payload is not None
    masked = active is not None
    per_col = tile_bytes_per_col(
        jnp.dtype(payload.dtype).itemsize if separate else 0)
    bn = auto_block_n(p, min(block_n, n), per_col)
    pad = (-n) % bn
    xp = jnp.pad(x, ((0, 0), (0, pad))) if pad else x
    np_ = n + pad

    tile = pl.BlockSpec((p, bn), lambda j: (0, j))
    # (p, 1) columns: a 2-D block equal to the array's shape is legal for
    # Mosaic, where a 1-D (p,) block is not.
    vec = pl.BlockSpec((p, 1), lambda j: (0, 0))
    in_specs = [vec]
    operands = [theta.astype(jnp.float32).reshape(p, 1)]
    if masked:
        in_specs.append(vec)
        operands.append(active.astype(jnp.float32).reshape(p, 1))
    if separate:
        qp = jnp.pad(payload, ((0, 0), (0, pad))) if pad else payload
        in_specs.append(tile)
        operands.append(qp)
    in_specs.append(tile)
    operands.append(xp)

    out = pl.pallas_call(
        functools.partial(_wagg_kernel, beta=float(beta), masked=masked,
                          separate_payload=separate),
        grid=(np_ // bn,),
        in_specs=in_specs,
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((p, np_), x.dtype),
        interpret=interpret,
    )(*operands)
    return out[:, :n] if pad else out


@functools.partial(jax.jit, static_argnames=("beta", "block_n", "interpret"))
def wagg(x: jax.Array, theta: jax.Array, beta: float,
         block_n: int = 8192, interpret: Optional[bool] = None) -> jax.Array:
    """x: (p, N); theta: (p,). Returns (p, N). The f32, maskless entry —
    the identical program ``wagg_fused`` emits with no payload and no mask
    (three refs: theta, x, out)."""
    return wagg_fused(x, theta, float(beta), block_n=block_n,
                      interpret=interpret)

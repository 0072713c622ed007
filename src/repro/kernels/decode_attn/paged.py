"""Pallas TPU kernel: paged single-token GQA flash-decode attention.

The serving engine's KV store is a pool of fixed-size blocks
(``serve/paged_cache.py``): each layer owns ``(n_blocks + 1, block_size,
kv, hd)`` K/V pools (the ``+1`` row is the trash block inactive slots write
into) and each request maps its logical cache onto physical blocks through a
``(b, n_blk)`` int32 block table. This kernel is the paged-aware variant of
``decode_attn``: the grid is ``(b, n_blk)`` and the K/V BlockSpec index
map reads the block table — delivered ahead of the kernel body via
``PrefetchScalarGridSpec`` scalar prefetch — so each grid step streams one
*physical* block, all kv heads of it, straight from the pool, no gather
materialization. Within a step every query head scores the block's
``bs * kv`` (position, head) rows on the MXU and keeps its own head's, so
the masked score tile is ``(kv*g, bs*kv)``: kv times the useful work, and
its cost grows as kv squared. On a TPU v5e at kv=32, hd=64, bs=16 a grid
step takes about 1.4 us, of which roughly half scales with that tile; the
call reaches about 0.11 of the K/V-bytes roofline (``PERF.md``, traced
with ``benchmarks/paged_decode_trace.py``).

Layouts (per layer, static):

* linear (full-attention layers): logical slot ``s`` holds token position
  ``s``; block ``j`` covers positions ``[j*bs, (j+1)*bs)``; valid iff
  ``s <= index`` (``index`` = position of the newest token, per request).
* ring (sliding-window layers): capacity ``R = n_blk * bs`` slots, token
  position ``p`` lives at slot ``p % R``. The age of slot ``s`` is
  ``(index - s) mod R`` and the slot is valid iff
  ``age < min(window, index + 1)`` — this masks both tokens older than the
  window and ring slots not yet written, and degenerates to the monolithic
  ring-cache semantics when ``R == window``.

Per-request cache lengths (continuous batching: every running request sits
at a different ``index``) ride in as a second scalar-prefetch operand.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import default_interpret

NEG_INF = -1e30


def _paged_decode_kernel(table_ref, index_ref, row_head_ref, col_head_ref,
                         col_pos_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *,
                         block_s: int, n_blocks: int, ring: Optional[int],
                         window: Optional[int]):
    bi = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)              # (kv*g, hd)
    k = k_ref[0].astype(jnp.float32)              # (bs*kv, hd)
    v = v_ref[0].astype(jnp.float32)              # (bs*kv, hd)
    hd = q.shape[-1]

    index = index_ref[bi]
    slot = j * block_s + col_pos_ref[...]         # (1, bs*kv)
    if ring is None:
        valid = slot <= index
    else:
        # (index - slot) mod ring, with slot in [0, ring): one conditional
        # add instead of a vector integer remainder.
        d = index % ring - slot
        age = jnp.where(d < 0, d + ring, d)
        lim = jnp.minimum(jnp.int32(ring if window is None else window),
                          index + 1)
        valid = age < lim
    # every query row scores every (position, head) row of the block; only
    # the rows of its own kv head are kept.
    keep = valid & (row_head_ref[...] == col_head_ref[...])  # (kv*g, bs*kv)

    s = jax.lax.dot_general(q * hd ** -0.5, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = jnp.where(keep, s, NEG_INF)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    m_safe = jnp.maximum(m_new, -0.5e30)
    p = jnp.exp(s - m_safe)
    corr = jnp.exp(m_prev - m_safe)
    l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
    acc_new = acc_prev * corr + jnp.dot(p, v,
                                        preferred_element_type=jnp.float32)

    m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc_new

    @pl.when(j == n_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_new / jnp.maximum(l_new, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("ring", "window", "interpret"))
def paged_decode_attn(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                      block_table: jax.Array, index: jax.Array, *,
                      ring: Optional[int] = None,
                      window: Optional[int] = None,
                      interpret: Optional[bool] = None) -> jax.Array:
    """q: (b, kv, g, hd); pools: (n_pool, bs, kv, hd);
    block_table: (b, n_blk) int32 physical block per logical block;
    index: (b,) int32 position of each request's newest token."""
    interpret = default_interpret() if interpret is None else interpret
    b, kv, g, hd = q.shape
    n_pool, bs = k_pool.shape[:2]
    n_blk = block_table.shape[1]
    if ring is not None and ring != n_blk * bs:
        raise ValueError(
            f"ring capacity {ring} != table blocks x block_size "
            f"({n_blk}x{bs})")
    rows, cols = kv * g, bs * kv
    # Free row-major views: query row r = head*g + group, pool row
    # c = position*kv + head. A (bs*kv, hd) block spans the array's last two
    # dims, which Mosaic accepts for any kv; a one-head (bs, 1, hd) slice of
    # the 4-D pool it refuses.
    q2 = q.reshape(b, rows, hd)
    k2 = k_pool.reshape(n_pool, cols, hd)
    v2 = v_pool.reshape(n_pool, cols, hd)
    row_head = (jnp.arange(rows, dtype=jnp.int32) // g)[:, None]
    col = jnp.arange(cols, dtype=jnp.int32)[None, :]
    col_head, col_pos = col % kv, col // kv

    kernel = functools.partial(
        _paged_decode_kernel, block_s=bs, n_blocks=n_blk, ring=ring,
        window=window)
    const = lambda shape: pl.BlockSpec(shape, lambda bi, ji, tab, idx: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_blk),
        in_specs=[
            const((rows, 1)),
            const((1, cols)),
            const((1, cols)),
            pl.BlockSpec((1, rows, hd),
                         lambda bi, ji, tab, idx: (bi, 0, 0)),
            pl.BlockSpec((1, cols, hd),
                         lambda bi, ji, tab, idx: (tab[bi, ji], 0, 0)),
            pl.BlockSpec((1, cols, hd),
                         lambda bi, ji, tab, idx: (tab[bi, ji], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, hd),
                               lambda bi, ji, tab, idx: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),     # running max m
            pltpu.VMEM((rows, 1), jnp.float32),     # running denominator l
            pltpu.VMEM((rows, hd), jnp.float32),    # output accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, hd), q.dtype),
        interpret=interpret,
    )(jnp.asarray(block_table, jnp.int32), jnp.asarray(index, jnp.int32),
      row_head, col_head, col_pos, q2, k2, v2)
    return out.reshape(b, kv, g, hd)


def paged_decode_attn_ref(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                          block_table: jax.Array, index: jax.Array, *,
                          ring: Optional[int] = None,
                          window: Optional[int] = None) -> jax.Array:
    """Pure-jnp reference: gather the table, run masked softmax attention.

    Same signature and masking semantics as the kernel; this is also the
    path the serving engine's jitted while_loop uses off-TPU (mirroring the
    monolithic decode, whose jnp reference serves on CPU)."""
    b, kv, g, hd = q.shape
    bs = k_pool.shape[1]
    n_blk = block_table.shape[1]
    S = n_blk * bs
    k = k_pool[block_table].reshape(b, S, kv, hd)
    v = v_pool[block_table].reshape(b, S, kv, hd)
    qg = (q * hd ** -0.5).astype(jnp.float32)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, k.astype(jnp.float32))
    slot = jnp.arange(S, dtype=jnp.int32)
    idx = jnp.asarray(index, jnp.int32)[:, None]
    if ring is None:
        valid = slot[None, :] <= idx
    else:
        if ring != S:
            raise ValueError(
                f"ring capacity {ring} != table blocks x block_size "
                f"({n_blk}x{bs})")
        age = jnp.mod(idx - slot[None, :], ring)
        lim = jnp.minimum(jnp.int32(ring if window is None else window),
                          idx + 1)
        valid = age < lim
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)

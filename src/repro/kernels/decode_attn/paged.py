"""Pallas TPU kernel: paged single-token GQA flash-decode attention.

The serving engine's KV store is a pool of fixed-size blocks
(``serve/paged_cache.py``): each layer owns ``(n_blocks + 1, block_size,
kv, hd)`` K/V pools (the ``+1`` row is the trash block inactive slots write
into) and each request maps its logical cache onto physical blocks through a
``(b, n_blk)`` int32 block table.

Walk. A row walks only its own live pages: ``min(n_blk, ceil(length /
bs))`` of them, ``length`` being ``index + 1`` for an active row and ``0``
for one that is not, which walks none and returns zeros. The pages are
scored ``P`` at a time (``_pages_per_block``: about 256 tokens, fewer where
the score tile would grow too wide), so the fixed cost of a step is paid
once per compute block, not once per 16-token page. The table and the
lengths are scalar-prefetched. How a block's pages reach VMEM depends on
the head width:

* ``hd`` a multiple of 128 lanes (Yi: 128): the grid is ``(b,)`` and each
  row loops over its own compute blocks. The pools stay in HBM and every
  page of a block is copied by its own DMA, double-buffered: while one
  block is scored the next one's pages are in flight, and a row's last
  block starts the first block of the next live row, so an idle row costs
  one empty grid step and a dead page nothing.
* narrower heads (stablelm: 64): Mosaic copies a 64-lane page only as a
  whole pipelined block (a DMA of it is refused as unaligned, and a
  128-lane view of the pool is a relayout copy), so the grid is
  ``(b, ceil(n_blk / P))`` and each of the ``P`` pages is a BlockSpec
  operand whose index map stops at the row's last live page: steps past a
  row's end fetch nothing and skip the scoring.

Within a block every query head scores the block's ``P * bs * kv``
(position, head) rows on the MXU and keeps its own head's, so the masked
score tile is ``(kv*g, P*bs*kv)``: kv times the useful work, and its cost
grows as kv squared (``PERF.md`` has the per-call device times at kv=4 and
kv=32, from ``benchmarks/paged_decode_trace.py``).

Layouts (per layer, static):

* linear (full-attention layers): logical slot ``s`` holds token position
  ``s``; block ``j`` covers positions ``[j*bs, (j+1)*bs)``; valid iff
  ``s <= index`` (``index`` = position of the newest token, per request).
* ring (sliding-window layers): capacity ``R = n_blk * bs`` slots, token
  position ``p`` lives at slot ``p % R``. The age of slot ``s`` is
  ``(index - s) mod R`` and the slot is valid iff
  ``age < min(window, index + 1)`` — this masks both tokens older than the
  window and ring slots not yet written, and degenerates to the monolithic
  ring-cache semantics when ``R == window``. Before the wrap a row walks
  the pages written so far; after it, all ``n_blk``.
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
# A compute block's target span in tokens, and the most elements its f32
# score tile may hold: at kv=32, g=1, bs=16 that is 8 pages (128 tokens),
# at Yi's kv=4, g=8 the full 16 pages.
BLOCK_TOKENS = 256
TILE_ELEMS = 32 * 4096
LANES = 128


def _pages_per_block(bs: int, kv: int, g: int, n_blk: int) -> int:
    """Pages per compute block, from static shapes: ``BLOCK_TOKENS`` worth,
    cut to keep the ``(kv*g, P*bs*kv)`` score tile within ``TILE_ELEMS``,
    and no more than the table holds."""
    by_tile = TILE_ELEMS // (kv * g * bs * kv)
    return max(1, min(n_blk, BLOCK_TOKENS // bs, by_tile))


# Scalar index arithmetic uses lax.div / lax.rem / lax.min on values that
# are never negative: jnp's floor division and where wrap each use in a
# nested jit, which Pallas traces and lowers once per table width.

def _n_pages(length, block_s: int, n_blk: int):
    """Pages a row of ``length`` tokens walks: none when it is idle."""
    return jax.lax.min(jax.lax.div(length + block_s - 1, block_s), n_blk)


def _n_steps(length, block_s: int, n_blk: int, pages: int):
    """Compute blocks of ``pages`` pages that cover the row's walk."""
    return jax.lax.div(_n_pages(length, block_s, n_blk) + pages - 1, pages)


def _valid(pos, index, ring: Optional[int], window: Optional[int]):
    """Which slots ``pos`` (int32, any shape) a row at ``index`` attends."""
    if ring is None:
        return pos <= index
    # (index - slot) mod ring, with slot in [0, ring): one conditional add
    # instead of a vector integer remainder. A last block that overhangs
    # the table holds no slot.
    d = jax.lax.rem(index, ring) - pos
    age = jnp.where(d < 0, d + ring, d)
    lim = jax.lax.min(ring if window is None else window, index + 1)
    return (age < lim) & (pos < ring)


def _attend(carry, q, same_head, chunks):
    """One online-softmax step over a compute block given as ``chunks`` of
    ``(k, v, valid)``: k, v (n, hd) f32, valid (1, n); ``q`` is scaled."""
    m_prev, l_prev, acc_prev = carry
    s = [jnp.where(valid & same_head, jax.lax.dot_general(
             q, k, (((1,), (1,)), ((), ())),
             preferred_element_type=jnp.float32), NEG_INF)
         for k, _, valid in chunks]
    m_new = m_prev
    for s_c in s:
        m_new = jnp.maximum(m_new, s_c.max(axis=-1, keepdims=True))
    m_safe = jnp.maximum(m_new, -0.5e30)
    corr = jnp.exp(m_prev - m_safe)
    l_new, acc_new = l_prev * corr, acc_prev * corr
    for s_c, (_, v, _) in zip(s, chunks):
        p = jnp.exp(s_c - m_safe)
        l_new = l_new + p.sum(axis=-1, keepdims=True)
        acc_new = acc_new + jnp.dot(p, v, preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _dma_kernel(table_ref, length_ref, row_head_ref, col_head_ref,
                col_pos_ref, q_ref, k_hbm, v_hbm, o_ref,
                k_buf, v_buf, k_sem, v_sem, slot_ref, *,
                block_s: int, pages: int, ring: Optional[int],
                window: Optional[int]):
    """Grid ``(b,)``: row ``bi`` loops over its compute blocks, its pages
    copied from the HBM pools by double-buffered DMAs."""
    b, n_blk = table_ref.shape
    bi = pl.program_id(0)

    def copies(r, blk, slot, op):
        """``op`` (start or wait) the K and V copies of the live pages of
        row ``r``'s compute block ``blk`` into buffer ``slot``; a page past
        the row's end is neither copied nor waited for."""
        n_live = jax.lax.min(
            _n_pages(length_ref[r], block_s, n_blk) - blk * pages, pages)

        def page(i, carry):
            at = table_ref[r, blk * pages + i]
            for pool, buf, sem in ((k_hbm, k_buf, k_sem),
                                   (v_hbm, v_buf, v_sem)):
                op(pltpu.make_async_copy(pool.at[at], buf.at[slot, i],
                                         sem.at[slot]))
            return carry

        jax.lax.fori_loop(0, n_live, page, 0)

    def start(r, blk, slot):
        copies(r, blk, slot, lambda c: c.start())

    def wait(r, blk, slot):
        copies(r, blk, slot, lambda c: c.wait())

    def next_live(r):
        """The first row after ``r`` with pages to walk, or ``b``."""
        return jax.lax.fori_loop(
            r + 1, b,
            lambda k, nxt: jax.lax.select(
                (nxt == b) & (length_ref[k] > 0), k, nxt),
            jnp.int32(b))

    @pl.when(bi == 0)
    def _prime():
        # Pages past a row's end in its last block keep what an earlier
        # block left there; zeroed once, they hold only finite pool data,
        # which the mask removes (a NaN there would survive p = 0).
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0
        first = next_live(jnp.int32(-1))

        @pl.when(first < b)
        def _():
            start(first, 0, 0)

    steps = _n_steps(length_ref[bi], block_s, n_blk, pages)

    @pl.when(steps == 0)
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(steps > 0)
    def _walk():
        q = q_ref[0].astype(jnp.float32)          # (kv*g, hd)
        rows, hd = q.shape
        q = q * hd ** -0.5
        same_head = row_head_ref[...] == col_head_ref[...]  # (kv*g, cols)
        index = length_ref[bi] - 1
        nxt = next_live(bi)

        def body(j, carry):
            *state, slot = carry
            other = 1 - slot
            # prefetch this row's next block, or after its last the next
            # live row's first
            last = j + 1 == steps
            r_next = jax.lax.select(last, nxt, bi)

            @pl.when(r_next < b)
            def _():
                start(r_next, jax.lax.select(last, 0, j + 1), other)

            wait(bi, j, slot)
            k = k_buf[slot].astype(jnp.float32).reshape(-1, hd)
            v = v_buf[slot].astype(jnp.float32).reshape(-1, hd)
            pos = j * (pages * block_s) + col_pos_ref[...]   # (1, cols)
            valid = _valid(pos, index, ring, window)
            return (*_attend(state, q, same_head, [(k, v, valid)]), other)

        init = (jnp.full((rows, 1), NEG_INF, jnp.float32),
                jnp.zeros((rows, 1), jnp.float32),
                jnp.zeros((rows, hd), jnp.float32), slot_ref[0])
        _, l, acc, slot = jax.lax.fori_loop(0, steps, body, init)
        slot_ref[0] = slot
        o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _page_kernel(table_ref, length_ref, row_head_ref, col_head_ref,
                 col_pos_ref, q_ref, *refs, block_s: int, pages: int,
                 ring: Optional[int], window: Optional[int]):
    """Grid ``(b, ceil(n_blk / P))``: step ``j`` of row ``bi`` scores the
    ``P`` pages its BlockSpec operands fetched, if the row reaches them."""
    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * pages:]
    n_blk = table_ref.shape[1]
    bi, j = pl.program_id(0), pl.program_id(1)
    steps = _n_steps(length_ref[bi], block_s, n_blk, pages)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < steps)
    def _score():
        q = q_ref[0].astype(jnp.float32)
        q = q * q.shape[-1] ** -0.5
        same_head = row_head_ref[...] == col_head_ref[...]  # (kv*g, bs*kv)
        index = length_ref[bi] - 1
        chunks = []
        for i in range(pages):
            pos = (j * pages + i) * block_s + col_pos_ref[...]
            chunks.append((k_refs[i][0].astype(jnp.float32),
                           v_refs[i][0].astype(jnp.float32),
                           _valid(pos, index, ring, window)))
        m_ref[...], l_ref[...], acc_ref[...] = _attend(
            (m_ref[...], l_ref[...], acc_ref[...]), q, same_head, chunks)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        # an idle row never scored: acc = 0, so its output is zeros
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("ring", "window", "interpret"))
def paged_decode_attn(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                      block_table: jax.Array, index: jax.Array,
                      active: Optional[jax.Array] = None, *,
                      ring: Optional[int] = None,
                      window: Optional[int] = None,
                      interpret: Optional[bool] = None) -> jax.Array:
    """q: (b, kv, g, hd); pools: (n_pool, bs, kv, hd);
    block_table: (b, n_blk) int32 physical block per logical block;
    index: (b,) int32 position of each request's newest token;
    active: (b,) bool, default all; an inactive row reads no page and its
    output is zeros."""
    interpret = default_interpret() if interpret is None else interpret
    b, kv, g, hd = q.shape
    n_pool, bs = k_pool.shape[:2]
    n_blk = block_table.shape[1]
    if ring is not None and ring != n_blk * bs:
        raise ValueError(
            f"ring capacity {ring} != table blocks x block_size "
            f"({n_blk}x{bs})")
    pages = _pages_per_block(bs, kv, g, n_blk)
    dma = hd % LANES == 0
    rows, page_rows = kv * g, bs * kv
    cols = pages * page_rows if dma else page_rows
    length = jnp.asarray(index, jnp.int32) + 1
    if active is not None:
        length = jnp.where(active, length, 0)
    # Free row-major views: query row r = head*g + group, pool row
    # c = position*kv + head. A page is a (bs*kv, hd) slab spanning the
    # array's last two dims.
    q2 = q.reshape(b, rows, hd)
    k2 = k_pool.reshape(n_pool, page_rows, hd)
    v2 = v_pool.reshape(n_pool, page_rows, hd)
    row_head = (jnp.arange(rows, dtype=jnp.int32) // g)[:, None]
    col = jnp.arange(cols, dtype=jnp.int32)[None, :]
    col_head, col_pos = col % kv, col // kv
    params = dict(block_s=bs, pages=pages, ring=ring, window=window)

    if dma:
        const = lambda shape: pl.BlockSpec(shape, lambda bi, tab, ln: (0, 0))
        per_row = pl.BlockSpec((1, rows, hd), lambda bi, tab, ln: (bi, 0, 0))
        hbm = pl.BlockSpec(memory_space=pl.ANY)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[const((rows, 1)), const((1, cols)), const((1, cols)),
                      per_row, hbm, hbm],
            out_specs=per_row,
            scratch_shapes=[
                pltpu.VMEM((2, pages, page_rows, hd), k_pool.dtype),
                pltpu.VMEM((2, pages, page_rows, hd), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),       # buffer of the next block
            ],
        )
        kernel = functools.partial(_dma_kernel, **params)
        operands = (q2, k2, v2)
    else:
        def page_spec(i):
            def index_map(bi, j, tab, ln):
                last = jnp.maximum(_n_pages(ln[bi], bs, n_blk) - 1, 0)
                return tab[bi, jnp.minimum(j * pages + i, last)], 0, 0
            return pl.BlockSpec((1, page_rows, hd), index_map)

        const = lambda shape: pl.BlockSpec(shape,
                                           lambda bi, j, tab, ln: (0, 0))
        per_row = pl.BlockSpec((1, rows, hd),
                               lambda bi, j, tab, ln: (bi, 0, 0))
        pages_in = [page_spec(i) for i in range(pages)]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, -(-n_blk // pages)),
            in_specs=[const((rows, 1)), const((1, cols)), const((1, cols)),
                      per_row, *pages_in, *pages_in],
            out_specs=per_row,
            scratch_shapes=[
                pltpu.VMEM((rows, 1), jnp.float32),     # running max m
                pltpu.VMEM((rows, 1), jnp.float32),     # running denominator
                pltpu.VMEM((rows, hd), jnp.float32),    # output accumulator
            ],
        )
        kernel = functools.partial(_page_kernel, **params)
        operands = (q2, *[k2] * pages, *[v2] * pages)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, hd), q.dtype),
        # rows run in order: in the DMA form each one's last block
        # prefetches the next's
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid_spec.grid)),
        interpret=interpret,
    )(jnp.asarray(block_table, jnp.int32), length,
      row_head, col_head, col_pos, *operands)
    return out.reshape(b, kv, g, hd)


def paged_decode_attn_ref(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                          block_table: jax.Array, index: jax.Array,
                          active: Optional[jax.Array] = None, *,
                          ring: Optional[int] = None,
                          window: Optional[int] = None) -> jax.Array:
    """Pure-jnp reference: gather the table, run masked softmax attention.

    Same signature and masking semantics as the kernel, inactive rows'
    zeros included; this is also the path the serving engine's jitted
    while_loop uses off-TPU (mirroring the monolithic decode, whose jnp
    reference serves on CPU)."""
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
    if active is not None:
        out = jnp.where(jnp.asarray(active)[:, None, None, None], out, 0)
    return out.astype(q.dtype)

"""jit'd wrapper exposing the kernel in the model's (b, 1, h, hd) layout."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.runtime import default_interpret
from repro.kernels.decode_attn.decode_attn import decode_attn
from repro.kernels.decode_attn.paged import (paged_decode_attn,
                                             paged_decode_attn_ref)
from repro.kernels.decode_attn.ref import decode_attn_ref


def _interpret() -> bool:
    return default_interpret()


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len, *, window: Optional[int] = None) -> jax.Array:
    """Model-layout entry: q (b, 1, h, hd), caches (b, S, kv, hd)."""
    b, _, h, hd = q.shape
    kv = k_cache.shape[2]
    g = h // kv
    qg = q.reshape(b, kv, g, hd)
    out = decode_attn(qg, k_cache, v_cache, jnp.asarray(cache_len, jnp.int32),
                      window=window, interpret=_interpret())
    return out.reshape(b, 1, h, hd)


def paged_decode_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                           block_table: jax.Array, index: jax.Array,
                           active: Optional[jax.Array] = None, *,
                           ring: Optional[int] = None,
                           window: Optional[int] = None) -> jax.Array:
    """Model-layout entry for the paged cache: q (b, 1, h, hd), pools
    (n_pool, block_size, kv, hd), block_table (b, n_blk), index (b,),
    active (b,) bool or None; an inactive row's output is zeros.

    Off-TPU this routes to the pure-jnp reference rather than interpret-mode
    Pallas: the serving engine traces this inside a jitted ``lax.while_loop``
    decode body, where the interpreter's per-grid-step Python overhead would
    dominate; the reference lowers to plain XLA gather + masked softmax."""
    b, _, h, hd = q.shape
    kv = k_pool.shape[2]
    g = h // kv
    qg = q.reshape(b, kv, g, hd)
    if _interpret():
        out = paged_decode_attn_ref(qg, k_pool, v_pool, block_table, index,
                                    active, ring=ring, window=window)
    else:
        out = paged_decode_attn(qg, k_pool, v_pool, block_table, index,
                                active, ring=ring, window=window,
                                interpret=False)
    return out.reshape(b, 1, h, hd)


__all__ = ["decode_attention", "decode_attn", "decode_attn_ref",
           "paged_decode_attention", "paged_decode_attn",
           "paged_decode_attn_ref"]

"""Paged/block KV cache: device-side block pools plus a host-side free-list
allocator and per-request block tables.

Replaces the monolithic ``(b, max_len, kv, hd)`` serving cache. Storage is
a per-layer pool of fixed-size blocks ``(n_pool, block_size, kv, hd)`` whose
last row is the *trash block* (inactive batch rows write there); requests
address the pool through int32 block tables, one table per *layout group*
(see ``models.cache_layout``):

* ``"full"`` group — full-attention layers. Each request reserves
  ``ceil((prompt + n_new) / block_size)`` blocks from a free list at
  admission (so the decode loop never allocates) and releases them at
  eviction; unreserved table entries point at the trash block and are
  masked off by the ``slot <= index`` validity test.
* ``"ring{R}"`` groups — sliding-window layers. The ring keeps every slot
  live, so each batch slot permanently owns its ``R / block_size`` blocks
  and the table is static.

Block ids are shared across all layers of a group: each layer has its own
K/V pool, indexed by the same table. Recycling a slot needs no zeroing —
the validity masks (age for rings, ``slot <= index`` for full layers)
already exclude a previous tenant's stale blocks.

SSM layers carry per-slot recurrent state ``(n_slots, ...)`` rather than
blocks; admission overwrites the row, the engine freezes inactive rows.
"""
from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import ssm as SSM
from repro.models.transformer import PagedKV, cache_layout


class PagedCache:
    def __init__(self, cfg: ModelConfig, n_slots: int, max_len: int,
                 block_size: int = 16, dtype=jnp.bfloat16,
                 full_blocks: int | None = None):
        """``full_blocks`` caps the full-group physical pool (default: fully
        provisioned, ``n_slots * ceil(max_len / block_size)``); a smaller
        budget makes admission wait on the free list — real paging."""
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.block_size = block_size
        self.layout = cache_layout(cfg, max_len, block_size)

        self._group_phys: Dict[str, int] = {}
        for name, g in self.layout["groups"].items():
            if g["ring"] is not None:
                self._group_phys[name] = n_slots * g["n_blk"]
            else:
                cap = (n_slots * g["n_blk"] if full_blocks is None
                       else full_blocks)
                self._group_phys[name] = cap

        self._tables_np: Dict[str, np.ndarray] = {}
        for name, g in self.layout["groups"].items():
            if g["ring"] is not None:
                nb = g["n_blk"]
                t = np.arange(n_slots * nb, dtype=np.int32).reshape(
                    n_slots, nb)
            else:
                # everything starts unmapped: point at the trash block
                t = np.full((n_slots, g["n_blk"]), self._group_phys[name],
                            np.int32)
            self._tables_np[name] = t
        self._tables_dev: Dict[str, jnp.ndarray] | None = None

        self._free: List[int] = list(range(self._group_phys.get("full", 0)))
        self._owned: Dict[int, List[int]] = {}

        self.pools: Dict[str, Dict] = {}
        for i in range(cfg.n_layers):
            ent: Dict = {}
            lay = self.layout["layers"][f"L{i}"]
            if "attn" in lay:
                n_pool = self._group_phys[lay["attn"]["group"]] + 1
                shape = (n_pool, block_size, cfg.n_kv_heads, cfg.head_dim)
                ent["attn"] = PagedKV(k=jnp.zeros(shape, dtype),
                                      v=jnp.zeros(shape, dtype))
            if "ssm" in lay:
                ent["ssm"] = SSM.init_ssm_state(n_slots, cfg.d_model, cfg.ssm,
                                                jnp.float32)
            self.pools[f"L{i}"] = ent

    # -- block tables -------------------------------------------------------

    @property
    def tables(self) -> Dict[str, jnp.ndarray]:
        if self._tables_dev is None:
            self._tables_dev = {k: jnp.asarray(v)
                                for k, v in self._tables_np.items()}
        return self._tables_dev

    def blocks_needed(self, n_tokens: int) -> int:
        if "full" not in self.layout["groups"]:
            return 0
        return -(-n_tokens // self.block_size)

    def can_admit(self, n_tokens: int) -> bool:
        return self.blocks_needed(n_tokens) <= len(self._free)

    def free_blocks(self) -> int:
        return len(self._free)

    def used_width(self) -> int | None:
        """Width (in blocks) of the full-group table prefix that is actually
        backed by reserved blocks, bucketed up to a multiple of four so a
        jitted consumer sees at most ``n_blk / 4`` distinct shapes.
        ``reserve`` fills each row's table as a contiguous prefix, so
        slicing to this width drops only trash-mapped (masked-off) columns.
        None when the config has no full-attention group or nothing is
        reserved."""
        if "full" not in self.layout["groups"]:
            return None
        used = max((len(b) for b in self._owned.values()), default=0)
        if used == 0:
            return None
        n_blk = self.layout["groups"]["full"]["n_blk"]
        return min(n_blk, 4 * (-(-used // 4)))

    def reserved_blocks(self) -> int:
        """Full-group blocks reserved by the running requests, summed."""
        return sum(len(b) for b in self._owned.values())

    def reserve(self, slot: int, n_tokens: int) -> None:
        """Reserve the request's full token budget up front so the decode
        loop never allocates."""
        need = self.blocks_needed(n_tokens)
        if need > len(self._free):
            raise RuntimeError(
                f"paged cache exhausted: need {need} blocks for slot {slot}, "
                f"{len(self._free)} free")
        blocks = [self._free.pop() for _ in range(need)]
        self._owned[slot] = blocks
        if need:
            self._tables_np["full"][slot, :need] = blocks
            self._tables_dev = None

    def release(self, slot: int) -> None:
        self._free.extend(self._owned.pop(slot, []))
        for name, g in self.layout["groups"].items():
            if g["ring"] is None:
                self._tables_np[name][slot, :] = self._group_phys[name]
        self._tables_dev = None

    # -- admission ----------------------------------------------------------

    def write_prefill(self, slot: int, mono_cache: Dict,
                      n_prompt: int, row: int = 0) -> None:
        """Scatter row ``row`` of a monolithic ``prefill`` cache into the
        pools at ``slot``. Linear layers gather mono positions
        ``0..n_prompt-1``;
        ring layers re-place the retained tail from the mono ring layout
        (slot ``p % size``) onto the padded ring (slot ``p % R``).

        Index arrays are built host-side; the scatter over all layers runs
        as one jitted call (cached per prompt-length bucket), so admission
        costs a handful of dispatches rather than a handful per layer."""
        bs = self.block_size
        idx: Dict[str, tuple] = {}
        for i in range(self.cfg.n_layers):
            lay = self.layout["layers"][f"L{i}"]
            if "attn" not in lay:
                continue
            al = lay["attn"]
            size_m = mono_cache[f"L{i}"]["kv"].k.shape[1]
            keep = min(n_prompt, size_m)
            pos = np.arange(n_prompt - keep, n_prompt)
            src = pos % size_m              # == pos when nothing wrapped
            ring = al["ring"]
            new_slot = pos % ring if ring is not None else pos
            pb = self._tables_np[al["group"]][slot, new_slot // bs]
            idx[f"L{i}"] = (pb.astype(np.int32), (new_slot % bs).astype(
                np.int32), src.astype(np.int32))
        self.pools = self._scatter(self.pools, mono_cache, idx,
                                   jnp.int32(slot), jnp.int32(row))

    @functools.cached_property
    def _scatter(self):
        cfg, layout = self.cfg, self.layout

        def scatter(pools, mono, idx, slot, row):
            new: Dict[str, Dict] = {}
            for i in range(cfg.n_layers):
                lay = layout["layers"][f"L{i}"]
                ent = dict(pools[f"L{i}"])
                m = mono[f"L{i}"]
                if "attn" in lay:
                    pb, off, src = idx[f"L{i}"]
                    kv, pool = m["kv"], ent["attn"]
                    ent["attn"] = PagedKV(
                        k=pool.k.at[pb, off].set(
                            kv.k[row, src].astype(pool.k.dtype)),
                        v=pool.v.at[pb, off].set(
                            kv.v[row, src].astype(pool.v.dtype)))
                if "ssm" in lay:
                    st = m["ssm"]
                    ent["ssm"] = SSM.SSMState(
                        s=ent["ssm"].s.at[slot].set(st.s[row]),
                        conv=ent["ssm"].conv.at[slot].set(st.conv[row]))
                new[f"L{i}"] = ent
            return new

        return jax.jit(scatter)

"""Composable decoder assembly covering all six assigned families.

One ``ModelConfig`` drives which sub-layers each block gets:

* dense / audio / vlm   — GQA self-attention (+ gated cross-attention for
  VLM layers) + gated MLP
* moe                   — GQA self-attention + top-k MoE FFN
                          (+ dense residual MLP for arctic)
* ssm                   — Mamba2 SSD mixer only
* hybrid (jamba)        — 1:7 attention:mamba interleave, MoE every other
                          layer, dense FFN otherwise

``init_params`` / ``abstract_params`` produce the parameter tree plus a
parallel logical-axes tree (see models/param.py). ``forward`` is the training
path, ``prefill``/``decode_step`` the serving path with per-layer caches.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.attention import (KVCache, cross_attention,
                                    cross_attention_init, decode_attention,
                                    self_attention, attention_init)
from repro.models.param import ParamBuilder, build, build_abstract


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_layer(b: ParamBuilder, cfg: ModelConfig, i: int):
    s = b.scope(f"L{i}")
    d = cfg.d_model
    if cfg.layer_is_attn(i):
        L.rmsnorm_init(s, "attn_norm", d)
        attention_init(s, "attn", d, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim)
        if cfg.layer_is_cross_attn(i):
            L.rmsnorm_init(s, "cross_norm", d)
            cross_attention_init(s, "cross", d, cfg.n_heads, cfg.n_kv_heads,
                                 cfg.head_dim)
            s.param("cross_gate", (1,), (None,), init="zeros")
    if cfg.layer_is_ssm(i):
        L.rmsnorm_init(s, "ssm_norm", d)
        SSM.ssm_init(s, "ssm", d, cfg.ssm)
    if cfg.layer_is_moe(i):
        L.rmsnorm_init(s, "ffn_norm", d)
        MOE.moe_init(s, "moe", d, cfg.moe)
        if cfg.moe.dense_residual and cfg.d_ff > 0:
            L.mlp_init(s, "dense_mlp", d, cfg.d_ff)
    elif cfg.d_ff > 0 and cfg.layer_is_attn(i):
        L.rmsnorm_init(s, "ffn_norm", d)
        L.mlp_init(s, "mlp", d, cfg.d_ff)
    elif cfg.d_ff > 0 and cfg.layer_is_ssm(i) and cfg.family == "hybrid":
        L.rmsnorm_init(s, "ffn_norm", d)
        L.mlp_init(s, "mlp", d, cfg.d_ff)


def _init_model(b: ParamBuilder, cfg: ModelConfig):
    L.embed_init(b, "embed", cfg.padded_vocab, cfg.d_model, cfg.n_codebooks)
    lb = b.scope("layers")
    for i in range(cfg.n_layers):
        _init_layer(lb, cfg, i)
    L.rmsnorm_init(b, "final_norm", cfg.d_model)
    if not cfg.tie_embeddings:
        L.head_init(b, "head", cfg.d_model, cfg.padded_vocab, cfg.n_codebooks)


def init_params(cfg: ModelConfig, key: jax.Array, param_dtype=None):
    dtype = jnp.dtype(param_dtype or cfg.param_dtype)
    return build(functools.partial(_init_model, cfg=cfg), key, dtype)


def abstract_params(cfg: ModelConfig, param_dtype=None):
    dtype = jnp.dtype(param_dtype or cfg.param_dtype)
    return build_abstract(functools.partial(_init_model, cfg=cfg), dtype)


# ---------------------------------------------------------------------------
# Layer application
# ---------------------------------------------------------------------------

class LayerAux(NamedTuple):
    moe_loss: jax.Array


def _apply_layer(cfg: ModelConfig, lp: Dict, x: jax.Array, positions: jax.Array,
                 media: Optional[jax.Array], i: int, compute_dtype,
                 cache: Optional[Dict] = None,
                 cache_index: Optional[jax.Array] = None):
    new_cache: Dict[str, Any] = {}
    moe_loss = jnp.zeros((), jnp.float32)

    if cfg.layer_is_attn(i):
        h = L.rmsnorm(lp["attn_norm"], x, cfg.norm_eps)
        kv = cache.get("kv") if cache is not None else None
        y, kv_new = self_attention(
            lp["attn"], h, positions,
            rope_theta=cfg.rope_theta,
            window=cfg.window_for_layer(i),
            compute_dtype=compute_dtype,
            cache=kv, cache_index=cache_index,
            unroll=cfg.unroll_attn_scan,
            windowed_qblock=cfg.windowed_qblock)
        x = x + y
        if kv_new is not None:
            new_cache["kv"] = kv_new
        if cfg.layer_is_cross_attn(i) and media is not None:
            h = L.rmsnorm(lp["cross_norm"], x, cfg.norm_eps)
            y = cross_attention(lp["cross"], h, media,
                                compute_dtype=compute_dtype,
                                unroll=cfg.unroll_attn_scan)
            x = x + jnp.tanh(lp["cross_gate"].astype(x.dtype)) * y

    if cfg.layer_is_ssm(i):
        h = L.rmsnorm(lp["ssm_norm"], x, cfg.norm_eps)
        st = cache.get("ssm") if cache is not None else None
        y, st_new = SSM.ssm_layer(lp["ssm"], h, cfg.ssm, cfg.d_model,
                                  compute_dtype, state=st)
        x = x + y
        if st_new is not None:
            new_cache["ssm"] = st_new

    if cfg.layer_is_moe(i):
        h = L.rmsnorm(lp["ffn_norm"], x, cfg.norm_eps)
        y, aux = MOE.moe_ffn(lp["moe"], h, cfg.moe, compute_dtype)
        if cfg.moe.dense_residual and "dense_mlp" in lp:
            y = y + L.mlp(lp["dense_mlp"], h, compute_dtype)
        x = x + y
        moe_loss = aux.load_balance_loss + aux.router_z_loss
    elif "mlp" in lp:
        h = L.rmsnorm(lp["ffn_norm"], x, cfg.norm_eps)
        x = x + L.mlp(lp["mlp"], h, compute_dtype)

    return x, new_cache, LayerAux(moe_loss)


# ---------------------------------------------------------------------------
# Training / scoring forward
# ---------------------------------------------------------------------------

def forward(cfg: ModelConfig, params: Dict, tokens: jax.Array,
            media: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """tokens: (b, s) int32 — or (b, s, n_codebooks) for audio.
    Returns (logits, total_moe_aux_loss)."""
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    x = L.embed(params["embed"], tokens, compute_dtype)
    positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
    moe_loss = jnp.zeros((), jnp.float32)

    for i in range(cfg.n_layers):
        lp = params["layers"][f"L{i}"]

        def run(lp, x, media, i=i):
            return _apply_layer(cfg, lp, x, positions, media, i, compute_dtype)

        if cfg.remat:
            run = jax.checkpoint(run, static_argnums=())
        x, _, aux = run(lp, x, media)
        moe_loss = moe_loss + aux.moe_loss

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = L.tied_head(params["embed"], x, compute_dtype,
                             cfg.logits_softcap)
    else:
        logits = L.head(params["head"], x, compute_dtype, cfg.logits_softcap)
    return logits, moe_loss


def loss_fn(cfg: ModelConfig, params: Dict, batch: Dict) -> Tuple[jax.Array, Dict]:
    """Next-token cross-entropy (+ MoE aux). batch: tokens, labels[, media]."""
    logits, moe_loss = forward(cfg, params, batch["tokens"],
                               batch.get("media"))
    labels = batch["labels"]
    logits = logits.astype(jnp.float32)
    if cfg.sharded_ce:
        # vocab-sharded friendly CE: logsumexp + one-hot contraction keep the
        # vocab dim a reduction (partial-sum + tiny all-reduce) instead of a
        # gather that forces a full-logits all-gather under SPMD (§Perf).
        lse = jax.nn.logsumexp(logits, axis=-1)
        onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
        label_logit = jnp.einsum("...v,...v->...", logits, onehot)
        nll = lse - label_logit
    else:
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    ce = nll.mean()
    loss = ce + moe_loss
    return loss, {"ce": ce, "moe_loss": moe_loss}


# ---------------------------------------------------------------------------
# Serving: cache init / prefill / decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict:
    cache: Dict[str, Dict] = {}
    for i in range(cfg.n_layers):
        entry: Dict[str, Any] = {}
        if cfg.layer_is_attn(i):
            w = cfg.window_for_layer(i)
            size = min(w, max_len) if w is not None else max_len
            entry["kv"] = KVCache(
                k=jnp.zeros((batch, size, cfg.n_kv_heads, cfg.head_dim), dtype),
                v=jnp.zeros((batch, size, cfg.n_kv_heads, cfg.head_dim), dtype))
            if cfg.layer_is_cross_attn(i):
                entry["cross"] = KVCache(
                    k=jnp.zeros((batch, cfg.n_media_tokens, cfg.n_kv_heads,
                                 cfg.head_dim), dtype),
                    v=jnp.zeros((batch, cfg.n_media_tokens, cfg.n_kv_heads,
                                 cfg.head_dim), dtype))
        if cfg.layer_is_ssm(i):
            entry["ssm"] = SSM.init_ssm_state(batch, cfg.d_model, cfg.ssm,
                                              jnp.float32)
        cache[f"L{i}"] = entry
    return cache


def cache_axes(cfg: ModelConfig, long_context: bool = False) -> Dict:
    """Logical axes tree matching ``init_cache`` output."""
    kv_seq = "kv_seq"
    ax: Dict[str, Dict] = {}
    for i in range(cfg.n_layers):
        entry: Dict[str, Any] = {}
        if cfg.layer_is_attn(i):
            spec = ("batch", kv_seq, "kv_heads", "head_dim")
            entry["kv"] = KVCache(k=spec, v=spec)
            if cfg.layer_is_cross_attn(i):
                mspec = ("batch", "media", "kv_heads", "head_dim")
                entry["cross"] = KVCache(k=mspec, v=mspec)
        if cfg.layer_is_ssm(i):
            entry["ssm"] = SSM.SSMState(
                s=("batch", "ssm_heads", "ssm_state", None),
                conv=("batch", None, "ssm_heads"))
        ax[f"L{i}"] = entry
    return ax


def _ring_slot(i_cfg_window: Optional[int], index: jax.Array) -> jax.Array:
    if i_cfg_window is None:
        return index
    return jnp.mod(index, i_cfg_window)


def decode_step(cfg: ModelConfig, params: Dict, tokens: jax.Array,
                cache: Dict, index: jax.Array,
                media: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Dict]:
    """One new token per sequence. tokens: (b, 1) (or (b, 1, n_q) audio);
    ``index`` is the number of tokens already in the cache."""
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    x = L.embed(params["embed"], tokens, compute_dtype)
    positions = jnp.full(x.shape[:2], index, jnp.int32)
    new_cache: Dict[str, Dict] = {}

    for i in range(cfg.n_layers):
        lp = params["layers"][f"L{i}"]
        entry = cache[f"L{i}"]
        out_entry: Dict[str, Any] = dict(entry)
        if cfg.layer_is_attn(i):
            h = L.rmsnorm(lp["attn_norm"], x, cfg.norm_eps)
            w = cfg.window_for_layer(i)
            kv = entry["kv"]
            size = kv.k.shape[1]
            wq = lp["attn"]["wq"].astype(compute_dtype)
            wk = lp["attn"]["wk"].astype(compute_dtype)
            wv = lp["attn"]["wv"].astype(compute_dtype)
            wo = lp["attn"]["wo"].astype(compute_dtype)
            q = L.apply_rope(jnp.einsum("bsd,dhk->bshk", h, wq), positions,
                             cfg.rope_theta)
            k = L.apply_rope(jnp.einsum("bsd,dhk->bshk", h, wk), positions,
                             cfg.rope_theta)
            v = jnp.einsum("bsd,dhk->bshk", h, wv)
            slot = jnp.mod(index, size) if w is not None else index
            k_c = jax.lax.dynamic_update_slice_in_dim(
                kv.k, k.astype(kv.k.dtype), slot, axis=1)
            v_c = jax.lax.dynamic_update_slice_in_dim(
                kv.v, v.astype(kv.v.dtype), slot, axis=1)
            eff_len = jnp.minimum(index + 1, size)
            att = decode_attention(q, k_c, v_c, eff_len, window=None)
            x = x + jnp.einsum("bshk,hkd->bsd", att, wo)
            out_entry["kv"] = KVCache(k_c, v_c)
            if cfg.layer_is_cross_attn(i) and "cross" in entry:
                h = L.rmsnorm(lp["cross_norm"], x, cfg.norm_eps)
                ck = entry["cross"]
                cq = jnp.einsum("bsd,dhk->bshk", h,
                                lp["cross"]["wq"].astype(compute_dtype))
                catt = decode_attention(cq, ck.k, ck.v,
                                        jnp.int32(ck.k.shape[1]), window=None)
                y = jnp.einsum("bshk,hkd->bsd", catt,
                               lp["cross"]["wo"].astype(compute_dtype))
                x = x + jnp.tanh(lp["cross_gate"].astype(x.dtype)) * y
        if cfg.layer_is_ssm(i):
            h = L.rmsnorm(lp["ssm_norm"], x, cfg.norm_eps)
            y, st = SSM.ssm_layer(lp["ssm"], h, cfg.ssm, cfg.d_model,
                                  compute_dtype, state=entry["ssm"])
            x = x + y
            out_entry["ssm"] = st
        if cfg.layer_is_moe(i):
            h = L.rmsnorm(lp["ffn_norm"], x, cfg.norm_eps)
            y, _ = MOE.moe_ffn(lp["moe"], h, cfg.moe, compute_dtype)
            if cfg.moe.dense_residual and "dense_mlp" in lp:
                y = y + L.mlp(lp["dense_mlp"], h, compute_dtype)
            x = x + y
        elif "mlp" in lp:
            h = L.rmsnorm(lp["ffn_norm"], x, cfg.norm_eps)
            x = x + L.mlp(lp["mlp"], h, compute_dtype)
        new_cache[f"L{i}"] = out_entry

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = L.tied_head(params["embed"], x, compute_dtype,
                             cfg.logits_softcap)
    else:
        logits = L.head(params["head"], x, compute_dtype, cfg.logits_softcap)
    return logits, new_cache


def prefill(cfg: ModelConfig, params: Dict, tokens: jax.Array, cache: Dict,
            media: Optional[jax.Array] = None) -> Tuple[jax.Array, Dict]:
    """Fill the cache from a full prompt; returns (last-position logits, cache)."""
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    x = L.embed(params["embed"], tokens, compute_dtype)
    s = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s), x.shape[:2])
    new_cache: Dict[str, Dict] = {}

    for i in range(cfg.n_layers):
        lp = params["layers"][f"L{i}"]
        entry = cache[f"L{i}"]
        out_entry: Dict[str, Any] = dict(entry)
        if cfg.layer_is_attn(i):
            h = L.rmsnorm(lp["attn_norm"], x, cfg.norm_eps)
            w = cfg.window_for_layer(i)
            kv = entry["kv"]
            size = kv.k.shape[1]
            wq = lp["attn"]["wq"].astype(compute_dtype)
            wk = lp["attn"]["wk"].astype(compute_dtype)
            wv = lp["attn"]["wv"].astype(compute_dtype)
            wo = lp["attn"]["wo"].astype(compute_dtype)
            q = L.apply_rope(jnp.einsum("bsd,dhk->bshk", h, wq), positions,
                             cfg.rope_theta)
            k = L.apply_rope(jnp.einsum("bsd,dhk->bshk", h, wk), positions,
                             cfg.rope_theta)
            v = jnp.einsum("bsd,dhk->bshk", h, wv)
            from repro.models.attention import (flash_attention,
                                                flash_attention_windowed)
            if cfg.windowed_qblock and w is not None:
                att = flash_attention_windowed(q, k, v, window=w)
            else:
                att = flash_attention(q, k, v, causal=True, window=w,
                                      unroll=cfg.unroll_attn_scan)
            x = x + jnp.einsum("bshk,hkd->bsd", att, wo)
            if w is not None and s >= size:
                # ring layout: slot of token p is p % size
                k_tail = jnp.roll(k[:, -size:], s % size, axis=1)
                v_tail = jnp.roll(v[:, -size:], s % size, axis=1)
                out_entry["kv"] = KVCache(k_tail.astype(kv.k.dtype),
                                          v_tail.astype(kv.v.dtype))
            else:
                k_c = jax.lax.dynamic_update_slice_in_dim(
                    kv.k, k.astype(kv.k.dtype), 0, axis=1)
                v_c = jax.lax.dynamic_update_slice_in_dim(
                    kv.v, v.astype(kv.v.dtype), 0, axis=1)
                out_entry["kv"] = KVCache(k_c, v_c)
            if cfg.layer_is_cross_attn(i) and media is not None:
                h = L.rmsnorm(lp["cross_norm"], x, cfg.norm_eps)
                y = cross_attention(lp["cross"], h, media,
                                    compute_dtype=compute_dtype,
                                    unroll=cfg.unroll_attn_scan)
                x = x + jnp.tanh(lp["cross_gate"].astype(x.dtype)) * y
                ck = jnp.einsum("bmd,dhk->bmhk", media,
                                lp["cross"]["wk"].astype(compute_dtype))
                cv = jnp.einsum("bmd,dhk->bmhk", media,
                                lp["cross"]["wv"].astype(compute_dtype))
                old = entry["cross"]
                out_entry["cross"] = KVCache(ck.astype(old.k.dtype),
                                             cv.astype(old.v.dtype))
        if cfg.layer_is_ssm(i):
            h = L.rmsnorm(lp["ssm_norm"], x, cfg.norm_eps)
            di = cfg.ssm.d_inner(cfg.d_model)
            nh = cfg.ssm.n_heads(cfg.d_model)
            ds = cfg.ssm.d_state
            proj = jnp.einsum("bsd,dk->bsk", h,
                              lp["ssm"]["in_proj"].astype(compute_dtype))
            z, xBC, dt_raw = SSM._split_proj(proj, di, ds, nh)
            xBC_c = SSM._causal_conv(xBC, lp["ssm"]["conv_w"].astype(compute_dtype),
                                     lp["ssm"]["conv_b"].astype(compute_dtype))
            xin, B, C = xBC_c[..., :di], xBC_c[..., di:di + ds], xBC_c[..., di + ds:]
            dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                                 + lp["ssm"]["dt_bias"].astype(jnp.float32))
            a = -jnp.exp(lp["ssm"]["A_log"].astype(jnp.float32))
            xs = xin.reshape(*xin.shape[:2], nh, cfg.ssm.head_dim)
            pad = (-s) % cfg.ssm.chunk_size
            if pad:
                xs_p = jnp.pad(xs, ((0, 0), (0, pad), (0, 0), (0, 0)))
                dt_p = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
                B_p = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
                C_p = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
            else:
                xs_p, dt_p, B_p, C_p = xs, dt, B, C
            y, final_state = SSM.ssd_chunked(xs_p, dt_p, a, B_p, C_p,
                                             cfg.ssm.chunk_size)
            y = y[:, :s] + \
                lp["ssm"]["D"].astype(jnp.float32)[:, None] * xs.astype(jnp.float32)
            y = y.reshape(x.shape[0], s, di)
            out = SSM._gated_norm(y, z, lp["ssm"]["norm_scale"])
            x = x + jnp.einsum("bsk,kd->bsd", out.astype(compute_dtype),
                               lp["ssm"]["out_proj"].astype(compute_dtype)
                               ).astype(x.dtype)
            conv_hist = jnp.concatenate(
                [jnp.zeros((x.shape[0], max(0, cfg.ssm.conv_width - 1 - s),
                            di + 2 * ds), jnp.float32),
                 xBC[:, -(cfg.ssm.conv_width - 1):].astype(jnp.float32)], axis=1)
            out_entry["ssm"] = SSM.SSMState(final_state.astype(jnp.float32),
                                            conv_hist)
        if cfg.layer_is_moe(i):
            h = L.rmsnorm(lp["ffn_norm"], x, cfg.norm_eps)
            y, _ = MOE.moe_ffn(lp["moe"], h, cfg.moe, compute_dtype)
            if cfg.moe.dense_residual and "dense_mlp" in lp:
                y = y + L.mlp(lp["dense_mlp"], h, compute_dtype)
            x = x + y
        elif "mlp" in lp:
            h = L.rmsnorm(lp["ffn_norm"], x, cfg.norm_eps)
            x = x + L.mlp(lp["mlp"], h, compute_dtype)
        new_cache[f"L{i}"] = out_entry

    x = L.rmsnorm(params["final_norm"], x[:, -1:], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = L.tied_head(params["embed"], x, compute_dtype,
                             cfg.logits_softcap)
    else:
        logits = L.head(params["head"], x, compute_dtype, cfg.logits_softcap)
    return logits, new_cache


# ---------------------------------------------------------------------------
# Serving: paged cache layout + decode
# ---------------------------------------------------------------------------

class PagedKV(NamedTuple):
    """Per-layer K/V block pools, shape (n_pool, block_size, kv_heads,
    head_dim). The last pool row is the trash block inactive slots write
    into; every other row is addressed through a per-request block table."""
    k: jax.Array
    v: jax.Array


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def cache_layout(cfg: ModelConfig, max_len: int, block_size: int = 16) -> Dict:
    """Static paged-cache geometry for ``cfg``.

    Layers fall into *layout groups* that share one block table per request:

    * ``"full"`` — full-attention layers; token position ``p`` is logical
      slot ``p``, the table has ``ceil(max_len / block_size)`` entries and
      is populated by a free-list allocator at admission.
    * ``"ring{R}"`` — sliding-window layers with the window's ring capacity
      padded to a block multiple ``R``; position ``p`` lives at slot
      ``p % R``. Every slot is always live, so ring tables are static
      (each batch slot permanently owns its ``R / block_size`` blocks).

    Block ids are valid across all layers of a group: each layer has its own
    K/V pool, indexed by the same table. Cross-attention (media) layers have
    no paged form — serve those archs with the legacy ``ServeEngine``.
    """
    layers: Dict[str, Dict] = {}
    groups: Dict[str, Dict] = {}
    for i in range(cfg.n_layers):
        ent: Dict[str, Any] = {}
        if cfg.layer_is_cross_attn(i):
            raise NotImplementedError(
                "paged cache does not cover cross-attention (media) layers; "
                "use the legacy ServeEngine for media archs")
        if cfg.layer_is_attn(i):
            w = cfg.window_for_layer(i)
            size = min(w, max_len) if w is not None else max_len
            if w is not None:
                ring = _ceil_to(size, block_size)
                group = f"ring{ring}"
                groups.setdefault(group,
                                  {"ring": ring,
                                   "n_blk": ring // block_size})
            else:
                ring = None
                group = "full"
                groups.setdefault(group,
                                  {"ring": None,
                                   "n_blk": _ceil_to(max_len, block_size)
                                   // block_size})
            ent["attn"] = {"group": group, "ring": ring, "window": size}
        if cfg.layer_is_ssm(i):
            ent["ssm"] = True
        layers[f"L{i}"] = ent
    return {"layers": layers, "groups": groups, "block_size": block_size,
            "max_len": max_len}


def decode_step_paged(cfg: ModelConfig, params: Dict, tokens: jax.Array,
                      pools: Dict, tables: Dict, index: jax.Array,
                      active: Optional[jax.Array] = None, *,
                      max_len: int, block_size: int = 16
                      ) -> Tuple[jax.Array, Dict]:
    """One decode step against the paged cache; batch rows are independent
    requests at independent positions.

    tokens (n, 1); ``index`` (n,) int32 is the position each row's token is
    written at (== tokens already cached); ``tables`` maps layout-group name
    to (n, n_blk) int32 physical block ids; ``pools`` maps ``L{i}`` to
    ``{"attn": PagedKV}`` / ``{"ssm": SSMState}`` with leading pool / slot
    dims. ``active`` (n,) bool, when given, redirects inactive rows' KV
    writes to the trash block (last pool row) and freezes their SSM state,
    so finished requests can ride in the batch without corrupting anything;
    the paged kernel walks none of their pages and gives them zeros.
    """
    layout = cache_layout(cfg, max_len, block_size)
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    x = L.embed(params["embed"], tokens, compute_dtype)
    n = x.shape[0]
    index = jnp.asarray(index, jnp.int32)
    positions = index[:, None]
    rows = jnp.arange(n)
    from repro.kernels.decode_attn.ops import paged_decode_attention
    new_pools: Dict[str, Dict] = {}

    for i in range(cfg.n_layers):
        lp = params["layers"][f"L{i}"]
        entry = pools[f"L{i}"]
        out_entry: Dict[str, Any] = dict(entry)
        if cfg.layer_is_attn(i):
            al = layout["layers"][f"L{i}"]["attn"]
            h = L.rmsnorm(lp["attn_norm"], x, cfg.norm_eps)
            wq = lp["attn"]["wq"].astype(compute_dtype)
            wk = lp["attn"]["wk"].astype(compute_dtype)
            wv = lp["attn"]["wv"].astype(compute_dtype)
            wo = lp["attn"]["wo"].astype(compute_dtype)
            q = L.apply_rope(jnp.einsum("bsd,dhk->bshk", h, wq), positions,
                             cfg.rope_theta)
            k = L.apply_rope(jnp.einsum("bsd,dhk->bshk", h, wk), positions,
                             cfg.rope_theta)
            v = jnp.einsum("bsd,dhk->bshk", h, wv)
            table = tables[al["group"]]
            ring = al["ring"]
            slot = jnp.mod(index, ring) if ring is not None else index
            pb = table[rows, slot // block_size]
            off = jnp.mod(slot, block_size)
            kv = entry["attn"]
            if active is not None:
                pb = jnp.where(active, pb, kv.k.shape[0] - 1)
            k_pool = kv.k.at[pb, off].set(k[:, 0].astype(kv.k.dtype))
            v_pool = kv.v.at[pb, off].set(v[:, 0].astype(kv.v.dtype))
            att = paged_decode_attention(q, k_pool, v_pool, table, index,
                                         active, ring=ring,
                                         window=al["window"])
            x = x + jnp.einsum("bshk,hkd->bsd", att, wo)
            out_entry["attn"] = PagedKV(k_pool, v_pool)
        if cfg.layer_is_ssm(i):
            h = L.rmsnorm(lp["ssm_norm"], x, cfg.norm_eps)
            y, st = SSM.ssm_layer(lp["ssm"], h, cfg.ssm, cfg.d_model,
                                  compute_dtype, state=entry["ssm"])
            x = x + y
            if active is not None:
                st = jax.tree.map(
                    lambda new, old: jnp.where(
                        active.reshape((-1,) + (1,) * (new.ndim - 1)),
                        new, old),
                    st, entry["ssm"])
            out_entry["ssm"] = st
        if cfg.layer_is_moe(i):
            h = L.rmsnorm(lp["ffn_norm"], x, cfg.norm_eps)
            y, _ = MOE.moe_ffn(lp["moe"], h, cfg.moe, compute_dtype)
            if cfg.moe.dense_residual and "dense_mlp" in lp:
                y = y + L.mlp(lp["dense_mlp"], h, compute_dtype)
            x = x + y
        elif "mlp" in lp:
            h = L.rmsnorm(lp["ffn_norm"], x, cfg.norm_eps)
            x = x + L.mlp(lp["mlp"], h, compute_dtype)
        new_pools[f"L{i}"] = out_entry

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = L.tied_head(params["embed"], x, compute_dtype,
                             cfg.logits_softcap)
    else:
        logits = L.head(params["head"], x, compute_dtype, cfg.logits_softcap)
    return logits, new_pools

"""Plain reference of the dense decoder and of one WASGD round.

Straightforward ``jax.numpy`` in float32, every matmul at ``HIGHEST``
precision, no kernel, cache or batching, written from the published
description and imported from nowhere in the program. It follows the
configuration as it is run (``bench/configs/<name>.json``): pre-norm
decoder layers of RMSNorm, rotary attention over all of each head's
dimensions (rotate-half form), grouped K/V heads and a SwiGLU MLP, no
biases, a final RMSNorm and an untied output head. Departures from a
published model are listed in its configuration file.

It computes in blocks so that it fits beside nothing else on one chip:
attention by blocks of 512 queries, the loss by blocks of 512 tokens, and
each layer under ``jax.checkpoint`` when differentiated.

``counts`` gives what each layer holds, for the FLOP and byte counts of
``bench/flops.py``.

``precision="fp8"`` is the control: every matmul operand is rounded to the
3 mantissa bits of float8 e4m3 (exponent range unlimited, as a scaled fp8
path has it) before an f32-accumulating product.

The WASGD round (paper Alg. 1, Eq. 10): each of p workers takes ``tau``
SGD steps on its own rows; its energy is the sum of its losses at the
recorded steps (Alg. 2 ``RecordIndex``); theta is the Boltzmann weight
``softmax(-a * h / sum(h))`` (Eq. 13); then every worker moves to
``(1 - beta) x_i + beta * sum_j theta_j x_j`` (Eq. 10).
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 512


# -- parameters ------------------------------------------------------------------

def dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return dict(d=d, h=h, kv=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or d // h,
                ff=cfg["intermediate_size"], v=cfg["vocab_size"],
                n=cfg["num_hidden_layers"], theta=cfg["rope_theta"],
                eps=cfg["rms_norm_eps"])


def layout(cfg: dict, dtype=jnp.float32) -> Tuple[Dict, Dict]:
    """(shapes, logical axes) of the parameter tree, by name."""
    m = dims(cfg)
    d, h, kv, hd, ff = m["d"], m["h"], m["kv"], m["hd"], m["ff"]

    def leaf(shape, ax):
        return jax.ShapeDtypeStruct(shape, dtype), ax

    tree = {"embed": {"tok": leaf((m["v"], d), ("vocab", "embed"))},
            "final_norm": {"scale": leaf((d,), ("embed",))},
            "head": {"w": leaf((d, m["v"]), ("embed", "vocab"))},
            "layers": {}}
    for i in range(m["n"]):
        tree["layers"][f"L{i}"] = {
            "attn_norm": {"scale": leaf((d,), ("embed",))},
            "attn": {
                "wq": leaf((d, h, hd), ("embed", "heads", "head_dim")),
                "wk": leaf((d, kv, hd), ("embed", "kv_heads", "head_dim")),
                "wv": leaf((d, kv, hd), ("embed", "kv_heads", "head_dim")),
                "wo": leaf((h, hd, d), ("heads", "head_dim", "embed"))},
            "ffn_norm": {"scale": leaf((d,), ("embed",))},
            "mlp": {"w_gate": leaf((d, ff), ("embed", "ffn")),
                    "w_up": leaf((d, ff), ("embed", "ffn")),
                    "w_down": leaf((ff, d), ("ffn", "embed"))}}

    def split(t, k):
        return {n: split(x, k) if isinstance(x, dict) else x[k]
                for n, x in t.items()}
    return split(tree, 0), split(tree, 1)


def counts(cfg: dict) -> flops.Counts:
    """Per layer: q, k, v, o and the gated MLP's three matrices, which each
    token multiplies; two norm scales; attention over every cached token.
    Outside the layers: the untied output head, the final norm, and one
    embedding row per token."""
    m = dims(cfg)
    d, h, kv, hd, ff = m["d"], m["h"], m["kv"], m["hd"], m["ff"]
    layer = flops.Layer(
        matmul=d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff,
        other=2 * d, attention=flops.Attention(h, kv, hd))
    return flops.Counts(layers=(layer,) * m["n"], head=d * m["v"], other=d,
                        embed_row=d)


# -- arithmetic ------------------------------------------------------------------

def _round(x: jax.Array, precision: str) -> jax.Array:
    if precision == "f32":
        return x
    if precision != "fp8":
        raise ValueError(f"unknown precision {precision!r}")
    # round to nearest even at 3 mantissa bits
    b = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    drop = 20
    lsb = (b >> drop) & 1
    b = (b + (1 << (drop - 1)) - 1 + lsb) & ~jnp.uint32((1 << drop) - 1)
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def _mm(eq: str, a, b, precision: str):
    return jnp.einsum(eq, _round(a, precision), _round(b, precision),
                      precision=HIGHEST, preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, precision: str):
    """Causal attention of q (s, h, hd) over k, v (s, kv, hd), by blocks of
    queries."""
    s, h, hd = q.shape
    kv = k.shape[1]
    g = h // kv
    nb = -(-s // BLOCK)
    pad = nb * BLOCK - s
    qg = (q * hd ** -0.5).reshape(s, kv, g, hd)
    qg = jnp.pad(qg, ((0, pad), (0, 0), (0, 0), (0, 0)))
    kpos = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qg, i * BLOCK, BLOCK, 0)
        sc = _mm("qkgd,tkd->kgqt", qb, k, precision)
        qpos = i * BLOCK + jnp.arange(BLOCK)
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return _mm("kgqt,tkd->qkgd", p, v, precision)

    out = jax.lax.map(block, jnp.arange(nb))
    return out.reshape(nb * BLOCK, h, hd)[:s]


def _layer(m, precision, lp, x, pos):
    h = _rms(x, lp["attn_norm"]["scale"], m["eps"])
    a = lp["attn"]
    q = _rope(_mm("sd,dhk->shk", h, a["wq"], precision), pos, m["theta"])
    k = _rope(_mm("sd,dhk->shk", h, a["wk"], precision), pos, m["theta"])
    v = _mm("sd,dhk->shk", h, a["wv"], precision)
    x = x + _mm("shk,hkd->sd", _attention(q, k, v, precision), a["wo"],
                precision)
    h = _rms(x, lp["ffn_norm"]["scale"], m["eps"])
    f = lp["mlp"]
    u = jax.nn.silu(_mm("sd,df->sf", h, f["w_gate"], precision)) \
        * _mm("sd,df->sf", h, f["w_up"], precision)
    return x + _mm("sf,fd->sd", u, f["w_down"], precision)


def hidden(cfg: dict, params: Dict, tokens, precision: str = "f32",
           remat: bool = False):
    """Final-normed hidden states (s, d) of one sequence."""
    m = dims(cfg)
    x = params["embed"]["tok"][tokens].astype(jnp.float32)
    pos = jnp.arange(tokens.shape[0])
    layer = functools.partial(_layer, m, precision)
    if remat:
        layer = jax.checkpoint(layer)
    for i in range(m["n"]):
        x = layer(params["layers"][f"L{i}"], x, pos)
    return _rms(x, params["final_norm"]["scale"], m["eps"])


def logits(cfg: dict, params: Dict, tokens, precision: str = "f32"):
    return _mm("sd,dv->sv", hidden(cfg, params, tokens, precision),
               params["head"]["w"], precision)


def loss(cfg: dict, params: Dict, tokens, labels, precision: str = "f32"):
    """Mean next-token NLL of one sequence; the head and the softmax by
    blocks of tokens."""
    x = hidden(cfg, params, tokens, precision, remat=True)
    s = tokens.shape[0]
    nb = -(-s // BLOCK)
    pad = nb * BLOCK - s
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(nb, BLOCK, -1)
    lb = jnp.pad(labels, (0, pad)).reshape(nb, BLOCK)
    valid = (jnp.arange(nb * BLOCK) < s).reshape(nb, BLOCK)

    @jax.checkpoint
    def block(xs, ls, ok):
        lg = _mm("sd,dv->sv", xs, params["head"]["w"], precision)
        nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, ls[:, None], -1)[:, 0]
        return jnp.sum(jnp.where(ok, nll, 0.0))

    tot = jax.lax.map(lambda a: block(*a), (xb, lb, valid)).sum()
    return tot / s


# -- one WASGD round -----------------------------------------------------------------

def record_indices(tau: int, m: int, c: int) -> List[int]:
    """Alg. 2 Function 1: step ``(i+1)*tau/c - j - 1`` for ``j < m/c`` of
    each of the ``c`` segments of the round."""
    c = max(1, min(c, tau))
    per = max(1, min(m // c if m >= c else 1, tau // c))
    out = set()
    for i in range(c):
        end = (i + 1) * tau // c
        for j in range(per):
            if 0 <= end - j - 1 < tau:
                out.add(end - j - 1)
    return sorted(out)


def make_round(cfg: dict, wasgd: dict, precision: str, mesh=None):
    """``round(x, tokens, labels) -> (x, losses (p, tau))`` on worker-stacked
    params ``x``; ``tokens``/``labels`` are ``(p, tau, b_local, s)``. With
    ``mesh`` (one axis ``"w"``), each device takes its workers' local
    steps."""
    lr, beta, a = wasgd["lr"], wasgd["beta"], wasgd["a_tilde"]
    rec = np.zeros((wasgd["tau"],), bool)
    rec[record_indices(wasgd["tau"], wasgd["m_estimate"],
                       wasgd["record_chunks"])] = True
    rec = jnp.asarray(rec)

    def rows_loss(x, toks, labs):
        per = jax.vmap(lambda t, l: loss(cfg, x, t, l, precision))(toks,
                                                                   labs)
        return per.mean()

    def worker(x, toks, labs):
        def step(x, tl):
            l, g = jax.value_and_grad(rows_loss)(x, *tl)
            return jax.tree.map(lambda p, d: p - lr * d, x, g), l
        return jax.lax.scan(step, x, (toks, labs))

    def local(x, toks, labs):
        # one worker after another, so one worker's gradients are live
        return jax.lax.map(lambda a: worker(*a), (x, toks, labs))

    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        local = jax.shard_map(local, mesh=mesh, in_specs=P("w"),
                              out_specs=P("w"))

    def rnd(x, toks, labs):
        x, losses = local(x, toks, labs)                     # (p, tau)
        h = jnp.sum(jnp.where(rec[None, :], losses, 0.0), axis=1)
        theta = jax.nn.softmax(-a * h / jnp.sum(h))

        def eq10(leaf):
            mix = jnp.tensordot(theta, leaf, axes=1, precision=HIGHEST)
            return (1.0 - beta) * leaf + beta * mix[None]
        return jax.tree.map(eq10, x), losses

    return jax.jit(rnd, donate_argnums=(0,))


def worker_major(batch: Dict, p: int, tau: int) -> Tuple[np.ndarray, ...]:
    """Round rows ``(p * tau * b_local, s)`` -> ``(p, tau, b_local, s)``,
    worker-major as the round deals them out."""
    out = []
    for k in ("tokens", "labels"):
        x = np.asarray(batch[k])
        out.append(x.reshape(p, tau, x.shape[0] // (p * tau), *x.shape[1:]))
    return tuple(out)


# -- served tokens ------------------------------------------------------------------

def served_gaps(cfg: dict, params: Dict, tokens, start: int, n: int,
                control: Optional[str] = None):
    """For one padded sequence ``tokens`` (prompt then served tokens, then
    padding): at each of the ``n`` served positions, how far the served
    token's logit lies below the reference's best. With ``control`` set,
    also the gap of the token that the reference in that precision puts
    first."""
    lg = logits(cfg, params, tokens)
    idx = start - 1 + jnp.arange(n)
    rows = lg[idx]
    served = tokens[idx + 1]
    best = rows.max(-1)
    gap = best - jnp.take_along_axis(rows, served[:, None], -1)[:, 0]
    if control is None:
        return gap
    top = jnp.argmax(logits(cfg, params, tokens, control)[idx], -1)
    return gap, best - jnp.take_along_axis(rows, top[:, None], -1)[:, 0]


def leaf_paths(tree: Dict, prefix: Tuple[str, ...] = ()) -> List:
    out = []
    for k in sorted(tree):
        if isinstance(tree[k], dict):
            out.extend(leaf_paths(tree[k], prefix + (k,)))
        else:
            out.append(prefix + (k,))
    return out


def get(tree: Dict, path: Sequence[str]):
    for k in path:
        tree = tree[k]
    return tree

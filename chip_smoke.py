#!/usr/bin/env python3
"""Smoke test of the WASGD round and the paged engine on TPU chips.

    python chip_smoke.py               # one chip: kernels, training, serving
    python chip_smoke.py --four-chips  # one host of four chips: Trainer(mesh=)

One chip, in this order, every phase fatal:

1. device check: the first device must be a TPU, or the run stops;
2. kernel parity at stablelm-1.6b widths: the fused Eq. 10 kernel (``wagg``,
   f32, and int8 with an Alg. 4 mask) against its reference, and the paged
   decode-attention kernel (kv=32, hd=64, bf16) against its reference; each
   compiled program must hold the kernel (``tpu_custom_call``);
3. training through ``Trainer``: stablelm-1.6b at every published width
   with its depth cut to 4 layers, p=2 workers on the chip, tau=2, seq
   1024, b_local 2, 3 rounds each under ``pallas_wagg:f32`` and
   ``einsum:f32``; losses finite, final params of the two specs equal;
4. serving through ``ContinuousEngine``: the trained beta=1 consensus,
   8 slots, 16-token blocks, bf16 cache, 8 seeded 128-token prompts with 32
   greedy new tokens each; every request finishes at its full length and
   the decode chunk holds the paged kernel.

``--four-chips`` runs only the multi-chip path: p=4 workers, one per chip,
on a ("data", "model") = (4, 1) mesh, ``rs_ag:f32`` against ``einsum:f32``
on the same mesh, with every worker-stacked leaf's 4 rows on 4 devices.

Weights and data are random, made from ``--seed``. Every number printed is
a first reading of a device metric, not a benchmark. The last line of
standard output is one JSON object: ``{"ok": true, "device": {...}}``; a
failed phase exits nonzero without it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "stablelm-1.6b"
N_LAYERS = 4            # of 24: four whole periods of this dense model
TAU, SEQ, B_LOCAL, ROUNDS, LR = 2, 1024, 2, 3, 0.03
# Final params of a spec against einsum:f32: max|diff| at most PARAM_RTOL of
# how far einsum:f32 moved them from init, which must be at least MIN_MOVE.
# A state left at init, or a round whose exchange is dropped, differs by
# about the whole move.
PARAM_RTOL, MIN_MOVE = 0.05, 1e-4
SERVE_SLOTS, SERVE_BLOCK, PROMPT, NEW = 8, 16, 128, 32


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(*parts) -> None:
    print(*parts, flush=True)


def require_kernel(jitted, args, what: str, **kwargs) -> None:
    """The compiled program must hold a Pallas kernel: interpret mode or a
    reference in its place would not."""
    text = jitted.lower(*args, **kwargs).compile().as_text()
    check("tpu_custom_call" in text, f"{what}: no tpu_custom_call in the "
          f"compiled program")
    say(f"[kernel] {what}: tpu_custom_call present")


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


# -- 1. device ----------------------------------------------------------------

def device_check(n_chips: int):
    devs = jax.devices()
    d0 = devs[0]
    say(f"[device] platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    check(d0.platform == "tpu", f"first device is {d0.platform!r}, not a TPU")
    check(len(devs) >= n_chips, f"needs {n_chips} chips, found {len(devs)}")
    return devs


# -- 2. kernels ---------------------------------------------------------------

def phase_kernels(cfg, seed: int) -> None:
    from repro.core.codecs import get_codec
    from repro.kernels.decode_attn import paged_decode_attn_ref
    from repro.kernels.decode_attn.ops import paged_decode_attention
    from repro.kernels.wagg import wagg_fused_ref
    from repro.kernels.wagg.ops import wagg_fused_leaf

    key = jax.random.key(seed)
    p, n = 2, cfg.d_model * cfg.d_ff          # one FFN leaf, two workers
    x = 0.02 * jax.random.normal(key, (p, n), jnp.float32)
    theta = jax.nn.softmax(jax.random.normal(jax.random.fold_in(key, 1),
                                             (p,)))
    beta = 0.9

    f32 = jax.jit(lambda x, t: wagg_fused_leaf(x, None, None, t, beta))
    require_kernel(f32, (x, theta), f"wagg f32 ({p}, {n})")
    with jax.default_matmul_precision("highest"):
        ref = wagg_fused_ref(x, theta, beta)
    err = float(jnp.abs(f32(x, theta) - ref).max())
    say(f"[kernel] wagg f32 max|kernel-ref|={err!r}")
    check(err <= 1e-5, f"wagg f32 differs from its reference by {err}")

    payload, aux = get_codec("int8").encode(x)
    active = jnp.asarray([True, False])
    i8 = jax.jit(lambda x, q, a, t, act: wagg_fused_leaf(x, q, a, t, beta,
                                                         active=act))
    require_kernel(i8, (x, payload, aux, theta, active),
                   f"wagg int8+mask ({p}, {n})")
    with jax.default_matmul_precision("highest"):
        ref = wagg_fused_ref(x, theta * jnp.float32(aux), beta,
                             payload=payload,
                             active=active.astype(jnp.float32))
    err = float(jnp.abs(i8(x, payload, aux, theta, active) - ref).max())
    say(f"[kernel] wagg int8+mask max|kernel-ref|={err!r}")
    check(err <= 1e-5, f"wagg int8+mask differs from its reference by {err}")

    b, kv, hd = 8, cfg.n_kv_heads, cfg.head_dim
    g = cfg.n_heads // kv
    bs, n_blk = SERVE_BLOCK, (PROMPT + NEW) // SERVE_BLOCK
    rng = np.random.default_rng(seed)
    n_pool = b * n_blk + 1
    q = jnp.asarray(rng.normal(size=(b, 1, kv * g, hd)), jnp.bfloat16)
    kp = jnp.asarray(rng.normal(size=(n_pool, bs, kv, hd)), jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=(n_pool, bs, kv, hd)), jnp.bfloat16)
    table = jnp.asarray(rng.permutation(b * n_blk).reshape(b, n_blk),
                        jnp.int32)
    index = jnp.asarray(rng.integers(0, bs * n_blk, size=b), jnp.int32)
    attn = jax.jit(paged_decode_attention)
    require_kernel(attn, (q, kp, vp, table, index),
                   f"paged decode kv={kv} hd={hd} bf16")
    out = attn(q, kp, vp, table, index).reshape(b, kv, g, hd)
    with jax.default_matmul_precision("highest"):
        ref = paged_decode_attn_ref(q.reshape(b, kv, g, hd), kp, vp, table,
                                    index)
    err = float(jnp.abs(out.astype(jnp.float32)
                        - ref.astype(jnp.float32)).max())
    say(f"[kernel] paged decode max|kernel-ref|={err!r}")
    check(err <= 3e-2, f"paged decode differs from its reference by {err}")


# -- 3. training ---------------------------------------------------------------

def lm_setup(cfg, p: int, seed: int):
    from repro.configs import TrainConfig, WASGDConfig
    from repro.data import make_tokens
    toks = make_tokens(seed, ROUNDS * p * TAU * B_LOCAL, SEQ,
                       cfg.vocab_size)
    per = p * TAU * B_LOCAL
    batches = [{"tokens": toks[r * per:(r + 1) * per, :-1],
                "labels": toks[r * per:(r + 1) * per, 1:]}
               for r in range(ROUNDS)]

    def tcfg(spec):
        return TrainConfig(learning_rate=LR, optimizer="sgd",
                           wasgd=WASGDConfig(tau=TAU, backend=spec))
    return batches, tcfg


def train(cfg, spec: str, p: int, seed: int, mesh=None):
    """Run ROUNDS fenced rounds; returns the trainer and its timings."""
    from repro.models import init_params
    from repro.train import Trainer
    from repro.train.lm import make_lm_loss
    batches, tcfg = lm_setup(cfg, p, seed)
    params, axes = init_params(cfg, jax.random.key(seed))
    trainer = Trainer(make_lm_loss(cfg), params, axes, tcfg(spec), p,
                      mesh=mesh)
    del params
    walls = []
    for batch in batches:
        t0 = time.perf_counter()
        trainer.run(iter([batch]), 1)
        jax.block_until_ready(trainer.state)
        walls.append(time.perf_counter() - t0)
    losses = trainer.losses()
    steady = float(np.median(walls[1:]))
    say(f"[train] {spec} p={p}: losses={losses.tolist()!r}")
    say(f"[train] {spec} p={p}: round 0 wall incl. compile={walls[0]!r} s, "
        f"compile~={walls[0] - steady!r} s, steady round walls="
        f"{walls[1:]!r} s (first reading, block_until_ready-fenced)")
    check(np.isfinite(losses).all(), f"{spec}: non-finite loss {losses}")
    return trainer


def compare_params(spec, trainer, ref_host, cfg, seed: int) -> None:
    """Max |params - reference| over every leaf, beside how far training
    moved the params from their (seeded) init."""
    from repro.models import init_params
    init, _ = init_params(cfg, jax.random.key(seed))
    err = move = 0.0
    for a, r, i in zip(jax.tree.leaves(trainer.state.params),
                       jax.tree.leaves(ref_host), jax.tree.leaves(init)):
        a = np.asarray(a)
        err = max(err, float(np.abs(a - r).max()))
        move = max(move, float(np.abs(r - np.asarray(i)[None]).max()))
    say(f"[train] {spec} vs einsum:f32 final params: max|diff|={err!r}, "
        f"max|einsum - init|={move!r}, ratio={err / max(move, 1e-30)!r} "
        f"(limit {PARAM_RTOL})")
    check(move >= MIN_MOVE, f"einsum:f32 moved the params by only {move} "
          f"< {MIN_MOVE}")
    check(err <= PARAM_RTOL * move, f"{spec} final params differ from "
          f"einsum:f32 by {err} > {PARAM_RTOL} x {move}")


def phase_train(cfg, seed: int):
    from repro.train.evaluate import consensus_params
    dev = jax.devices()[0]
    ref = train(cfg, "einsum:f32", 2, seed)
    ref_host = jax.tree.map(np.asarray, ref.state.params)
    say(f"[train] einsum:f32 peak_bytes_in_use={peak_bytes(dev)}")
    del ref
    trainer = train(cfg, "pallas_wagg:f32", 2, seed)
    say(f"[train] pallas_wagg:f32 peak_bytes_in_use={peak_bytes(dev)} "
        f"(process peak so far)")
    compare_params("pallas_wagg:f32", trainer, ref_host, cfg, seed)
    # the served copy, as HotSwapBridge extracts it: beta=1 equal
    # aggregation, then one worker's slice
    return consensus_params(trainer.state.params, trainer.axes)


# -- 4. serving ----------------------------------------------------------------

def phase_serve(cfg, served, seed: int) -> None:
    from repro.serve import ContinuousEngine
    engine = ContinuousEngine(cfg, served, n_slots=SERVE_SLOTS,
                              max_len=PROMPT + NEW, block_size=SERVE_BLOCK,
                              cache_dtype=jnp.bfloat16)
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size, size=(SERVE_SLOTS, PROMPT),
                           dtype=np.int32)
    t0 = time.perf_counter()
    out = engine.generate(prompts, NEW)
    wall = time.perf_counter() - t0
    done = engine.scheduler.finished
    check(len(done) == SERVE_SLOTS, f"{len(done)} of {SERVE_SLOTS} "
          f"requests finished")
    lengths = sorted(len(r.tokens) for r in done.values())
    check(lengths == [NEW] * SERVE_SLOTS, f"request lengths {lengths}")
    check(out.shape == (SERVE_SLOTS, NEW), f"output shape {out.shape}")
    say(f"[serve] {len(done)} requests finished, {NEW} tokens each; "
        f"generate wall incl. compile={wall!r} s (first reading)")
    require_kernel(engine._chunk, (engine.params, engine.cache.pools,
                                   engine.cache.tables, engine._st,
                                   jnp.asarray(False)),
                   "serve decode chunk", max_steps=engine.chunk)
    say(f"[serve] peak_bytes_in_use={peak_bytes(jax.devices()[0])} "
        f"(process peak)")


# -- four chips ----------------------------------------------------------------

def phase_four_chips(cfg, seed: int) -> None:
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(data=4, model=1)
    ref = train(cfg, "einsum:f32", 4, seed, mesh=mesh)
    ref_host = jax.tree.map(np.asarray, ref.state.params)
    del ref
    say("[mesh] einsum:f32 peak_bytes_in_use per device="
        f"{[peak_bytes(d) for d in jax.devices()]}")
    trainer = train(cfg, "rs_ag:f32", 4, seed, mesh=mesh)
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            trainer.state.params):
        rows = {s.device: s.index[0] for s in leaf.addressable_shards}
        starts = sorted((sl.start or 0) for sl in rows.values())
        check(len(rows) == 4 and starts == [0, 1, 2, 3],
              f"leaf {jax.tree_util.keystr(path)}: worker rows {starts} on "
              f"{len(rows)} devices")
    say("[mesh] every worker-stacked leaf: 4 worker rows on 4 distinct "
        "devices")
    compare_params("rs_ag:f32", trainer, ref_host, cfg, seed)
    for d in jax.devices():
        say(f"[mesh] device {d.id} peak_bytes_in_use={peak_bytes(d)} "
            f"(process peak)")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip Trainer(mesh=) path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    n_chips = 4 if args.four_chips else 1

    devs = device_check(n_chips)
    from repro.configs import get_config
    from repro.launch.cache import enable_compile_cache
    say(f"[cache] persistent compilation cache: {enable_compile_cache()}")
    cfg = dataclasses.replace(get_config(ARCH), n_layers=N_LAYERS)
    say(f"[config] {ARCH}: published widths (d_model={cfg.d_model}, "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads}x{cfg.head_dim}, "
        f"d_ff={cfg.d_ff}, vocab={cfg.vocab_size}); cut: n_layers 24 -> "
        f"{N_LAYERS}; seq {SEQ}, b_local {B_LOCAL}, tau {TAU}, "
        f"{ROUNDS} rounds, random weights from seed {args.seed}")

    if args.four_chips:
        phase_four_chips(cfg, args.seed)
    else:
        phase_kernels(cfg, args.seed)
        served = phase_train(cfg, args.seed)
        phase_serve(cfg, served, args.seed)

    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

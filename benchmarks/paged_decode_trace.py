#!/usr/bin/env python3
"""Device time of the paged decode-attention kernel against its jnp
reference, read from a profiler trace on a TPU.

    python benchmarks/paged_decode_trace.py [--kv N] [--block N] [--out DIR]

Shapes are stablelm-1.6b decode: 8 requests, 32 kv heads of 64 (one query
head each), bf16 pools of 16-token blocks, every request at a context of
160 tokens (the chip smoke's serving shape) and of 2048. For each context
it traces 20 calls of the kernel and 20 of the reference, each a jitted
program of its own name, and prints per call:

* the device time: the summed durations of the program's events on the
  TPU planes' ``XLA Modules`` line, over the calls;
* the host time of 20 fenced calls, over 20;
* the K/V bytes one call must read and the attention FLOPs it must do, and
  the roofline share: the larger of bytes over peak HBM bandwidth and FLOPs
  over peak compute, divided by the device time.

``--kv`` and ``--block`` change the kv-head count and the block size from
stablelm's 32 and the engine's 16, to see how the kernel's time scales
with them. ``--src DIR`` imports ``repro`` from another source tree (a
parent commit unpacked beside this one), so two kernels can be compared in
one run.
Exits nonzero, printing no result, where the first device is not a TPU.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

# Published peaks (Google Cloud documentation, "TPU v5e"), by device_kind.
PEAKS = {"TPU v5 lite": {"hbm_bytes_s": 819e9, "bf16_flop_s": 197e12}}

B, G, HD = 8, 1, 64
CONTEXTS = (160, 2048)
ITERS = 20


def device_times(xspace_path: str, names) -> dict:
    """Summed device nanoseconds of each jitted program in ``names``: its
    events (``jit_<name>(...)``) on the ``XLA Modules`` line of every TPU
    device plane of the trace."""
    from jax.profiler import ProfileData
    want = {f"jit_{n}": n for n in names}
    total = {n: 0 for n in names}
    for plane in ProfileData.from_file(xspace_path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            for ev in line.events:
                n = want.get(ev.name.split("(")[0])
                if n is not None:
                    total[n] += ev.duration_ns
    return total


def trace_outline(xspace_path: str) -> list:
    """Planes, their lines and a few event names: what to look at by hand
    when ``device_times`` finds nothing."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(xspace_path).planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            lines.append({"line": line.name, "n_events": len(evs),
                          "first": sorted({e.name for e in evs})[:8]})
        out.append({"plane": plane.name, "lines": lines})
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="chiprun_out/paged_decode_trace")
    ap.add_argument("--src", default=None,
                    help="source tree to import repro from")
    ap.add_argument("--tag", default="tree")
    ap.add_argument("--kv", type=int, default=32)
    ap.add_argument("--block", type=int, default=16)
    args = ap.parse_args()
    if any(c % args.block for c in CONTEXTS):
        ap.error(f"--block must divide the contexts {CONTEXTS}")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.abspath(args.src) if args.src
                    else os.path.join(root, "src"))

    import jax
    import jax.numpy as jnp
    import numpy as np
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"first device is {dev.platform!r}, not a TPU", file=sys.stderr)
        return 1
    if dev.device_kind not in PEAKS:
        print(f"no peaks recorded for {dev.device_kind!r}", file=sys.stderr)
        return 1
    peak = PEAKS[dev.device_kind]
    from repro.kernels.decode_attn.paged import (paged_decode_attn,
                                                 paged_decode_attn_ref)

    kv, bs = args.kv, args.block
    rng = np.random.default_rng(0)
    progs, inputs = {}, {}
    for ctx in CONTEXTS:
        n_blk = ctx // bs
        n_pool = B * n_blk + 1
        q = jnp.asarray(rng.normal(size=(B, kv, G, HD)), jnp.bfloat16)
        kp = jnp.asarray(rng.normal(size=(n_pool, bs, kv, HD)), jnp.bfloat16)
        vp = jnp.asarray(rng.normal(size=(n_pool, bs, kv, HD)), jnp.bfloat16)
        table = jnp.asarray(rng.permutation(B * n_blk).reshape(B, n_blk),
                            jnp.int32)
        index = jnp.full((B,), ctx - 1, jnp.int32)
        inputs[ctx] = (q, kp, vp, table, index)
        for kind, fn in (("kernel", paged_decode_attn),
                         ("ref", paged_decode_attn_ref)):
            def prog(q, kp, vp, table, index, fn=fn):
                return fn(q, kp, vp, table, index)
            prog.__name__ = prog.__qualname__ = f"paged_{kind}_ctx{ctx}"
            progs[(kind, ctx)] = jax.jit(prog)

    host = {}
    for (kind, ctx), f in progs.items():
        jax.block_until_ready(f(*inputs[ctx]))          # compile + warm
        t0 = time.perf_counter()
        for _ in range(ITERS):
            out = f(*inputs[ctx])
        jax.block_until_ready(out)
        host[(kind, ctx)] = (time.perf_counter() - t0) / ITERS

    trace_dir = os.path.join(args.out, args.tag)
    with jax.profiler.trace(trace_dir):
        for (kind, ctx), f in progs.items():
            for _ in range(ITERS):
                out = f(*inputs[ctx])
            jax.block_until_ready(out)
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    names = [f.__name__ for f in progs.values()]
    dev_ns = device_times(path, names)
    with open(os.path.join(args.out, f"{args.tag}_outline.json"), "w") as fh:
        json.dump(trace_outline(path), fh, indent=1)

    rows = []
    for (kind, ctx), f in progs.items():
        kv_bytes = 2 * B * ctx * kv * HD * 2
        flops = 4 * B * kv * G * ctx * HD
        dev_s = dev_ns[f.__name__] / 1e9 / ITERS
        bound = max(kv_bytes / peak["hbm_bytes_s"],
                    flops / peak["bf16_flop_s"])
        rows.append({
            "tag": args.tag, "prog": kind, "context": ctx, "batch": B,
            "kv_heads": kv, "head_dim": HD, "block": bs,
            "device_us_per_call": dev_s * 1e6 if dev_s else None,
            "host_us_per_call": host[(kind, ctx)] * 1e6,
            "kv_bytes": kv_bytes, "flops": flops,
            "roofline_share": bound / dev_s if dev_s else None,
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())}})
    for r in rows:
        print(json.dumps(r), flush=True)
    return 0 if all(r["device_us_per_call"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())

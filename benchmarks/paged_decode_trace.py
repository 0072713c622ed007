#!/usr/bin/env python3
"""Device time of the paged decode-attention kernel against its jnp
reference, read from a profiler trace on a TPU.

    python benchmarks/paged_decode_trace.py [--kv N] [--group N] [--hd N]
        [--block N] [--out DIR] [--src DIR --tag NAME]

Shapes default to stablelm-1.6b decode: 32 kv heads of 64, one query head
each, bf16 pools of 16-token blocks; ``--kv 4 --group 8 --hd 128`` is Yi-6B.
Three cases:

* ``ctx160``, ``ctx2048``: 8 requests, every one at a context of 160 tokens
  (the chip smoke's serving shape) and of 2048;
* ``mixed``: the serving cell's batch, 16 slots over a 4096-token table
  (256 blocks) of which 4 rows are live, at Azure-conversation-like
  contexts of 700, 1100, 1500 and 2500 tokens, and 12 inactive.

For each case it traces 20 calls of the kernel and 20 of the reference,
each a jitted program of its own name, and prints per call:

* the device time: the summed durations of the program's events on the
  TPU planes' ``XLA Modules`` line, over the calls;
* the host time of 20 fenced calls, over 20;
* the largest difference from the reference's output on the same inputs;
* the K/V bytes the live rows must read and the attention FLOPs they must
  do, and the roofline share: the larger of bytes over peak HBM bandwidth
  and FLOPs over peak compute, divided by the device time.

``--kv``, ``--group``, ``--hd`` and ``--block`` change the kv-head count,
the query heads per kv head, the head width and the block size. ``--src
DIR`` imports ``repro`` from another source tree (a parent commit unpacked
beside this one), so two kernels can be compared in one run; a kernel
without the ``active`` argument is given none, and walks the inactive rows.
Exits nonzero, printing no result, where the first device is not a TPU.
"""
from __future__ import annotations

import argparse
import glob
import inspect
import json
import os
import sys
import time

# Published peaks (Google Cloud documentation, "TPU v5e"), by device_kind.
PEAKS = {"TPU v5 lite": {"hbm_bytes_s": 819e9, "bf16_flop_s": 197e12}}

B = 8
CONTEXTS = (160, 2048)
MIXED_SLOTS, MIXED_WIDTH = 16, 4096
MIXED_LIVE = (700, 1100, 1500, 2500)
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


def cases():
    """``(name, contexts of the rows, table width in tokens)``; a context
    of 0 is an inactive row."""
    out = [(f"ctx{c}", (c,) * B, c) for c in CONTEXTS]
    live = MIXED_LIVE + (0,) * (MIXED_SLOTS - len(MIXED_LIVE))
    out.append(("mixed", live, MIXED_WIDTH))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="chiprun_out/paged_decode_trace")
    ap.add_argument("--src", default=None,
                    help="source tree to import repro from")
    ap.add_argument("--tag", default="tree")
    ap.add_argument("--kv", type=int, default=32)
    ap.add_argument("--group", type=int, default=1)
    ap.add_argument("--hd", type=int, default=64)
    ap.add_argument("--block", type=int, default=16)
    args = ap.parse_args()
    if any(w % args.block for _, _, w in cases()):
        ap.error(f"--block must divide the widths {CONTEXTS} and "
                 f"{MIXED_WIDTH}")
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

    kv, g, hd, bs = args.kv, args.group, args.hd, args.block
    rng = np.random.default_rng(0)
    progs, inputs, lives = {}, {}, {}
    for case, ctxs, width in cases():
        b, n_blk = len(ctxs), width // bs
        n_pool = b * n_blk + 1
        q = jnp.asarray(rng.normal(size=(b, kv, g, hd)), jnp.bfloat16)
        kp = jnp.asarray(rng.normal(size=(n_pool, bs, kv, hd)), jnp.bfloat16)
        vp = jnp.asarray(rng.normal(size=(n_pool, bs, kv, hd)), jnp.bfloat16)
        table = jnp.asarray(rng.permutation(b * n_blk).reshape(b, n_blk),
                            jnp.int32)
        index = jnp.asarray([max(c - 1, 0) for c in ctxs], jnp.int32)
        active = jnp.asarray([c > 0 for c in ctxs])
        inputs[case] = (q, kp, vp, table, index, active)
        lives[case] = ctxs
        for kind, fn in (("kernel", paged_decode_attn),
                         ("ref", paged_decode_attn_ref)):
            takes_active = "active" in inspect.signature(fn).parameters

            def prog(q, kp, vp, table, index, active, fn=fn,
                     takes_active=takes_active):
                if takes_active:
                    return fn(q, kp, vp, table, index, active)
                return fn(q, kp, vp, table, index)
            prog.__name__ = prog.__qualname__ = f"paged_{kind}_{case}"
            progs[(kind, case)] = jax.jit(prog)

    host, outs = {}, {}
    for (kind, case), f in progs.items():
        outs[(kind, case)] = jax.block_until_ready(f(*inputs[case]))
        t0 = time.perf_counter()
        for _ in range(ITERS):
            out = f(*inputs[case])
        jax.block_until_ready(out)
        host[(kind, case)] = (time.perf_counter() - t0) / ITERS

    trace_dir = os.path.join(args.out, args.tag)
    with jax.profiler.trace(trace_dir):
        for (kind, case), f in progs.items():
            for _ in range(ITERS):
                out = f(*inputs[case])
            jax.block_until_ready(out)
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    names = [f.__name__ for f in progs.values()]
    dev_ns = device_times(path, names)
    with open(os.path.join(args.out, f"{args.tag}_outline.json"), "w") as fh:
        json.dump(trace_outline(path), fh, indent=1)

    rows = []
    for (kind, case), f in progs.items():
        tokens = sum(lives[case])
        kv_bytes = 2 * tokens * kv * hd * 2
        flops = 4 * tokens * kv * g * hd
        dev_s = dev_ns[f.__name__] / 1e9 / ITERS
        bound = max(kv_bytes / peak["hbm_bytes_s"],
                    flops / peak["bf16_flop_s"])
        rows.append({
            "tag": args.tag, "prog": kind, "case": case,
            "contexts": list(lives[case]), "batch": len(lives[case]),
            "kv_heads": kv, "group": g, "head_dim": hd, "block": bs,
            "device_us_per_call": dev_s * 1e6 if dev_s else None,
            "host_us_per_call": host[(kind, case)] * 1e6,
            "kv_bytes": kv_bytes, "flops": flops,
            "max_abs_err_vs_ref": float(jnp.abs(
                outs[(kind, case)].astype(jnp.float32)
                - outs[("ref", case)].astype(jnp.float32)).max()),
            "roofline_share": bound / dev_s if dev_s else None,
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())}})
    for r in rows:
        print(json.dumps(r), flush=True)
    return 0 if all(r["device_us_per_call"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())

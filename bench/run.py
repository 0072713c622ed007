#!/usr/bin/env python3
"""Run one benchmark cell once, in this process, on the chips it asks for.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic and settings are found by name from
``BENCHMARK.json`` at the root of the checkout. The run makes its weights
and inputs from ``--seed``, warms up every shape the cell's traffic uses,
measures for ``--seconds``, checks what the timed path produced against
the plain reference, and prints one JSON object as the last line of
standard output. With ``--trace 0`` its metrics are the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler and the
metrics are the cell's per-layer metrics.

Exits with 2, printing no result, when the first device is not a TPU, when
its ``device_kind`` has no published peaks, or when there are fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, root: str = ROOT, require=None) -> int:
    """``require(n) -> devices`` replaces the look for chips (tests)."""
    args = parse(argv)
    for path in (os.path.join(root, "src"), root):
        if path not in sys.path:
            sys.path.insert(0, path)
    from bench import harness
    cell = harness.resolve(args.workload, root)
    try:
        devices = (require or harness.require_chips)(cell.chips)
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr, flush=True)
        return 2
    harness.CACHE = os.path.join(root, ".bench_cache")
    harness.enable_cache(os.path.join(harness.CACHE, "jax"))
    counter = harness.CompileCounter()
    out = cell.driver().run(cell, devices, args.seed, args.seconds,
                            bool(args.trace), T_START, counter)
    harness.log(f"run: {time.perf_counter() - T_START!r} s in all")
    harness.emit(out["result"], out["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())

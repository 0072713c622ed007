#!/usr/bin/env python3
"""Find a serving cell's knee: the highest offered rate at which the backlog
does not grow through the window. One engine, warmed once, then one
open-loop window per rate, each drained before the next. Every window
holds the same ``--requests`` requests (the same lengths, in another
order), so its length is ``requests / rate`` and one warm-up serves every
rate. The benchmark's own runs never run this; its result is written into
the traffic file as a fixed rate.

    python bench/sweep.py --workload <cell> --rates 3,4,5,6 [--requests 80]

Per rate it prints one JSON line: the requests queued (due, not yet given
a first token) at a third of the window and at its end, the requests
finished in the window, the tokens delivered per second, and time to first
token and time per output token at the median and the 90th percentile.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def queued(recs, t: float) -> int:
    return sum(1 for r in recs.values() if r.req.due_s <= t
               and (r.first_s is None or r.first_s > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--requests", type=int, default=80)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    for path in (os.path.join(ROOT, "src"), ROOT):
        sys.path.insert(0, path)
    from bench import generator, harness
    from bench.control import _Clock
    from bench.drivers import serve as S
    cell = harness.resolve(args.workload, ROOT)
    try:
        harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 2
    harness.enable_cache(os.path.join(ROOT, ".bench_cache", "jax"))
    vocab = cell.config["vocab_size"]

    def schedule(i, rate):
        seconds = args.requests / rate
        return seconds, generator.open_loop(
            args.seed + i, dict(cell.traffic, rate=rate), seconds, vocab)

    counter = harness.CompileCounter()
    t0 = time.perf_counter()
    engine = S.build(cell, args.seed)
    S.warm(cell, engine, schedule(0, 1.0)[1])
    print(json.dumps({"warm_s": time.perf_counter() - t0,
                      **counter.snapshot()}), flush=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        seconds, reqs = schedule(i, rate)
        recs, chunks, close = S.serve_window(engine, reqs, seconds,
                                             _Clock())
        done = [r for r in recs.values() if r.done_s is not None
                and r.done_s <= close]
        every = [r for r in recs.values() if r.done_s is not None]
        ttft = [(r.first_s - r.req.due_s) * 1e3 for r in every]
        tpot = [t for t in map(S.tpot_ms, every) if t is not None]
        delivered = sum(a - b for end, rows in chunks if end <= close
                        for _, b, a in rows)
        print(json.dumps({
            "rate": rate, "requests": len(reqs),
            "seconds": seconds,
            "queued_at_third": queued(recs, seconds / 3),
            "queued_at_end": queued(recs, close),
            "finished_in_window": len(done),
            "tokens_per_s": delivered / close,
            "ttft_p50_ms": S.pct(ttft, 50), "ttft_p90_ms": S.pct(ttft, 90),
            "tpot_p50_ms": S.pct(tpot, 50), "tpot_p90_ms": S.pct(tpot, 90),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

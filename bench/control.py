#!/usr/bin/env python3
"""Readings that set a cell's correctness limits: the program's, its
control's and its faults', on several seeds, in one process on the chips
the cell asks for. The benchmark's own runs never run this.

    python bench/control.py --workload <cell> --seeds 11,12,13 [--seconds 8]

Training cells, per seed: the program's first three rounds through
``Trainer.run`` (as a run's set-up drives them) and, after the program's
state is freed, the plain reference's; the control, which is the reference
with every matmul operand at fp8's 3 mantissa bits in the program's place;
and two faults planted in the reference put in the program's place: half
of every row's tokens left out (the mean taken over the rest), and Eq. 10's
exchange left out (beta 0). A state left unchanged reads 1 by the
comparison's measure and needs no run.

Serving cells: one engine, warmed once for the window's lengths; per seed, its weights swapped in
and a short open-loop window at the cell's own load, then the sample the
run checks: the served tokens' gaps under the reference, the gaps of the
tokens that the fp8 control puts first, and those of the served tokens
each altered by one id (a token altered where it is produced).

Each seed prints one JSON line of the numbers ``bench/compare.py`` holds to
the limits.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def train(cell, devices, seeds, _seconds):
    from bench import compare
    from bench.drivers import train as T
    seq = cell.traffic["seq"]
    for seed in seeds:
        rounds = T.batches(cell, seed)
        trainer, layout = T.build(cell, seed)
        prog = T.check_rounds(cell, trainer, layout, rounds, seed)
        T.free(trainer)
        del trainer
        ref = T.reference_rounds(cell, devices, seed, rounds)
        runs = {
            "program": prog,
            "control_fp8": T.reference_rounds(cell, devices, seed, rounds,
                                              "fp8"),
            "fault_half_batch": T.reference_rounds(cell, devices, seed,
                                                   rounds, seq=seq // 2),
            "fault_no_exchange": T.reference_rounds(cell, devices, seed,
                                                    rounds, beta=0.0),
        }
        out = {k: compare.train_numbers(v, ref) for k, v in runs.items()}
        print(json.dumps({"seed": seed, "reference_losses": ref["losses"],
                          **out}), flush=True)


class _Clock:
    def __init__(self):
        self.t0 = time.perf_counter()

    def close(self):
        return time.perf_counter() - self.t0


def serve(cell, devices, seeds, seconds):
    import jax.numpy as jnp
    import numpy as np
    from bench import generator, weights
    from bench.drivers import serve as S
    cfg = cell.config
    vocab = cfg["vocab_size"]
    engine = S.build(cell, seeds[0])
    # every seed's window holds the same lengths, in another order
    S.warm(cell, engine, generator.open_loop(seeds[0], cell.traffic,
                                             seconds, vocab))
    shapes, axes = cell.reference().layout(cfg)
    for seed in seeds:
        engine.swap_params(weights.make(
            shapes, axes, seed, dtype=jnp.dtype(cfg["precision"]["params"])))
        reqs = generator.open_loop(seed, cell.traffic, seconds, vocab)
        recs, _, _ = S.serve_window(engine, reqs, seconds, _Clock())
        picked = S.sample(recs, seed)
        engine.swap_params(None)
        both = S.reference_gaps(cell, seed, picked, control="fp8")
        for r in picked:
            r.tokens = [(t + 1) % vocab for t in r.tokens]
        altered = S.reference_gaps(cell, seed, picked)
        print(json.dumps({
            "seed": seed, "requests": len(picked),
            "tokens": int(sum(len(a) for a, _ in both)),
            "program": float(max(np.max(a) for a, _ in both)),
            "control_fp8": float(max(np.max(c) for _, c in both)),
            "fault_token_altered": float(max(np.max(a) for a in altered)),
        }), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    for path in (os.path.join(ROOT, "src"), ROOT):
        sys.path.insert(0, path)
    from bench import harness
    cell = harness.resolve(args.workload, ROOT)
    try:
        devices = harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 2
    harness.enable_cache(os.path.join(ROOT, ".bench_cache", "jax"))
    seeds = [int(s) for s in args.seeds.split(",")]
    {"train": train, "serve": serve}[cell.traffic["kind"]](
        cell, devices, seeds, args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())

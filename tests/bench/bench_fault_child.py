"""Child process of the fault tests: drives tiny runs of one cell, sound and
with each fault planted in the program underneath the timed path, then the
control readings, and prints ``RESULT <json>`` last.

    python bench_fault_child.py <root> <train|serve> <seed>
"""
from __future__ import annotations

import contextlib
import io
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(REPO, "src"), REPO]

import jax  # noqa: E402


def unchanged_state():
    import repro.train.trainer as trainer_mod
    orig = trainer_mod.build_train_step

    def build(*a, **k):
        step = orig(*a, **k)

        def same(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return same
    return trainer_mod, "build_train_step", build


def half_batch():
    import repro.train.lm as lm
    orig = lm.lm_loss

    def loss(cfg, params, batch):
        half = {k: v[:, :v.shape[1] // 2] for k, v in batch.items()}
        return orig(cfg, params, half)
    return lm, "lm_loss", loss


def no_exchange():
    import repro.core.backends as backends

    def aggregate(wcfg, params, axes, theta, **kw):
        return params
    return backends, "aggregate_from_config", aggregate


def token_altered():
    import repro.serve.engine as engine_mod
    orig = engine_mod.decode_step_paged

    def step(*a, **k):
        logits, pools = orig(*a, **k)
        top = logits.max(-1, keepdims=True)
        return logits.at[..., :1].set(top + 1.0), pools
    return engine_mod, "decode_step_paged", step


def cache_unwritten():
    import repro.serve.engine as engine_mod
    orig = engine_mod.decode_step_paged

    def step(cfg, params, tokens, pools, *a, **k):
        logits, _ = orig(cfg, params, tokens, pools, *a, **k)
        return logits, pools
    return engine_mod, "decode_step_paged", step


FAULTS = {"train": {"unchanged_state": unchanged_state,
                    "half_batch": half_batch, "no_exchange": no_exchange},
          "serve": {"token_altered": token_altered,
                    "cache_unwritten": cache_unwritten}}


def run_once(root: str, workload: str, seed: int) -> dict:
    from bench import run
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", "1", "--trace", "0"], root=root,
                      require=lambda n: jax.devices()[:n])
    assert rc == 0, rc
    return json.loads(out.getvalue().strip().splitlines()[-1])


def main() -> int:
    root, kind, seed = sys.argv[1], sys.argv[2], int(sys.argv[3])
    workload = f"tiny-{kind}"
    res = {"sound": run_once(root, workload, seed)["correct"]}
    for name, plant in FAULTS[kind].items():
        mod, attr, fake = plant()
        orig = getattr(mod, attr)
        setattr(mod, attr, fake)
        try:
            res[name] = run_once(root, workload, seed)["correct"]
        finally:
            setattr(mod, attr, orig)
    from bench import control, harness
    cell = harness.resolve(workload, root)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        getattr(control, kind)(cell, jax.devices()[:1], [seed], 1.0)
    res["readings"] = json.loads(out.getvalue().strip().splitlines()[-1])
    res["limits"] = cell.settings["limits"]
    print("RESULT " + json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

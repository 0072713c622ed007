"""Training cells: WASGD rounds through the program's ``Trainer``.

Set-up builds one ``Trainer`` (its state made from the run's seed, on a
mesh when the cell names one), then drives it through three rounds with
the window's own call, ``Trainer.run`` on one host batch, on rows that
all differ. The first of them compiles. After round 1 and round 3 it reads
each leaf's norm of the change from the initial weights: those readings
and the three rounds' losses are what the reference is held to. The same
object then runs rounds for the measured window, cycling through a pool of
distinct batches, and stops starting rounds once the window's seconds have
passed. ``train_tokens_per_s`` is the tokens of every round run over the
window's whole length, up to the fence on the last round.

Once the window has closed and the peak memory is read, the program's
state is freed and the plain reference runs the same three rounds from the
same seed.
"""
from __future__ import annotations

import gc
import math
import time
from typing import Dict, List

from bench import compare, flops, generator, peaks, weights
from bench import harness as H
from bench import trace as tr

N_CHECK = 3          # rounds the reference follows
N_POOL = 4           # distinct round batches the window cycles through


def wasgd_settings(cell) -> dict:
    s = cell.settings
    return {"tau": cell.traffic["tau"], "lr": s["lr"], "beta": s["beta"],
            "a_tilde": s["a_tilde"], "m_estimate": s["m_estimate"],
            "record_chunks": s["record_chunks"]}


def rows_per_round(cell) -> int:
    t = cell.traffic
    return t["tau"] * cell.settings["workers"] * t["b_local"]


def batches(cell, seed: int) -> List[dict]:
    return generator.train_rounds(seed, cell.traffic, rows_per_round(cell),
                                  N_CHECK + N_POOL,
                                  cell.config["vocab_size"])


def change_norms(stacked: Dict, shapes: Dict, axes: Dict, seed: int
                 ) -> Dict[str, float]:
    """Per leaf, the norm over every worker's copy of the change from the
    seed's initial weights (made again inside the jit, never kept)."""
    import jax
    import jax.numpy as jnp
    paths = weights.paths(shapes, axes)

    def f(stacked, key):
        out = {}
        for path in paths:
            x = stacked
            for k in path:
                x = x[k]
            x0 = weights.leaf_value(shapes, axes, key, path)
            d = x.astype(jnp.float32) - x0[None]
            out["/".join(path)] = jnp.sqrt(jnp.sum(d * d))
        return out

    out = jax.jit(f)(stacked, weights.seed_key(seed))
    return {k: float(v) for k, v in out.items()}


def build(cell, seed: int):
    """The program's ``Trainer`` for this cell, its state from the seed."""
    import jax  # noqa: F401
    from repro.configs import TrainConfig, WASGDConfig
    from repro.models import abstract_params
    from repro.train import Trainer
    from repro.train.lm import make_lm_loss
    cfg, s = cell.config, cell.settings
    mcfg = H.program_config(cfg)
    shapes, axes = abstract_params(mcfg)
    rshapes, raxes = cell.reference().layout(cfg)
    bad = H.same_layout(shapes, rshapes)
    if bad:
        raise RuntimeError(f"program and reference parameter layouts "
                           f"differ at {bad}")
    mesh = placed = None
    if s.get("mesh"):
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(data=s["mesh"][0], model=s["mesh"][1])
        placed = NamedSharding(mesh, PartitionSpec())
    with H.span("init"):
        params = weights.make(rshapes, raxes, seed, sharding=placed)
    w = wasgd_settings(cell)
    tcfg = TrainConfig(
        learning_rate=w["lr"], optimizer=s["optimizer"],
        wasgd=WASGDConfig(tau=w["tau"], beta=w["beta"], a_tilde=w["a_tilde"],
                          m_estimate=w["m_estimate"],
                          record_chunks=w["record_chunks"],
                          backend=s["spec"]))
    with H.span("build"):
        trainer = Trainer(make_lm_loss(mcfg), params, axes, tcfg,
                          s["workers"], mesh=mesh)
    del params
    return trainer, (rshapes, raxes)


def check_rounds(cell, trainer, layout, rounds: List[dict], seed: int
                 ) -> dict:
    """Drive the first rounds through ``Trainer.run`` and read what the
    reference is held to."""
    import jax
    lr = cell.settings["lr"]
    out = {"losses": []}
    for r, batch in enumerate(rounds[:N_CHECK]):
        with H.span("check_round"):
            trainer.run(iter([batch]), 1)
        out["losses"].append(float(trainer.history[-1]["loss"]))
        if r == 0:
            out["update"] = {k: v / lr for k, v in change_norms(
                trainer.state.params, *layout, seed).items()}
    jax.block_until_ready(trainer.state)
    out["change"] = change_norms(trainer.state.params, *layout, seed)
    return out


def reference_rounds(cell, devices, seed: int, rounds: List[dict],
                     precision: str = "f32", beta=None, seq=None) -> dict:
    """The plain reference's first rounds from the same seed and rows.
    ``beta`` and ``seq`` plant faults in it: no exchange (beta 0) and half
    of every row's tokens left out."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    cfg = cell.config
    ref = cell.reference()
    shapes, axes = ref.layout(cfg)
    p, tau = cell.settings["workers"], cell.traffic["tau"]
    w = wasgd_settings(cell)
    if beta is not None:
        w["beta"] = beta
    mesh = None
    if len(devices) > 1 and p % len(devices) == 0:
        mesh = Mesh(np.array(devices), ("w",))
        split = NamedSharding(mesh, P("w"))
    else:
        split = jax.sharding.SingleDeviceSharding(devices[0])
    x = weights.make(shapes, axes, seed, stack=p, sharding=split)
    step = ref.make_round(cfg, w, precision, mesh)
    out = {"losses": []}
    for r, batch in enumerate(rounds[:N_CHECK]):
        toks, labs = ref.worker_major(batch, p, tau)
        if seq is not None:
            toks, labs = toks[..., :seq], labs[..., :seq]
        x, losses = step(x, jax.device_put(toks, split),
                         jax.device_put(labs, split))
        out["losses"].append(float(np.asarray(losses).mean()))
        if r == 0:
            out["update"] = {k: v / w["lr"] for k, v in change_norms(
                x, shapes, axes, seed).items()}
    out["change"] = change_norms(x, shapes, axes, seed)
    return out


def free(trainer) -> None:
    trainer.state = None
    gc.collect()


def run(cell, devices, seed: int, seconds: float, traced: bool,
        t_start: float, counter) -> dict:
    import jax
    rounds = batches(cell, seed)
    trainer, layout = build(cell, seed)
    prog = check_rounds(cell, trainer, layout, rounds, seed)
    setup = counter.snapshot()
    setup_s = time.perf_counter() - t_start
    H.log(f"setup: {setup_s!r} s; programs {setup}; compiled "
          f"{dict(counter.names)}")

    pool = rounds[N_CHECK:]
    n = 0
    with H.Window(cell.name, traced) as win:
        while time.perf_counter() - win.t0 < seconds:
            with H.span("round"):
                trainer.run(iter([pool[n % len(pool)]]), 1)
            n += 1
        with H.span("fence"):
            jax.block_until_ready(trainer.state)
        win.close()
    window_compiles = counter.programs - setup["programs"]
    print(f"window_compiles={window_compiles}", flush=True)
    losses = [float(h["loss"]) for h in trainer.history]
    failed = sum(1 for v in losses[N_CHECK:] if not math.isfinite(v))
    device = H.device_block(devices)
    free(trainer)
    del trainer

    tokens = n * rows_per_round(cell) * cell.traffic["seq"]
    result = {"correct": False, "attempted": n, "failed": failed,
              "device": device,
              "setup": {**setup, "window_compiles": window_compiles}}
    if traced:
        per_token = flops.train_flops_per_token(cell.config,
                                                cell.traffic["seq"])
        inputs = H.LayerInputs(
            trace=win.trace, chips=cell.chips, config=cell.config,
            peaks=peaks.peaks_for(device["kind"]),
            counts={"rounds": n, "round_flops": per_token
                    * rows_per_round(cell) * cell.traffic["seq"]})
        result["metrics"] = H.per_layer(cell, inputs)
        result["device"].update(busy_s=tr.mean_busy_s(
            win.trace), window_s=win.trace.window_s)
        result["breakdown"] = H.breakdown(win.trace)
    else:
        result["metrics"] = {
            "train_tokens_per_s": {"value": tokens / win.seconds,
                                   "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}

    with H.span("reference"):
        ref = reference_rounds(cell, devices, seed, rounds)
    numbers = compare.train_numbers(prog, ref)
    chk = H.checks(numbers, cell.settings["limits"])
    H.log(f"program losses {prog['losses']!r}; reference {ref['losses']!r}")
    result["correct"] = H.all_within(chk) and failed == 0 and n > 0
    return {"result": result, "checks": chk}

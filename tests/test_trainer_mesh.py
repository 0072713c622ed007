"""Trainer(mesh=) on 4 forced host devices (subprocess, like
test_dryrun_small, so the device count never leaks into other tests).

The worker-stacked state lives on the mesh with one worker per device, the
round is jitted with pinned shardings and a donated state, and the explicit
collective schedules must land where the meshless reference lands.
"""
import json
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import functools, json
    import numpy as np, jax
    from repro.configs import TrainConfig, WASGDConfig
    from repro.data import OrderedDataset, make_classification
    from repro.launch.mesh import make_host_mesh
    from repro.models import cnn
    from repro.models.param import build
    from repro.train import Trainer

    assert len(jax.devices()) == 4
    X, y = make_classification(0, 256, d=8, n_classes=3)
    params, axes = build(functools.partial(
        cnn.mlp_init, d_in=8, d_hidden=16, n_classes=3), jax.random.key(0))

    def loss_fn(p, b):
        return cnn.classification_loss(cnn.mlp_apply(p, b["x"]), b["y"]), {}

    mesh = make_host_mesh(data=4, model=1)
    out = {}
    for spec in ("einsum:f32", "rs_ag:f32", "shard_map:f32"):
        tcfg = TrainConfig(learning_rate=0.05,
                           wasgd=WASGDConfig(tau=2, backend=spec))
        tr = Trainer(loss_fn, params, axes, tcfg, 4, mesh=mesh)
        tr.run(OrderedDataset({"x": X, "y": y}, 4, 2, 4, seed=3).batches(), 3)
        devs = {len({s.device for s in leaf.addressable_shards})
                for leaf in jax.tree.leaves(tr.state.params)}
        rows = {tuple(s.data.shape[0] for s in leaf.addressable_shards)
                for leaf in jax.tree.leaves(tr.state.params)}
        try:
            tr.resize(2)
            refused = False
        except NotImplementedError:
            refused = True
        out[spec] = {"params": jax.tree.map(np.asarray, tr.state.params),
                     "loss": tr.losses().tolist(), "devs": sorted(devs),
                     "rows": sorted(rows), "resize_refused": refused}
    ref = out["einsum:f32"]
    res = {}
    for spec, o in out.items():
        err = max(float(np.abs(a - b).max()) for a, b in zip(
            jax.tree.leaves(o["params"]), jax.tree.leaves(ref["params"])))
        res[spec] = {"err": err, "devs": o["devs"], "rows": o["rows"],
                     "finite": bool(np.isfinite(o["loss"]).all()),
                     "resize_refused": o["resize_refused"]}
    print("RESULT", json.dumps(res))
""")


def test_mesh_state_shardings_follow_the_logical_axes():
    """Params split by their logical axes, AdamW moments like the params
    they follow, everything else replicated — no guessing from shapes (the
    replicated leaves here all have a leading dim of p)."""
    import functools
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.configs import TrainConfig, WASGDConfig
    from repro.launch.mesh import make_host_mesh
    from repro.models import cnn
    from repro.models.param import build
    from repro.train import Trainer

    p = 2
    params, axes = build(functools.partial(
        cnn.mlp_init, d_in=8, d_hidden=16, n_classes=3), jax.random.key(0))
    tcfg = TrainConfig(learning_rate=0.05, optimizer="adamw",
                       wasgd=WASGDConfig(tau=2, policy="ema(0.5)|boltzmann"))
    tr = Trainer(lambda p_, b: (0.0, {}), params, axes, tcfg, p,
                 mesh=make_host_mesh(data=1, model=1))
    sh = tr._state_sh
    specs = [s.spec for s in jax.tree.leaves(sh.params)]
    assert specs and all(s[0] == "data" for s in specs)
    mu, nu, count = sh.opt_state
    assert jax.tree.leaves(mu) == jax.tree.leaves(sh.params)
    assert jax.tree.leaves(nu) == jax.tree.leaves(sh.params)
    assert count.spec == P()
    rest = [sh.step, sh.energy] + jax.tree.leaves(sh.comm_state)
    assert len(rest) > 2 and all(s.spec == P() for s in rest)
    assert all(x.shape[0] == p for x in jax.tree.leaves(tr.state.comm_state))


def test_trainer_on_4_device_mesh_matches_einsum():
    """rs_ag:f32 and shard_map:f32 through Trainer(mesh=) equal einsum:f32
    on the same mesh, and every params leaf holds one worker row on each of
    the 4 devices; resize refuses the mesh-placed state."""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT")][0]
    res = json.loads(line[len("RESULT "):])
    for spec, r in res.items():
        assert r["finite"], spec
        assert r["devs"] == [4], (spec, r["devs"])
        assert r["rows"] == [[1, 1, 1, 1]], (spec, r["rows"])
        assert r["err"] < 1e-5, (spec, r["err"])
        # resizing would gather the split state onto one device: refused
        assert r["resize_refused"], spec

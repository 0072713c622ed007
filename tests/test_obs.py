"""Observability subsystem (src/repro/obs + tools/obs_report.py).

Three guarantees under test:

* **NullSink no-op** — the default (telemetry off) path is byte-for-byte
  the un-instrumented trainer: bitwise-identical params, zero implicit
  host transfers (``jax.transfer_guard("disallow")``), zero added
  retraces of the fused step;
* **event fidelity** — every run mode (sync, async on_device, pipelined,
  elastic with async checkpoints, serving) emits its typed events, the
  JSONL round-trip preserves them, and the phased instrumented round
  produces the same params as the fused one;
* **reporter** — ``tools/obs_report.py`` summarizes a recorded run, and
  its ``--json`` output is pinned by a golden fixture.
"""
import functools
import json
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import TrainConfig, WASGDConfig
from repro.core import MembershipSchedule
from repro.data import (OrderedDataset, RoundPrefetcher, make_classification)
from repro.models import cnn
from repro.models.param import build
from repro.obs import (NULL, CheckpointSave, HotSwap, JsonlSink,
                       MembershipChange, NullSink, RingSink, RoundTrace,
                       ServeSample, Telemetry, WorkerAssessment,
                       event_from_record, read_events, to_record)
from repro.train import Trainer


def _problem(seed=0):
    X, y = make_classification(seed, 1024, d=16, n_classes=4)
    params, axes = build(functools.partial(
        cnn.mlp_init, d_in=16, d_hidden=32, n_classes=4),
        jax.random.key(seed))

    def loss_fn(p, b):
        return cnn.classification_loss(cnn.mlp_apply(p, b["x"]), b["y"]), {}

    return X, y, params, axes, loss_fn


def _ds(X, y, w=2, tau=2, bl=8, **kw):
    return OrderedDataset({"x": X, "y": y}, w, tau, bl, n_segments=1, **kw)


def _trees_equal(a, b):
    same = jax.tree.map(lambda x, y: bool(np.array_equal(np.asarray(x),
                                                         np.asarray(y))),
                        a, b)
    return all(jax.tree.leaves(same))


# ---------------------------------------------------------------------------
# Events + sinks
# ---------------------------------------------------------------------------

def test_event_record_round_trip():
    events = [
        RoundTrace(round=3, total_s=0.5, host_staging_s=0.01,
                   phases={"local_steps": 0.3, "reduce": 0.1},
                   detail="phased", p=4),
        WorkerAssessment(round=3, theta=[0.25, 0.75], energies=[1.0, 0.5],
                         theta_entropy=0.56, active=[True, False],
                         policy="boltzmann",
                         policy_state={"n_leaves": 2, "l2": 1.5}),
        ServeSample(chunk_s=0.1, steps=8, tokens=16, itl_s=0.0125,
                    n_running=2, queue_depth=1, admitted=2, finished=1,
                    blocks_free=10, blocks_total=16, occupancy=0.375,
                    ttft_s=[0.2], e2e_s=[1.1]),
        MembershipChange(round=2, old_p=2, new_p=3, generation=1),
        CheckpointSave(path="/tmp/ck", round=2, duration_s=0.05,
                       nbytes=1024),
        HotSwap(round=4, rounds_since_last=2, tokens_under_prev=64,
                param_drift_l2=0.7, in_flight=3),
    ]
    for e in events:
        rec = to_record(e)
        assert rec["kind"] == e.kind
        back = event_from_record(json.loads(json.dumps(rec)))
        assert type(back) is type(e)
        for k, v in rec.items():
            if k != "kind":
                assert getattr(back, k) == pytest.approx(v) \
                    if isinstance(v, float) else getattr(back, k) == v


def test_event_from_record_rejects_unknown_kind_drops_unknown_fields():
    with pytest.raises(ValueError, match="unknown"):
        event_from_record({"kind": "nope"})
    e = event_from_record({"kind": "membership_change", "round": 1,
                           "old_p": 2, "new_p": 4, "from_the_future": 9})
    assert (e.old_p, e.new_p) == (2, 4)
    assert not hasattr(e, "from_the_future")


def test_sinks_satisfy_protocol_and_ring_caps():
    assert isinstance(NULL, Telemetry)
    assert isinstance(NullSink(), Telemetry)
    ring = RingSink(maxlen=3)
    assert isinstance(ring, Telemetry)
    for r in range(5):
        ring.emit(MembershipChange(round=r, old_p=2, new_p=2))
    assert [e.round for e in ring.events()] == [2, 3, 4]
    assert not NULL.enabled and ring.enabled


def test_jsonl_sink_round_trip(tmp_path):
    path = str(tmp_path / "run.jsonl")
    sink = JsonlSink(path)
    sink.emit(RoundTrace(round=0, total_s=1.0, phases={"reduce": 0.5}))
    sink.emit(WorkerAssessment(round=0, theta=[1.0], energies=[2.0],
                               theta_entropy=0.0))
    sink.close()
    assert sink.n_emitted == 2
    evs = list(read_events(path))
    assert [e.kind for e in evs] == ["round_trace", "worker_assessment"]
    assert evs[0].phases == {"reduce": 0.5}
    assert evs[1].theta == [1.0]


def test_jsonl_sink_surfaces_writer_failure(tmp_path):
    path = str(tmp_path / "run.jsonl")
    sink = JsonlSink(path)
    sink._f.close()      # simulate the disk going away under the writer
    sink.emit(MembershipChange(round=0, old_p=1, new_p=2))
    with pytest.raises(RuntimeError, match="telemetry writer failed"):
        sink.close()


# ---------------------------------------------------------------------------
# NullSink no-op guarantee
# ---------------------------------------------------------------------------

def test_null_sink_path_is_bitwise_noop_and_transfer_clean():
    """telemetry=None and telemetry=NullSink() take the fused step with no
    added fences, no host transfers, no retraces — and identical params."""
    X, y, params, axes, loss_fn = _problem()
    tcfg = TrainConfig(learning_rate=0.05, wasgd=WASGDConfig(tau=2))

    tr0 = Trainer(loss_fn, params, axes, tcfg, 2)
    tr0.run(_ds(X, y).batches(), 4)

    tr1 = Trainer(loss_fn, params, axes, tcfg, 2)
    tr1.run(_ds(X, y).batches(), 4, telemetry=NullSink(),
            transfer_guard="disallow")

    assert _trees_equal(tr0.state.params, tr1.state.params)
    # one trace each: the NullSink run must not add a second signature
    assert tr0._step._cache_size() == 1
    assert tr1._step._cache_size() == 1
    # and no phased programs were built
    assert tr1._phased_cache == {}


def test_phased_instrumented_round_matches_fused_params():
    """With a real sink the round runs as separately-jitted phases; the
    result must still equal the fused step bitwise (same program split at
    phase boundaries)."""
    X, y, params, axes, loss_fn = _problem()
    tcfg = TrainConfig(learning_rate=0.05, wasgd=WASGDConfig(tau=2))

    tr0 = Trainer(loss_fn, params, axes, tcfg, 2)
    tr0.run(_ds(X, y).batches(), 4)

    sink = RingSink()
    tr1 = Trainer(loss_fn, params, axes, tcfg, 2)
    tr1.run(_ds(X, y).batches(), 4, telemetry=sink)

    assert _trees_equal(tr0.state.params, tr1.state.params)
    assert len(sink.by_kind("round_trace")) == 4


# ---------------------------------------------------------------------------
# Per-mode event emission
# ---------------------------------------------------------------------------

def test_sync_run_emits_phased_round_trace_and_assessment():
    X, y, params, axes, loss_fn = _problem()
    tcfg = TrainConfig(learning_rate=0.05, wasgd=WASGDConfig(tau=2))
    sink = RingSink()
    tr = Trainer(loss_fn, params, axes, tcfg, 2)
    tr.run(_ds(X, y).batches(), 3, telemetry=sink)

    traces = sink.by_kind("round_trace")
    assert len(traces) == 3
    for t in traces:
        assert t.detail == "phased" and t.p == 2
        assert set(t.phases) == {"local_steps", "judge", "reduce",
                                 "finalize"}
        assert all(v >= 0 for v in t.phases.values())
        assert t.total_s >= max(t.phases.values())
        assert t.host_staging_s >= 0
    wa = sink.by_kind("worker_assessment")
    assert len(wa) == 3
    for a in wa:
        assert len(a.theta) == 2 and len(a.energies) == 2
        assert a.theta == pytest.approx([sum(a.theta) - a.theta[1],
                                         a.theta[1]])
        assert sum(a.theta) == pytest.approx(1.0, abs=1e-5)
        assert a.policy == "boltzmann"
        assert a.active is None          # sync round: no Alg. 4 mask


def test_async_on_device_run_emits_active_mask():
    X, y, params, axes, loss_fn = _problem()
    tcfg = TrainConfig(learning_rate=0.05,
                       wasgd=WASGDConfig(tau=2, async_mode="on_device"))
    sink = RingSink()
    tr = Trainer(loss_fn, params, axes, tcfg, 3)
    tr.run(_ds(X, y, w=3).batches(), 3, telemetry=sink)

    wa = sink.by_kind("worker_assessment")
    assert len(wa) == 3
    for a in wa:
        assert a.active is not None and len(a.active) == 3
        assert all(isinstance(f, bool) for f in a.active)
    assert all(t.detail == "phased" for t in sink.by_kind("round_trace"))


def test_pipelined_run_emits_coarse_round_trace():
    X, y, params, axes, loss_fn = _problem()
    tcfg = TrainConfig(learning_rate=0.05, wasgd=WASGDConfig(tau=2))
    sink = RingSink()
    ds = _ds(X, y, boundary_delay=RoundPrefetcher.run_ahead())
    tr = Trainer(loss_fn, params, axes, tcfg, 2, pipeline="parity")
    tr.run(ds, 3, telemetry=sink)

    traces = sink.by_kind("round_trace")
    assert len(traces) == 3
    # the pipelined step is one fused program — whole-round timing only
    assert all(t.detail == "fused" and t.phases == {} for t in traces)
    assert len(sink.by_kind("worker_assessment")) == 3


def test_elastic_run_emits_membership_and_checkpoint_events(tmp_path):
    X, y, params, axes, loss_fn = _problem()
    tcfg = TrainConfig(learning_rate=0.05, wasgd=WASGDConfig(tau=2))
    sink = RingSink()
    tr = Trainer(loss_fn, params, axes, tcfg, 2)
    tr.run(_ds(X, y), 4, telemetry=sink,
           membership_schedule=MembershipSchedule(2, {2: 3}),
           checkpoint_every=2, checkpoint_path=str(tmp_path / "ck"))

    mc = sink.by_kind("membership_change")
    assert [(e.round, e.old_p, e.new_p) for e in mc] == [(2, 2, 3)]
    cs = sink.by_kind("checkpoint_save")
    assert len(cs) == 2
    for e in cs:
        assert e.duration_s > 0 and e.nbytes > 0
        assert os.path.isdir(e.path)
    # worker assessments follow the live worker count across the resize
    wa = sink.by_kind("worker_assessment")
    assert [len(a.theta) for a in wa] == [2, 2, 3, 3]


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def _serve_setup(telemetry=None):
    import dataclasses
    from repro.configs import get_smoke_config
    from repro.data import lm_batch
    from repro.models import init_params
    from repro.serve import ContinuousEngine
    cfg = dataclasses.replace(get_smoke_config("gemma3-1b"),
                              compute_dtype="float32")
    params, _ = init_params(cfg, jax.random.key(0))
    eng = ContinuousEngine(cfg, params, n_slots=2, max_len=64, block_size=8,
                           cache_dtype=jnp.float32, chunk=8,
                           telemetry=telemetry)
    prompts = np.asarray(lm_batch(0, 3, 8, cfg.vocab_size)["tokens"])
    return cfg, params, eng, prompts


def test_continuous_engine_emits_serve_samples_and_stays_bitwise():
    sink = RingSink()
    _, params, eng, prompts = _serve_setup(telemetry=sink)
    out = eng.generate(prompts, n_new=12)

    samples = sink.by_kind("serve_sample")
    assert samples, "no ServeSample emitted"
    total_tokens = sum(s.tokens for s in samples)
    assert total_tokens == eng.tokens_generated
    ttft = [t for s in samples for t in s.ttft_s]
    assert len(ttft) == 3 and all(t > 0 for t in ttft)
    # TTFT ends when the first tokens reach the host, in the admitting step
    assert sorted(ttft) == sorted(r.t_first - r.t_submit for r in
                                  eng.scheduler.finished.values())
    e2e = [t for s in samples for t in s.e2e_s]
    assert len(e2e) == 3 and all(t > 0 for t in e2e)
    for s in samples:
        assert s.steps >= 1 and s.itl_s == pytest.approx(s.chunk_s / s.steps)
        assert 0.0 <= s.occupancy <= 1.0
        assert s.blocks_free + round(s.occupancy * s.blocks_total) \
            == s.blocks_total

    # telemetry must not perturb decoding
    _, _, eng2, _ = _serve_setup()
    np.testing.assert_array_equal(out, eng2.generate(prompts, n_new=12))


def test_span_records_name_and_ids_only_under_the_profiler(tmp_path):
    import glob
    import warnings
    from jax.profiler import ProfileData, TraceAnnotation
    from repro.obs import span
    assert isinstance(span("serve.step"), TraceAnnotation)
    with span("serve.outside", rid=7):           # usable with no session
        pass
    with jax.profiler.trace(str(tmp_path)):
        with span("serve.step"):
            with span("serve.scatter", rid=3, slot=1):
                pass
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    with warnings.catch_warnings():   # jaxlib's stats type lacks __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        got = {ev.name: dict(ev.stats)
               for plane in ProfileData.from_file(path).planes
               if plane.name.startswith("/host:")
               for line in plane.lines for ev in line.events
               if ev.name.startswith("serve.")}
    assert got == {"serve.step": {}, "serve.scatter": {"rid": 3, "slot": 1}}


def test_hot_swap_bridge_emits_hot_swap_event():
    from repro.serve import HotSwapBridge
    sink = RingSink()
    _, params, eng, prompts = _serve_setup(telemetry=sink)
    bridge = HotSwapBridge(eng)          # inherits the engine's sink
    eng.generate(prompts, n_new=4)       # tokens served under the old params
    stacked = jax.tree.map(lambda x: jnp.stack([x, x]), params)
    axes = jax.tree.map(lambda x: ("worker",) + (None,) * x.ndim, params)
    bridge(5, stacked, axes)
    bridge(9, stacked, axes)
    hs = sink.by_kind("hot_swap")
    assert [(e.round, e.rounds_since_last) for e in hs] == [(5, None),
                                                           (9, 4)]
    assert hs[0].tokens_under_prev == eng.tokens_generated
    assert hs[1].tokens_under_prev == 0
    assert hs[1].param_drift_l2 == 0.0


# ---------------------------------------------------------------------------
# Reporter
# ---------------------------------------------------------------------------

def _report_main(argv, capsys):
    from tools.obs_report import main
    rc = main(argv)
    return rc, capsys.readouterr().out


def test_obs_report_renders_recorded_run(tmp_path, capsys):
    X, y, params, axes, loss_fn = _problem()
    path = str(tmp_path / "run.jsonl")
    sink = JsonlSink(path)
    tcfg = TrainConfig(learning_rate=0.05, wasgd=WASGDConfig(tau=2))
    tr = Trainer(loss_fn, params, axes, tcfg, 2)
    tr.run(_ds(X, y).batches(), 3, telemetry=sink)
    sink.close()

    rc, out = _report_main([path], capsys)
    assert rc == 0
    for needle in ("rounds: 3", "local_steps", "judge", "reduce",
                   "finalize", "theta entropy", "policy=boltzmann"):
        assert needle in out, needle


def test_obs_report_json_golden(tmp_path, capsys):
    """A hand-written run pins the --json summary shape and arithmetic."""
    path = str(tmp_path / "golden.jsonl")
    sink = JsonlSink(path)
    for r in range(2):
        sink.emit(RoundTrace(round=r, total_s=0.4 + 0.2 * r,
                             host_staging_s=0.01,
                             phases={"local_steps": 0.2, "reduce": 0.1},
                             detail="phased", p=2))
        sink.emit(WorkerAssessment(round=r, theta=[0.5 + 0.2 * r,
                                                   0.5 - 0.2 * r],
                                   energies=[1.0, 2.0],
                                   theta_entropy=0.69 - 0.2 * r,
                                   policy="boltzmann"))
    sink.emit(ServeSample(chunk_s=0.2, steps=8, tokens=16, itl_s=0.025,
                          n_running=2, queue_depth=0, admitted=2,
                          finished=2, blocks_free=8, blocks_total=16,
                          occupancy=0.5, ttft_s=[0.1, 0.3],
                          e2e_s=[1.0, 2.0]))
    sink.emit(MembershipChange(round=1, old_p=2, new_p=4))
    sink.emit(CheckpointSave(path="/tmp/ck", round=1, duration_s=0.5,
                             nbytes=2048))
    sink.emit(HotSwap(round=1, rounds_since_last=None, tokens_under_prev=16,
                      param_drift_l2=0.25, in_flight=1))
    sink.close()

    rc, out = _report_main([path, "--json"], capsys)
    assert rc == 0
    s = json.loads(out)
    assert s["n_events"] == 8
    assert s["rounds"]["n"] == 2
    assert s["rounds"]["detail"] == ["phased"]
    assert s["rounds"]["total_s"]["mean"] == pytest.approx(0.5)
    assert s["rounds"]["phases"]["local_steps"]["p50"] == pytest.approx(0.2)
    assert s["assessment"]["theta_entropy"] == {
        "first": pytest.approx(0.69), "last": pytest.approx(0.49),
        "min": pytest.approx(0.49), "max": pytest.approx(0.69)}
    assert s["assessment"]["top_worker_share"]["mean"] == pytest.approx(0.6)
    assert s["serve"]["tokens"] == 16
    assert s["serve"]["tokens_per_s"] == pytest.approx(80.0)
    assert s["serve"]["ttft_s"]["p50"] == pytest.approx(0.2)
    assert s["membership"] == [{"round": 1, "old_p": 2, "new_p": 4}]
    assert s["checkpoints"]["total_bytes"] == 2048
    assert s["hot_swaps"]["n"] == 1
    assert s["hot_swaps"]["mean_rounds_since_last"] is None


def test_obs_report_empty_file_fails(tmp_path, capsys):
    path = str(tmp_path / "empty.jsonl")
    open(path, "w").close()
    rc, _ = _report_main([path], capsys)
    assert rc == 1

"""Continuous-batching serve stack: greedy parity under scheduling, paged
cache recycling, RNG schedule-independence, and the train-to-serve hot-swap.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import TrainConfig, WASGDConfig, get_smoke_config
from repro.data import lm_batch
from repro.models import init_params
from repro.serve import ContinuousEngine, HotSwapBridge, ServeEngine
from repro.train import Trainer
from repro.train.lm import make_lm_loss

# exact parity needs row-independent per-token compute: MoE capacity
# dispatch ranks tokens across the batch, so MoE archs are excluded.
PARITY_ARCHS = ["yi-6b", "gemma3-1b", "mamba2-370m"]


def _f32(cfg):
    return dataclasses.replace(cfg, compute_dtype="float32")


def _setup(arch, seed=0):
    cfg = _f32(get_smoke_config(arch))
    params, _ = init_params(cfg, jax.random.key(seed))
    return cfg, params


@pytest.mark.parametrize("arch", PARITY_ARCHS)
def test_continuous_greedy_parity_vs_solo(arch):
    """Batched continuous decode == legacy solo generate, token for token."""
    cfg, params = _setup(arch)
    prompts = np.asarray(lm_batch(0, 3, 8, cfg.vocab_size)["tokens"])
    legacy = ServeEngine(cfg, params, max_len=64, cache_dtype=jnp.float32)
    eng = ContinuousEngine(cfg, params, n_slots=4, max_len=64, block_size=8,
                           cache_dtype=jnp.float32, chunk=16)
    out = eng.generate(prompts, n_new=12)
    for i in range(3):
        solo = np.asarray(legacy.generate(prompts[i:i + 1], n_new=12))[0]
        np.testing.assert_array_equal(out[i], solo)


def test_greedy_parity_under_insert_evict():
    """More requests than slots with staggered lengths: requests finish
    mid-flight, slots/blocks recycle, later requests are inserted next to
    running ones — and every request still matches its solo decode. The
    longest request decodes far past gemma3's window, so ring wraparound is
    exercised under scheduling too."""
    cfg, params = _setup("gemma3-1b", seed=1)
    legacy = ServeEngine(cfg, params, max_len=64, cache_dtype=jnp.float32)
    eng = ContinuousEngine(cfg, params, n_slots=2, max_len=64, block_size=8,
                           cache_dtype=jnp.float32, chunk=8)
    prompts = np.asarray(lm_batch(5, 5, 8, cfg.vocab_size)["tokens"])
    n_news = [3, 30, 7, 14, 1]
    rids = [eng.submit(prompts[i], n_news[i], seed=i) for i in range(5)]
    done = eng.run()
    for i, rid in enumerate(rids):
        solo = np.asarray(legacy.generate(prompts[i:i + 1], n_news[i]))[0]
        np.testing.assert_array_equal(done[rid], solo)
    # everything was recycled on the way out
    assert eng.scheduler.idle
    assert eng.cache.free_blocks() == eng.cache._group_phys["full"]
    assert eng.n_running == 0


def test_sampled_decode_is_schedule_independent():
    """temperature > 0: the token at position p is keyed by
    fold_in(fold_in(engine_key, seed), p) — a request samples identically
    whether it runs alone or shares the batch with other requests."""
    cfg, params = _setup("yi-6b", seed=2)
    prompt = np.asarray(lm_batch(2, 1, 6, cfg.vocab_size)["tokens"])[0]

    solo = ContinuousEngine(cfg, params, n_slots=2, max_len=32, block_size=8,
                            cache_dtype=jnp.float32, chunk=8, seed=7)
    rid = solo.submit(prompt, 10, temperature=0.8, seed=3)
    a = solo.run()[rid]

    busy = ContinuousEngine(cfg, params, n_slots=2, max_len=32, block_size=8,
                            cache_dtype=jnp.float32, chunk=8, seed=7)
    other = np.asarray(lm_batch(9, 3, 6, cfg.vocab_size)["tokens"])
    rids = [busy.submit(other[i], 4 + 3 * i, temperature=0.5, seed=20 + i)
            for i in range(3)]
    rid_b = busy.submit(prompt, 10, temperature=0.8, seed=3)
    b = busy.run()[rid_b]
    np.testing.assert_array_equal(a, b)


def test_moe_arch_serves_continuously():
    """MoE/hybrid archs run on the paged engine (no exact-parity guarantee,
    but decode must work: attention + SSM caches both paged)."""
    cfg, params = _setup("jamba-v0.1-52b", seed=3)
    eng = ContinuousEngine(cfg, params, n_slots=2, max_len=32, block_size=8,
                           cache_dtype=jnp.float32, chunk=4)
    out = eng.generate(
        np.asarray(lm_batch(4, 2, 6, cfg.vocab_size)["tokens"]), n_new=5)
    assert out.shape == (2, 5)
    assert (out >= 0).all() and (out < cfg.padded_vocab).all()


def test_unsupported_archs_raise():
    for arch in ["llama-3.2-vision-11b", "musicgen-large"]:
        cfg, params = _setup(arch, seed=4)
        with pytest.raises(NotImplementedError):
            ContinuousEngine(cfg, params, n_slots=1, max_len=32)


def test_continuous_eos_parity_and_recycling():
    """A stop token finishes a request early via the in-loop done-flags:
    its tokens match the legacy engine's (truncated at the first stop
    token), and its slot + blocks recycle to the waiting queue."""
    cfg, params = _setup("yi-6b", seed=9)
    legacy = ServeEngine(cfg, params, max_len=64, cache_dtype=jnp.float32)
    prompts = np.asarray(lm_batch(11, 3, 8, cfg.vocab_size)["tokens"])
    base = np.asarray(legacy.generate(prompts[0:1], 16))[0]
    eos = int(base[5])
    j = list(base).index(eos)

    eng = ContinuousEngine(cfg, params, n_slots=2, max_len=64, block_size=8,
                           cache_dtype=jnp.float32, chunk=4, eos_id=eos)
    rids = [eng.submit(p, 16, seed=i) for i, p in enumerate(prompts)]
    done = eng.run()
    got = done[rids[0]]
    assert len(got) == j + 1 and got[-1] == eos
    np.testing.assert_array_equal(got, base[:j + 1])
    for rid in rids[1:]:                 # others ran to budget or their eos
        toks = done[rid]
        assert len(toks) == 16 or toks[-1] == eos
    assert eng.scheduler.idle and eng.n_running == 0
    assert eng.cache.free_blocks() == eng.cache._group_phys["full"]


def test_budget_validation():
    cfg, params = _setup("yi-6b", seed=5)
    eng = ContinuousEngine(cfg, params, n_slots=1, max_len=32, block_size=8)
    prompt = np.zeros((30,), np.int32)
    with pytest.raises(ValueError, match="exceeds the cache budget"):
        eng.submit(prompt, n_new=3)
    small = ContinuousEngine(cfg, params, n_slots=2, max_len=32,
                             block_size=8, full_blocks=2)
    with pytest.raises(ValueError, match="cache blocks"):
        small.submit(np.zeros((20,), np.int32), n_new=4)


def test_constrained_blocks_queue_and_complete():
    """A block budget that fits only one request at a time still drains the
    queue correctly — admission waits on the free list."""
    cfg, params = _setup("yi-6b", seed=6)
    legacy = ServeEngine(cfg, params, max_len=32, cache_dtype=jnp.float32)
    eng = ContinuousEngine(cfg, params, n_slots=4, max_len=32, block_size=8,
                           cache_dtype=jnp.float32, chunk=8, full_blocks=2)
    prompts = np.asarray(lm_batch(6, 3, 8, cfg.vocab_size)["tokens"])
    rids = [eng.submit(p, 6) for p in prompts]
    done = eng.run()
    for i, rid in enumerate(rids):
        solo = np.asarray(legacy.generate(prompts[i:i + 1], 6))[0]
        np.testing.assert_array_equal(done[rid], solo)
    assert eng.cache.free_blocks() == 2


def test_hot_swap_keeps_in_flight_requests_alive():
    """Trainer.run(serve_hook=) swaps the beta=1 consensus into a live
    engine mid-generation: the in-flight request survives every swap,
    finishes its full budget, and the bridge records per-swap staleness."""
    cfg, params_axes = None, None
    cfg = _f32(get_smoke_config("stablelm-1.6b"))
    params, axes = init_params(cfg, jax.random.key(7))
    tcfg = TrainConfig(learning_rate=0.05, optimizer="sgd",
                       wasgd=WASGDConfig(tau=2, beta=0.9))
    tr = Trainer(make_lm_loss(cfg), params, axes, tcfg, 2)

    eng = ContinuousEngine(cfg, params, n_slots=2, max_len=64, block_size=8,
                           cache_dtype=jnp.float32, chunk=4)
    bridge = HotSwapBridge(eng)
    prompt = np.asarray(lm_batch(7, 1, 8, cfg.vocab_size)["tokens"])[0]
    rid = eng.submit(prompt, n_new=40)
    eng.step()
    assert eng.n_running == 1

    def hook(r, p, a):
        eng.step()                       # serve between training rounds
        bridge(r, p, a)

    def batches():
        r = 0
        while True:
            yield {k: jnp.asarray(v) for k, v in
                   lm_batch(r, 4, 16, cfg.vocab_size).items()}
            r += 1

    tr.run(batches(), 4, serve_hook=hook, serve_every=2)
    done = eng.run()
    assert len(done[rid]) == 40          # request survived both swaps
    assert eng.n_swaps == 2
    assert len(bridge.swaps) == 2
    first, second = bridge.swaps
    assert first["in_flight"] == 1 and second["in_flight"] == 1
    assert first["rounds_since_last"] is None
    assert second["rounds_since_last"] == 2
    assert second["param_drift_l2"] > 0
    assert second["tokens_under_prev"] > 0


def test_swap_params_identity_under_same_params():
    """Swapping in the same params mid-flight is a strict no-op on output:
    generate with a swap between chunks == generate without."""
    cfg, params = _setup("gemma3-1b", seed=8)
    prompt = np.asarray(lm_batch(8, 1, 8, cfg.vocab_size)["tokens"])[0]

    plain = ContinuousEngine(cfg, params, n_slots=1, max_len=64,
                             block_size=8, cache_dtype=jnp.float32, chunk=4)
    rid = plain.submit(prompt, 20)
    a = plain.run()[rid]

    swapped = ContinuousEngine(cfg, params, n_slots=1, max_len=64,
                               block_size=8, cache_dtype=jnp.float32,
                               chunk=4)
    rid = swapped.submit(prompt, 20)
    swapped.step()
    swapped.swap_params(jax.tree.map(jnp.copy, params))
    b = swapped.run()[rid]
    np.testing.assert_array_equal(a, b)
    assert swapped.n_swaps == 1


# ---------------------------------------------------------------------------
# Spans and per-request stamps
# ---------------------------------------------------------------------------

def _queued_run(eng, cfg):
    """Five requests of three prompt lengths through two slots, so later
    requests queue and some steps admit a prefill group of two."""
    prompts = np.asarray(lm_batch(12, 5, 12, cfg.vocab_size)["tokens"])
    lens, n_news = [8, 8, 12, 5, 12], [6, 9, 3, 7, 5]
    rids = [eng.submit(prompts[i][:lens[i]], n_news[i], seed=i)
            for i in range(5)]
    done = eng.run()
    return rids, [done[r] for r in rids]


def _serve_events(trace_dir):
    """``(start, end, name, args)`` of every ``serve.*`` host event of the
    one trace written under ``trace_dir``."""
    import glob
    import warnings
    from jax.profiler import ProfileData
    path, = glob.glob(str(trace_dir / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    out = []
    with warnings.catch_warnings():   # jaxlib's stats type lacks __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith("serve."):
                            out.append((ev.start_ns,
                                        ev.start_ns + ev.duration_ns,
                                        ev.name, dict(ev.stats)))
    return path, sorted(out, key=lambda e: (e[0], -e[1]))


def test_profiled_step_spans_nest_and_name_the_requests(tmp_path):
    """Under the profiler each ``serve.step`` holds one ``serve.readback``,
    prefill groups and scatters sit inside ``serve.admit``, the scatters'
    ``rid`` args are the admitted requests, and the greedy tokens are those
    of a run with the profiler off. The benchmark's trace reduction keeps
    only its own ``bench.`` spans, so its idle labels do not change."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import trace as bench_trace

    cfg, params = _setup("yi-6b", seed=12)

    def engine():
        return ContinuousEngine(cfg, params, n_slots=2, max_len=64,
                                block_size=8, cache_dtype=jnp.float32,
                                chunk=4)

    _, plain = _queued_run(engine(), cfg)
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            rids, traced = _queued_run(engine(), cfg)
    for a, b in zip(plain, traced):
        np.testing.assert_array_equal(a, b)

    path, ev = _serve_events(tmp_path)

    def named(n):
        return [e for e in ev if e[2] == n]

    def inside(e, outer):
        return outer[0] <= e[0] and e[1] <= outer[1]

    steps, admits = named("serve.step"), named("serve.admit")
    assert steps and len(admits) == len(steps)
    for name in ("serve.readback", "serve.tables", "serve.dispatch",
                 "serve.collect"):
        for s in steps:
            assert sum(inside(e, s) for e in named(name)) == 1, name
    prefills, scatters = named("serve.prefill"), named("serve.scatter")
    for e in prefills + scatters:
        assert any(inside(e, a) for a in admits)
    assert sorted(e[3]["rid"] for e in scatters) == sorted(rids)
    assert sum(e[3]["k"] for e in prefills) == len(rids)
    assert any(e[3]["k"] == 2 for e in prefills)
    assert {e[3]["n_prompt"] for e in prefills} == {5, 8, 12}

    spans = bench_trace.load(path).spans
    assert [n for _, _, n in spans] == ["bench.window"]


def test_tables_span_carries_width_and_live_blocks(tmp_path):
    """Each ``serve.tables`` span carries the full-attention table width
    the chunk sees and the blocks the running rows reserved, which never
    exceed the slots times that width; the queued run leaves part of the
    width unreserved, the part the paged kernel does not walk."""
    cfg, params = _setup("yi-6b", seed=14)
    eng = ContinuousEngine(cfg, params, n_slots=2, max_len=64, block_size=8,
                           cache_dtype=jnp.float32, chunk=4)
    with jax.profiler.trace(str(tmp_path)):
        _queued_run(eng, cfg)
    _, ev = _serve_events(tmp_path)
    tables = [e[3] for e in ev if e[2] == "serve.tables"]
    assert tables
    n_blk = eng.cache.layout["groups"]["full"]["n_blk"]
    for args in tables:
        assert 0 < args["width"] <= n_blk
        assert 0 < args["live"] <= 2 * args["width"]
    assert any(a["live"] < 2 * a["width"] for a in tables)


def test_request_stamps_are_ordered():
    """Every finished request was stamped at each boundary, in order:
    submitted, bound to a slot, its admission dispatched, its first
    tokens read back."""
    cfg, params = _setup("yi-6b", seed=13)
    eng = ContinuousEngine(cfg, params, n_slots=2, max_len=64, block_size=8,
                           cache_dtype=jnp.float32, chunk=4)
    rids, _ = _queued_run(eng, cfg)
    reqs = [eng.scheduler.finished[r] for r in rids]
    for r in reqs:
        assert r.t_submit <= r.t_admit <= r.t_admitted <= r.t_first
    # the two slots make the last requests wait for a free one
    assert max(r.t_admit - r.t_submit for r in reqs) \
        > min(r.t_admit - r.t_submit for r in reqs)

"""Serving cells: open-loop traffic through the program's
``ContinuousEngine``.

Set-up makes the weights from the seed, builds one engine, and warms every
shape the run's requests can reach by driving the engine's own calls. The
engine prefills the requests it admits together in one batch per prompt
length, so for each prompt length of the run, a group of ``k`` requests
for every ``k`` up to the number of the run's requests of that length
(and the engine's slots): one batched prefill, one scatter per request
and one decode chunk. The run's prompts all differ in length (see
``bench/generator.py``), so that is one group of one per length. Then one
decode chunk for every width of block table that a request of the run can
make the engine use: the widths are read from the engine's own cache
(``reserve``/``used_width``/``release``) for each request's budget of
prompt plus output; to run a chunk at a width, a spare slot holds a
reservation of that budget while one short request decodes.

The window submits each request when it is due (``generator.open_loop``)
and otherwise steps the engine; when nothing is queued or running it sleeps
until the next request is due. A request's first token reaches the host at
the end of the chunk after its admission, and its last at the end of the
chunk in which it finishes; both are read after ``step()`` returns. Time to
first token counts from when the request was due. The first readback
brings the prefill's token and those the chunk decoded after it, so time
per output token counts only the tokens that came after the first
readback: (last readback - first readback) / those tokens, over the
requests that got any. After the window closes,
the run keeps stepping, with no new arrivals, until every request due in
the window has finished, for at most ``DRAIN_S`` seconds; one that never
finishes is failed.

The reference then checks a sample, drawn from the seed, of the finished
greedy requests, the longest among them: the widest gap by which a served
token's logit lies below the reference's best.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import time
from typing import Dict, List, Optional

import numpy as np

from bench import compare, generator, peaks, weights
from bench import harness as H
from bench import trace as tr

SAMPLE = 6           # greedy requests the reference checks
DRAIN_S = 60.0       # how long past the close the run waits for answers


@dataclasses.dataclass
class Record:
    req: generator.Req
    rid: int
    submit_s: float
    first_s: Optional[float] = None
    done_s: Optional[float] = None
    tokens: Optional[List[int]] = None
    n_seen: int = 0
    n_first: int = 0      # tokens that came with the first readback


def build(cell, seed: int):
    import jax.numpy as jnp
    from repro.models import abstract_params
    from repro.serve import ContinuousEngine
    cfg, s = cell.config, cell.settings
    mcfg = H.program_config(cfg)
    shapes, _ = abstract_params(mcfg)
    rshapes, raxes = cell.reference().layout(cfg)
    bad = H.same_layout(shapes, rshapes)
    if bad:
        raise RuntimeError(f"program and reference parameter layouts "
                           f"differ at {bad}")
    with H.span("init"):
        params = weights.make(rshapes, raxes, seed,
                              dtype=jnp.dtype(cfg["precision"]["params"]))
    engine = ContinuousEngine(
        mcfg, params, n_slots=s["slots"], max_len=s["max_len"],
        block_size=s["block_size"],
        cache_dtype=jnp.dtype(cfg["precision"]["kv_cache"]),
        chunk=s["chunk"], seed=seed % (2 ** 31))
    return engine


def _drain(engine) -> None:
    while not engine.scheduler.idle:
        engine.step()


def widths(engine, reqs: List[generator.Req]) -> Dict[int, int]:
    """Each table width the engine can use for these requests, with one
    budget (prompt plus output tokens) that makes it. The widest row sets
    the width, so each request's own budget gives every width."""
    cache = engine.cache
    out: Dict[int, int] = {}
    for r in reqs:
        budget = len(r.prompt) + r.n_new
        cache.reserve(0, budget)
        out.setdefault(cache.used_width(), budget)
        cache.release(0)
    return out


def warm(cell, engine, reqs: List[generator.Req]) -> None:
    """Compile or load every program that serving ``reqs`` can call."""
    slots = cell.settings["slots"]
    vocab = cell.config["vocab_size"]
    rng = np.random.default_rng(0)
    same = collections.Counter(len(r.prompt) for r in reqs)
    total = engine.cache.free_blocks()
    for n_prompt, m in sorted(same.items()):
        for k in range(1, min(m, slots) + 1):
            for _ in range(k):
                engine.submit(rng.integers(0, vocab, n_prompt), 1)
            _drain(engine)
    shortest = min(same)
    for w, budget in sorted(widths(engine, reqs).items()):
        engine.cache.reserve(0, budget)
        engine.submit(rng.integers(0, vocab, shortest), 2)
        _drain(engine)
        engine.cache.release(0)
    if engine.cache.free_blocks() != total:
        raise RuntimeError("warm-up left cache blocks reserved")


def serve_window(engine, reqs: List[generator.Req], seconds: float, win
                 ) -> tuple:
    """Drive the open loop; returns the records and, per chunk, ``(end_s,
    rows)`` with ``rows`` = ``[(prompt_len, tokens_before, tokens_after)]``
    for every request that got tokens in it."""
    sched = engine.scheduler
    recs: Dict[int, Record] = {}
    pending = collections.deque(reqs)
    chunks = []

    def now():
        return time.perf_counter() - win.t0

    def submit(until):
        while pending and pending[0].due_s <= until:
            r = pending.popleft()
            with H.span("submit"):
                rid = engine.submit(r.prompt, r.n_new, r.temperature, r.seed)
            recs[rid] = Record(r, rid, now())

    def step():
        with H.span("chunk"):
            finished = engine.step()
        t = now()
        rows = []
        for q in list(sched.running.values()) + list(finished):
            rec = recs.get(q.rid)
            if rec is None:
                continue
            n = len(q.tokens)
            if n > rec.n_seen:
                rows.append((len(rec.req.prompt), rec.n_seen, n))
                if rec.n_seen == 0:
                    rec.first_s, rec.n_first = t, n
                rec.n_seen = n
        for q in finished:
            rec = recs.get(q.rid)
            if rec is not None:
                rec.done_s, rec.tokens = t, list(q.tokens)
        chunks.append((t, rows))

    while (t := now()) < seconds:
        submit(t)
        if sched.idle:
            nxt = pending[0].due_s if pending else seconds
            with H.span("wait"):
                time.sleep(max(0.0, min(nxt, seconds) - now()))
            continue
        step()
    close = win.close()
    t_drain = now()
    submit(float("inf"))
    while any(r.done_s is None for r in recs.values()) \
            and now() < t_drain + DRAIN_S:
        step()
    return recs, chunks, close


def decode_steps(chunks, until: float) -> List[List[int]]:
    """Per decode step of the chunks that ended by ``until``, the position
    each decoding row wrote its token at. A request's first token comes
    from its prefill; token ``m >= 1`` is decoded from the token at
    position ``prompt_len + m - 1``."""
    steps: List[List[int]] = []
    for end, rows in chunks:
        if end > until:
            break
        per: List[List[int]] = []
        for n_prompt, before, after in rows:
            m0 = max(before, 1)
            for j in range(after - m0):
                while len(per) <= j:
                    per.append([])
                per[j].append(n_prompt + m0 + j - 1)
        steps.extend(per)
    return steps


def admitted(chunks, until: float) -> int:
    return sum(1 for end, rows in chunks if end <= until
               for _, before, _ in rows if before == 0)


def prefill_groups(chunks, until: float) -> int:
    """Batched prefills run by the steps that ended by ``until``: a request
    is admitted, prefilled and read back first in one step, and the engine
    prefills a step's admissions in one batch per prompt length."""
    return sum(len({n for n, before, _ in rows if before == 0})
               for end, rows in chunks if end <= until)


def tpot_ms(r: Record) -> Optional[float]:
    """Time per output token after the first readback; None for a request
    that got all its tokens with it."""
    after = len(r.tokens) - r.n_first
    if after <= 0:
        return None
    return (r.done_s - r.first_s) / after * 1e3


def pct(values: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(values, float), q))


def sample(recs: Dict[int, Record], seed: int) -> List[Record]:
    """The longest finished greedy request and others drawn from the
    seed."""
    done = sorted((r for r in recs.values() if r.tokens is not None
                   and r.req.temperature == 0.0), key=lambda r: r.req.index)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.req.prompt) + len(r.tokens),
                                       -r.req.index))
    rest = [r for r in done if r is not longest]
    rng = generator.rng_for(seed, 3)
    pick = rng.choice(len(rest), size=min(SAMPLE - 1, len(rest)),
                      replace=False) if rest else []
    return [longest] + [rest[i] for i in sorted(pick)]


def reference_gaps(cell, seed: int, picked: List[Record],
                   control: Optional[str] = None):
    """Per sampled request, the gaps of its served tokens (and, with
    ``control``, of the tokens the reference in that precision puts
    first)."""
    import jax
    import jax.numpy as jnp
    cfg = cell.config
    ref = cell.reference()
    shapes, axes = ref.layout(cfg)
    params = weights.make(shapes, axes, seed)
    max_len = cell.settings["max_len"]
    n_max = cell.traffic["output"]["max"]
    fn = jax.jit(lambda p, toks, start: ref.served_gaps(
        cfg, p, toks, start, n_max, control))
    out = []
    for r in picked:
        seq = np.zeros((max_len,), np.int32)
        toks = np.concatenate([r.req.prompt, np.asarray(r.tokens, np.int32)])
        seq[:len(toks)] = toks
        res = fn(params, jnp.asarray(seq), jnp.int32(len(r.req.prompt)))
        n = len(r.tokens)
        if control is None:
            out.append(np.asarray(res)[:n])
        else:
            out.append(tuple(np.asarray(x)[:n] for x in res))
    return out


def free(engine) -> None:
    engine.params = None
    engine.cache.pools = None
    gc.collect()


def run(cell, devices, seed: int, seconds: float, traced: bool,
        t_start: float, counter) -> dict:
    reqs = generator.open_loop(seed, cell.traffic, seconds,
                               cell.config["vocab_size"])
    engine = build(cell, seed)
    with H.span("warm"):
        warm(cell, engine, reqs)
    setup = counter.snapshot()
    setup_s = time.perf_counter() - t_start
    H.log(f"setup: {setup_s!r} s; programs {setup}; compiled "
          f"{dict(counter.names)}; {len(reqs)} requests")

    with H.Window(cell.name, traced) as win:
        recs, chunks, close = serve_window(engine, reqs, seconds, win)
    window_compiles = counter.programs - setup["programs"]
    print(f"window_compiles={window_compiles}", flush=True)
    device = H.device_block(devices)
    free(engine)
    del engine

    done = [r for r in recs.values() if r.done_s is not None]
    failed = len(reqs) - len(done)
    delivered = sum(after - before for end, rows in chunks if end <= close
                    for _, before, after in rows)
    result = {"correct": False, "attempted": len(reqs), "failed": failed,
              "device": device,
              "setup": {**setup, "window_compiles": window_compiles}}
    if traced:
        prec = cell.config["precision"]
        inputs = H.LayerInputs(
            trace=win.trace, chips=cell.chips, config=cell.config,
            peaks=peaks.peaks_for(device["kind"]),
            counts={"decode_steps": decode_steps(chunks, close),
                    "admitted": admitted(chunks, close),
                    "prefill_groups": prefill_groups(chunks, close),
                    "weight_bytes": min(
                        np.dtype(prec["params"]).itemsize,
                        np.dtype(prec["compute"]).itemsize),
                    "kv_bytes": np.dtype(prec["kv_cache"]).itemsize})
        result["metrics"] = H.per_layer(cell, inputs)
        result["device"].update(busy_s=tr.mean_busy_s(win.trace),
                                window_s=win.trace.window_s)
        result["breakdown"] = H.breakdown(win.trace)
    elif done:
        ttft = [(r.first_s - r.req.due_s) * 1e3 for r in done]
        tpot = [t for t in map(tpot_ms, done) if t is not None]
        result["metrics"] = {
            "serve_tokens_per_s": {"value": delivered / close,
                                   "unit": "tokens/s"},
            "serve_ttft_p90_ms": {"value": pct(ttft, 90), "unit": "ms"},
            "serve_tpot_p90_ms": {"value": pct(tpot, 90), "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        lag = [(r.submit_s - r.req.due_s) * 1e3 for r in recs.values()]
        result["generator_lag_ms"] = {"p50": pct(lag, 50), "max": max(lag)}
        H.log(f"window {close!r} s: {len(done)} of {len(reqs)} finished, "
              f"{delivered} tokens delivered; submission lag p50 "
              f"{pct(lag, 50)!r} ms, max {max(lag)!r} ms; ttft p50 "
              f"{pct(ttft, 50)!r} ms; tpot p50 {pct(tpot, 50)!r} ms over "
              f"{len(tpot)} requests")

    picked = sample(recs, seed)
    with H.span("reference"):
        gaps = reference_gaps(cell, seed, picked)
    flat = [float(g) for a in gaps for g in a]
    numbers = compare.serve_numbers(flat)
    chk = H.checks(numbers, cell.settings["limits"])
    H.log(f"reference checked {len(picked)} requests, {len(flat)} tokens")
    result["correct"] = H.all_within(chk) and failed == 0 and len(flat) > 0
    result.setdefault("metrics", {})
    return {"result": result, "checks": chk}

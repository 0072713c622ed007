"""Serving engines.

``ContinuousEngine`` is the production path: a continuous-batching engine
over the paged block cache (serve/paged_cache.py) whose decode body is ONE
jitted ``lax.while_loop`` program — sampling, paged cache writes and
per-request done-flags all happen inside the loop, with no host round-trip
per token. Requests are admitted into and evicted from the running batch at
token boundaries (serve/scheduler.py); the loop exits early when a request
finishes while others are queued, so freed slots/blocks are recycled
immediately.

Determinism: the key for the token at absolute position ``p`` of a request
is ``fold_in(fold_in(engine_key, request_seed), p)`` — a pure function of
the request, never of the batch it happened to ride in. Together with the
row-independence of every per-token op (norms, attention, MLP, SSM step,
argmax), a request decodes token-for-token identically whether it runs solo
or is inserted/evicted mid-flight — the greedy-parity guarantee
(tests/test_serve_continuous.py). MoE layers are the exception: capacity
dispatch ranks tokens across the whole batch, so only non-MoE archs get
exact parity.

``ServeEngine`` is the legacy monolithic-cache engine, kept for the archs
the paged path does not cover (cross-attention/media, audio codebooks).

For trained WASGD checkpoints the served copy is worker 0's slice after a
final beta=1 aggregation (all workers coincide — Sec. 4.1's tau-step fixed
point): ``train.evaluate.consensus_params``. ``HotSwapBridge`` wires that
into ``Trainer.run(serve_hook=...)``: each call swaps the fresh consensus
into a live engine without touching in-flight decode state (params are an
argument of the jitted loop, not a constant), and records per-swap
staleness metrics.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import decode_step, decode_step_paged, init_cache, prefill
from repro.obs import NULL, HotSwap, ServeSample, span
from repro.serve.paged_cache import PagedCache
from repro.serve.scheduler import Request, Scheduler


class ServeEngine:
    """Legacy engine: monolithic ``(b, max_len, ...)`` cache, Python
    token loop. Covers every arch (incl. media/audio); use
    ``ContinuousEngine`` for throughput serving of text archs."""

    def __init__(self, cfg: ModelConfig, params: Dict, max_len: int = 2048,
                 cache_dtype=jnp.bfloat16):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.cache_dtype = cache_dtype
        self._prefill = jax.jit(functools.partial(prefill, cfg))
        self._decode = jax.jit(functools.partial(decode_step, cfg))

    def generate(self, prompt: np.ndarray, n_new: int,
                 media: Optional[np.ndarray] = None,
                 temperature: float = 0.0, seed: int = 0,
                 eos_id: Optional[int] = None) -> np.ndarray:
        """prompt: (b, s) int32 (or (b, s, n_q) audio). Greedy if T == 0.

        With ``eos_id`` set, decoding stops once every row has emitted the
        stop token, and a row's positions after its first stop token are
        padded with it. Checking the stop condition forces a device-to-host
        read of every token — the structural cost of a Python decode loop
        that the ``ContinuousEngine`` while_loop folds into its compiled
        done-flags."""
        b, s = prompt.shape[:2]
        if s + n_new > self.max_len:
            raise ValueError(
                f"prompt ({s}) + n_new ({n_new}) = {s + n_new} tokens "
                f"exceeds the cache budget max_len={self.max_len}")
        cache = init_cache(self.cfg, b, self.max_len, self.cache_dtype)
        logits, cache = self._prefill(self.params, jnp.asarray(prompt), cache,
                                      media)
        key = jax.random.key(seed)
        key, sub = jax.random.split(key)
        out = [self._sample(logits, temperature, sub)]
        done = (np.asarray(out[-1])[:, 0] == eos_id
                if eos_id is not None else None)
        index = s
        for t in range(n_new - 1):
            if done is not None and done.all():
                break
            key, sub = jax.random.split(key)
            tok = out[-1]
            logits, cache = self._decode(self.params, tok, cache,
                                         jnp.int32(index), media)
            out.append(self._sample(logits, temperature, sub))
            if done is not None:
                done |= np.asarray(out[-1])[:, 0] == eos_id
            index += 1
        toks = np.concatenate([np.asarray(t) for t in out], axis=1)
        if eos_id is not None:
            hit = toks == eos_id
            past_eos = np.cumsum(hit, axis=1) - hit   # strictly after first
            toks = np.where(past_eos > 0, eos_id, toks)
        return toks

    def _sample(self, logits, temperature, key):
        logits = logits[:, -1:] if logits.shape[1] > 1 else logits
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits.astype(jnp.float32) / temperature, axis=-1
        ).astype(jnp.int32)


def _sample_rows(logits: jax.Array, temps: jax.Array,
                 keys: jax.Array) -> jax.Array:
    """Per-row sampling: argmax where temp <= 0, categorical otherwise.
    logits (n, 1, V) -> (n,) int32."""
    lg = logits[:, -1].astype(jnp.float32)
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    safe_t = jnp.where(temps > 0, temps, 1.0)
    cat = jax.vmap(jax.random.categorical)(keys, lg / safe_t[:, None])
    return jnp.where(temps > 0, cat.astype(jnp.int32), greedy)


class ContinuousEngine:
    """Continuous-batching engine on the paged KV cache.

    ``n_slots`` concurrent requests share per-layer block pools; admission
    reserves each request's whole token budget from the free list (decode
    never allocates) and scatters a batch=1 prefill into its blocks. The
    decode chunk is one jitted ``lax.while_loop``; finished rows keep
    riding the batch (KV writes redirected to the trash block, SSM state
    frozen) until the host recycles their slot at the next chunk boundary.

    ``eos_id``, when set, is a stop token: a row that emits it finishes
    regardless of remaining budget. The check compiles into the loop's
    done-flags — the host never reads a token to test it.

    ``step()`` opens ``serve.*`` host spans (``repro.obs.span``) that a
    profiler trace records on the device's clock: ``serve.step`` holds
    ``serve.admit`` (with a ``serve.prefill`` per prefill group and a
    ``serve.scatter`` per request, args ``rid`` and ``slot``),
    ``serve.tables``, ``serve.dispatch``, ``serve.readback`` and
    ``serve.collect``. The device programs are named ``jit_prefill``,
    ``jit_scatter``, ``jit_admit_state`` and ``jit_decode_chunk``.
    """

    def __init__(self, cfg: ModelConfig, params: Dict, n_slots: int = 8,
                 max_len: int = 2048, block_size: int = 16,
                 cache_dtype=jnp.bfloat16, chunk: int = 32,
                 full_blocks: Optional[int] = None, seed: int = 0,
                 eos_id: Optional[int] = None, telemetry=None):
        """``telemetry`` (a ``repro.obs`` sink; default ``NullSink`` = off)
        receives one ``ServeSample`` per ``step()``: fenced chunk wall
        time, inter-token latency, TTFT for requests admitted that step
        (submission to their first tokens read back on the host, which
        happens in the step that admits them), block-pool occupancy,
        queue depth, admission/eviction counts. A sink adds one fence per
        step, on the chunk; with the default sink the engine adds no
        fences or host reads."""
        for i in range(cfg.n_layers):
            if cfg.layer_is_cross_attn(i):
                raise NotImplementedError(
                    "ContinuousEngine does not serve cross-attention "
                    "(media) archs — use the legacy ServeEngine")
        if getattr(cfg, "n_codebooks", 0):
            raise NotImplementedError(
                "ContinuousEngine does not serve multi-codebook (audio) "
                "archs — use the legacy ServeEngine")
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.block_size = block_size
        self.cache_dtype = cache_dtype
        self.chunk = chunk
        self.cache = PagedCache(cfg, n_slots, max_len, block_size,
                                dtype=cache_dtype, full_blocks=full_blocks)
        self.scheduler = Scheduler(n_slots)
        self.tokens_generated = 0
        self.n_swaps = 0
        self.eos_id = eos_id
        self.telemetry = telemetry if telemetry is not None else NULL
        self._key = jax.random.key(seed)

        n = n_slots
        self._st: Dict[str, Any] = {
            "last_tok": jnp.zeros((n, 1), jnp.int32),
            "index": jnp.zeros((n,), jnp.int32),
            "remaining": jnp.zeros((n,), jnp.int32),
            "active": jnp.zeros((n,), bool),
            # chunk steps + the admission-time first token for a fresh row
            "out_buf": jnp.zeros((n, chunk + 1), jnp.int32),
            "out_pos": jnp.zeros((n,), jnp.int32),
            "keys": jax.random.split(self._key, n),
            "temps": jnp.zeros((n,), jnp.float32),
        }

        self._prefill = jax.jit(prefill, static_argnums=0)
        # prefill scratch caches keyed (batch, prompt bucket): the scratch
        # only has to hold the prompt (write_prefill reads nothing past
        # it), so admission never copies max_len-wide buffers
        self._mono_scratch: Dict[tuple, Dict] = {}

        def decode_chunk(params, pools, tables, st, stop_early, *,
                         max_steps: int):
            entry_active = st["active"]
            keys, temps = st["keys"], st["temps"]
            rows = jnp.arange(entry_active.shape[0])
            out_cap = st["out_buf"].shape[1]
            # loop-invariant: all-greedy batches skip per-step RNG entirely
            any_sampled = jnp.any(temps > 0)

            def cond(c):
                _, _, _, _, act, _, _, t = c
                newly_done = jnp.any(entry_active & ~act)
                return (jnp.any(act) & (t < max_steps)
                        & ~(stop_early & newly_done))

            def body(c):
                pools, lt, idx, rem, act, ob, op, t = c
                logits, pools = decode_step_paged(
                    cfg, params, lt, pools, tables, idx, act,
                    max_len=max_len, block_size=block_size)

                def sampled(lg, i):
                    tok_keys = jax.vmap(jax.random.fold_in)(keys, i + 1)
                    return _sample_rows(lg, temps, tok_keys)

                def greedy(lg, i):
                    return jnp.argmax(lg[:, -1].astype(jnp.float32),
                                      axis=-1).astype(jnp.int32)

                tok = jax.lax.cond(any_sampled, sampled, greedy, logits, idx)
                lt = jnp.where(act[:, None], tok[:, None], lt)
                opc = jnp.minimum(op, out_cap - 1)
                ob = ob.at[rows, opc].set(
                    jnp.where(act, tok, ob[rows, opc]))
                inc = act.astype(jnp.int32)
                idx = idx + inc
                op = op + inc
                rem = rem - inc
                act = act & (rem > 0)
                if eos_id is not None:       # in-loop done-flag, no host read
                    act = act & (tok != eos_id)
                return (pools, lt, idx, rem, act, ob, op, t + 1)

            c0 = (pools, st["last_tok"], st["index"], st["remaining"],
                  st["active"], st["out_buf"], st["out_pos"], jnp.int32(0))
            pools, lt, idx, rem, act, ob, op, t = jax.lax.while_loop(
                cond, body, c0)
            return pools, {**st, "last_tok": lt, "index": idx,
                           "remaining": rem, "active": act, "out_buf": ob,
                           "out_pos": op}, t

        self._chunk = jax.jit(decode_chunk, static_argnames=("max_steps",))

        def admit_state(st, lg, key, slot, seed, n_prompt, n_new, temp):
            """Fold a freshly prefilled request into the batch state: sample
            its first token (keyed by absolute position ``n_prompt``, same
            discipline as the decode loop) and set its slot's rows. One
            jitted call instead of a dozen eager dispatches."""
            base = jax.random.fold_in(key, seed)
            first_key = jax.random.fold_in(base, n_prompt)
            lg = lg.astype(jnp.float32)
            safe_t = jnp.where(temp > 0, temp, 1.0)
            cat = jax.random.categorical(first_key, lg / safe_t)
            tok = jnp.where(temp > 0, cat, jnp.argmax(lg)).astype(jnp.int32)
            st = dict(st)
            st["last_tok"] = st["last_tok"].at[slot, 0].set(tok)
            st["index"] = st["index"].at[slot].set(n_prompt)
            st["remaining"] = st["remaining"].at[slot].set(n_new - 1)
            st["active"] = st["active"].at[slot].set(n_new > 1)
            st["out_buf"] = st["out_buf"].at[slot, 0].set(tok)
            st["out_pos"] = st["out_pos"].at[slot].set(1)
            st["keys"] = st["keys"].at[slot].set(base)
            st["temps"] = st["temps"].at[slot].set(temp)
            return st

        self._admit_state = jax.jit(admit_state)

    # -- request API --------------------------------------------------------

    def submit(self, prompt: np.ndarray, n_new: int,
               temperature: float = 0.0, seed: int = 0) -> int:
        """prompt: (s,) int32. Returns a request id; drive with step()/run().
        The whole token budget is validated here — no silent overflow."""
        prompt = np.asarray(prompt, np.int32)
        s = prompt.shape[-1]
        if n_new < 1:
            raise ValueError(f"n_new must be >= 1, got {n_new}")
        if s + n_new > self.max_len:
            raise ValueError(
                f"prompt ({s}) + n_new ({n_new}) = {s + n_new} tokens "
                f"exceeds the cache budget max_len={self.max_len}")
        need = self.cache.blocks_needed(s + n_new)
        total = self.cache._group_phys.get("full", 0)
        if need > total > 0:
            raise ValueError(
                f"request needs {need} cache blocks but the pool only has "
                f"{total} — raise full_blocks or max_len")
        return self.scheduler.submit(prompt, n_new, temperature, seed)

    def swap_params(self, params: Dict) -> None:
        """Hot-swap served params. They are an *argument* of the jitted
        decode chunk, so this neither recompiles nor perturbs in-flight
        request state — the next chunk simply decodes under the new model."""
        self.params = params
        self.n_swaps += 1

    @property
    def n_running(self) -> int:
        return len(self.scheduler.running)

    # -- drive --------------------------------------------------------------

    def _admit_all(self) -> List[Request]:
        """Admit every waiting request that fits (FIFO, stop at the first
        that doesn't). Admissions sharing a prompt length share one batched
        prefill into a bucketed scratch cache; each request's prefill KV is
        then scattered into its reserved blocks and its first token folded
        into the batch state — it rides ``out_buf[slot, 0]`` and is
        collected with the next chunk, so admission never syncs the host.
        Each request gets ``t_admitted`` once its scatter and first-token
        sampling are dispatched. Returns the admitted requests."""
        with span("serve.admit"):
            admitted: List[Request] = []
            while True:
                req = self.scheduler.next_admit()
                if req is None or not self.cache.can_admit(req.total_budget):
                    break
                r = self.scheduler.admit()
                self.cache.reserve(r.slot, r.total_budget)
                admitted.append(r)
            by_len: Dict[int, List[Request]] = {}
            for r in admitted:
                by_len.setdefault(len(r.prompt), []).append(r)
            for n_prompt, group in by_len.items():
                k = len(group)
                with span("serve.prefill", k=k, n_prompt=n_prompt):
                    bucket = min(self.max_len,
                                 1 << max(3, (n_prompt - 1).bit_length()))
                    if (k, bucket) not in self._mono_scratch:
                        self._mono_scratch[(k, bucket)] = init_cache(
                            self.cfg, k, bucket, self.cache_dtype)
                    prompts = jnp.asarray(np.stack([r.prompt for r in group]))
                    logits, mono = self._prefill(
                        self.cfg, self.params, prompts,
                        self._mono_scratch[(k, bucket)], None)
                for i, r in enumerate(group):
                    with span("serve.scatter", rid=r.rid, slot=r.slot):
                        self.cache.write_prefill(r.slot, mono, n_prompt,
                                                 row=i)
                        self._st = self._admit_state(
                            self._st, logits[i, -1], self._key, r.slot,
                            r.seed, n_prompt, r.n_new,
                            jnp.float32(r.temperature))
                    r.t_admitted = time.perf_counter()
        return admitted

    def _collect(self) -> List[Request]:
        """Read the chunk's tokens back (this blocks until the chunk
        ends), hand them to their requests, stamp ``t_first`` on the
        requests that got their first tokens, and recycle finished
        slots."""
        st = self._st
        with span("serve.readback"):
            out_pos = np.asarray(st["out_pos"])
            out_buf = np.asarray(st["out_buf"])
            active = np.asarray(st["active"])
        with span("serve.collect"):
            now = time.perf_counter()
            finished: List[Request] = []
            for slot, req in list(self.scheduler.running.items()):
                k = int(out_pos[slot])
                if k:
                    if req.t_first is None:
                        req.t_first = now
                    req.tokens.extend(int(t) for t in out_buf[slot, :k])
                    self.tokens_generated += k
                if not active[slot]:     # budget spent or stop token emitted
                    self.cache.release(slot)
                    finished.append(self.scheduler.evict(slot))
            st["out_pos"] = jnp.zeros_like(st["out_pos"])
        return finished

    def step(self) -> List[Request]:
        """One scheduling round: admit waiting requests into free slots,
        run one jitted decode chunk, collect tokens and recycle finished
        slots. Returns the requests that finished this round."""
        with span("serve.step"):
            tele = self.telemetry
            obs_on = tele.enabled
            admitted = self._admit_all()
            if not self.scheduler.running:
                return []
            # attend only over block-table columns actually backed by
            # reserved blocks (bucketed to a multiple of four to bound
            # retraces) — the monolithic engine must attend over the whole
            # max_len budget. The span's ``width`` (full-attention table
            # blocks the chunk sees, 0 without that group) and ``live``
            # (blocks the running rows reserved) say how much of a walk
            # over the whole width the paged kernel skips.
            w = self.cache.used_width()
            with span("serve.tables", width=w or 0,
                      live=self.cache.reserved_blocks()):
                tables = self.cache.tables
                full = tables.get("full")
                if full is not None and w is not None and w < full.shape[1]:
                    tables = {**tables, "full": full[:, :w]}
            with span("serve.dispatch"):
                stop_early = jnp.asarray(bool(self.scheduler.queue))
                t0 = time.perf_counter() if obs_on else 0.0
                pools, st, t = self._chunk(self.params, self.cache.pools,
                                           tables, self._st, stop_early,
                                           max_steps=self.chunk)
                self.cache.pools = pools
                self._st = st
            if obs_on:
                jax.block_until_ready(st["out_pos"])
                chunk_s = time.perf_counter() - t0
                steps = int(t)               # host read: telemetry only
            tokens_before = self.tokens_generated
            finished = self._collect()
            if obs_on:
                now = time.perf_counter()
                tokens = self.tokens_generated - tokens_before
                free = self.cache.free_blocks()
                total = self.cache._group_phys.get("full", 0)
                tele.emit(ServeSample(
                    chunk_s=chunk_s, steps=steps, tokens=tokens,
                    itl_s=chunk_s / max(steps, 1),
                    n_running=self.n_running,
                    queue_depth=len(self.scheduler.queue),
                    admitted=len(admitted), finished=len(finished),
                    blocks_free=free, blocks_total=total,
                    occupancy=(1.0 - free / total) if total else 0.0,
                    ttft_s=[r.t_first - r.t_submit for r in admitted],
                    e2e_s=[now - r.t_submit for r in finished]))
            return finished

    def run(self) -> Dict[int, np.ndarray]:
        """Drain queue + running batch; returns {rid: generated tokens}."""
        while not self.scheduler.idle:
            self.step()
        return {rid: np.asarray(r.tokens, np.int32)
                for rid, r in self.scheduler.finished.items()}

    def generate(self, prompts: np.ndarray, n_new: int,
                 temperature: float = 0.0, seed: int = 0) -> np.ndarray:
        """Batched convenience wrapper: submit one request per row (row i
        seeded ``seed + i``), drain, return (b, n_new) in submission order."""
        prompts = np.asarray(prompts, np.int32)
        rids = [self.submit(p, n_new, temperature, seed + i)
                for i, p in enumerate(prompts)]
        done = self.run()
        return np.stack([done[r] for r in rids])


class HotSwapBridge:
    """``Trainer.run(serve_hook=...)`` adapter: on each call, extract the
    Sec. 4.1 fixed point (beta=1 equal aggregation, worker 0's slice) and
    hot-swap it into a live engine; in-flight requests keep decoding. Each
    swap appends a staleness record to ``swaps``: rounds since the engine
    last saw fresh params, how many tokens were served under the stale
    copy, the L2 drift the swap closed, and the in-flight request count."""

    def __init__(self, engine, telemetry=None):
        """``telemetry`` defaults to the engine's own sink, so a bridge
        over an instrumented engine emits ``HotSwap`` events without
        extra wiring; pass an explicit sink (or ``repro.obs.NULL``) to
        override."""
        self.engine = engine
        self.telemetry = (telemetry if telemetry is not None
                          else getattr(engine, "telemetry", NULL))
        self.swaps: List[Dict] = []
        self._last_round: Optional[int] = None
        self._tokens_at_swap = engine.tokens_generated

    @staticmethod
    def _drift(old: Dict, new: Dict) -> float:
        sq = jax.tree.map(
            lambda a, b: jnp.sum(
                (a.astype(jnp.float32) - b.astype(jnp.float32)) ** 2),
            old, new)
        return float(jnp.sqrt(sum(jax.tree.leaves(sq))))

    def __call__(self, round_idx: int, params: Dict, axes: Dict) -> Dict:
        from repro.train.evaluate import consensus_params
        new = consensus_params(params, axes)
        rec = {
            "round": int(round_idx),
            "rounds_since_last": (int(round_idx) - self._last_round
                                  if self._last_round is not None else None),
            "tokens_under_prev": self.engine.tokens_generated
            - self._tokens_at_swap,
            "param_drift_l2": self._drift(self.engine.params, new),
            "in_flight": self.engine.n_running,
        }
        self.engine.swap_params(new)
        self._last_round = int(round_idx)
        self._tokens_at_swap = self.engine.tokens_generated
        self.swaps.append(rec)
        if self.telemetry.enabled:
            self.telemetry.emit(HotSwap(**rec))
        return rec

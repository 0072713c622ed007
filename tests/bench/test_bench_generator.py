"""The traffic generator is deterministic per seed, gives every seed the
same work in another order, and the serving loop times each request from
when it was due."""
import collections
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from bench import generator  # noqa: E402
from bench.drivers import serve  # noqa: E402

CHAT = {"kind": "serve", "rate": 5.0,
        "prompt": {"median": 1020, "sigma": 0.5, "min": 16, "max": 3072},
        "output": {"median": 129, "sigma": 1.0, "min": 1, "max": 1024},
        "temperature": 0.7, "greedy_share": 0.25}
BIG = 2 ** 33 + 5


def test_open_loop_is_deterministic_per_seed():
    a = generator.open_loop(BIG, CHAT, 20, 64000)
    b = generator.open_loop(BIG, CHAT, 20, 64000)
    assert [(r.due_s, r.n_new, r.temperature, r.seed) for r in a] == \
        [(r.due_s, r.n_new, r.temperature, r.seed) for r in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    c = generator.open_loop(BIG + 2 ** 32, CHAT, 20, 64000)
    assert [r.due_s for r in a] != [r.due_s for r in c]


def test_every_seed_gets_the_same_work_in_another_order():
    runs = [generator.open_loop(s, CHAT, 20, 64000) for s in (1, 2, BIG)]
    for reqs in runs:
        assert len(reqs) == 100
        assert sum(r.temperature == 0.0 for r in reqs) == 25
        due = [r.due_s for r in reqs]
        assert due == sorted(due) and due[0] == 0.0 and due[-1] < 20
    sizes = [sorted(r.n_new for r in reqs) for reqs in runs]
    assert sizes[0] == sizes[1] == sizes[2]
    assert min(sizes[0]) >= 1 and max(sizes[0]) <= 1024
    assert np.median(sizes[0]) == 129
    assert [r.n_new for r in runs[0]] != [r.n_new for r in runs[1]]
    prompts = [sorted(len(r.prompt) for r in reqs) for reqs in runs]
    assert prompts[0] == prompts[1] == prompts[2]
    work = [sorted((len(r.prompt), r.n_new, r.temperature) for r in reqs)
            for reqs in runs]
    assert work[0] == work[1] == work[2]
    assert min(prompts[0]) >= 16 and max(prompts[0]) <= 3072
    assert abs(np.median(prompts[0]) - 1020) <= 5


def test_prompt_lengths_all_differ_where_the_range_allows():
    lens = generator.prompt_lengths(CHAT, 400)
    assert len(set(lens.tolist())) == 400 and lens.max() == 3072
    assert (np.diff(lens) > 0).all()
    tight = {**CHAT, "prompt": {"median": 10, "sigma": 1.0, "min": 8,
                                "max": 12}}
    few = generator.prompt_lengths(tight, 5)
    assert sorted(few.tolist()) == [8, 9, 10, 11, 12]
    many = generator.prompt_lengths(tight, 9)
    assert many.min() >= 8 and many.max() <= 12


def test_bursts_keep_the_count_and_the_span():
    reqs = generator.open_loop(3, {**CHAT, "bursts": {
        "every_s": 10, "length_s": 2, "factor": 3}}, 20, 64000)
    due = np.array([r.due_s for r in reqs])
    assert len(due) == 100 and due.min() >= 0 and due.max() < 20
    in_burst = (np.mod(due, 10) < 2).mean()
    assert 0.3 < in_burst < 0.55          # 3x the rate in a fifth of time


def test_bigram_rows_are_deterministic_and_follow_the_permutation():
    a = generator.bigram_rows(BIG, 8, 256, 1000)
    assert (a == generator.bigram_rows(BIG, 8, 256, 1000)).all()
    assert a.shape == (8, 257) and a.dtype == np.int32
    rounds = generator.train_rounds(7, {"seq": 64}, 8, 3, 1000)
    assert len(rounds) == 3 and rounds[0]["tokens"].shape == (8, 64)
    assert (rounds[0]["labels"][:, :-1] == rounds[0]["tokens"][:, 1:]).all()
    assert not (rounds[0]["tokens"] == rounds[1]["tokens"]).all()


class _Req:
    def __init__(self, rid, n_new):
        self.rid, self.n_new, self.tokens = rid, n_new, []


class _Sched:
    def __init__(self):
        self.queue, self.running = collections.deque(), {}

    @property
    def idle(self):
        return not self.queue and not self.running


class _Engine:
    """One token per step to every running request; each step takes
    ``dt`` seconds."""

    def __init__(self, dt):
        self.dt, self.scheduler, self.n = dt, _Sched(), 0

    def submit(self, prompt, n_new, temperature, seed):
        self.scheduler.queue.append(_Req(self.n, n_new))
        self.n += 1
        return self.n - 1

    def step(self):
        s = self.scheduler
        while s.queue:
            r = s.queue.popleft()
            s.running[r.rid] = r
        time.sleep(self.dt)
        done = []
        for rid, r in list(s.running.items()):
            r.tokens.append(1)
            if len(r.tokens) == r.n_new:
                done.append(s.running.pop(rid))
        return done


class _Clock:
    def __init__(self):
        self.t0 = time.perf_counter()

    def close(self):
        return time.perf_counter() - self.t0


def test_time_to_first_token_counts_from_when_the_request_was_due():
    reqs = [generator.Req(i, due, np.zeros(4, np.int32), n, 0.0, 0)
            for i, (due, n) in enumerate([(0.0, 3), (0.01, 2), (0.3, 2)])]
    recs, chunks, close = serve.serve_window(_Engine(0.1), reqs, 0.5,
                                             _Clock())
    assert close >= 0.5 and all(r.done_s is not None for r in recs.values())
    late = recs[1]
    # due at 0.01 while the first step ran: submitted late, and its first
    # token is timed from 0.01, so it counts the wait
    assert late.submit_s >= 0.1
    assert late.first_s - late.req.due_s >= 0.19
    assert recs[2].first_s - recs[2].req.due_s >= 0.1
    assert sum(a - b for _, rows in chunks for _, b, a in rows) == 7


def test_decode_positions_per_step():
    # prompt 10: first token from prefill, then tokens 1..3 decoded from
    # positions 10, 11, 12; prompt 5 already at 4 tokens decodes 4 and 5
    chunks = [(1.0, [(10, 0, 4), (5, 4, 6)]), (9.0, [(10, 4, 5)])]
    assert serve.decode_steps(chunks, 2.0) == [[10, 8], [11, 9], [12]]
    assert serve.admitted(chunks, 2.0) == 1


def test_prefill_groups_count_one_batch_per_length_per_step():
    chunks = [(1.0, [(10, 0, 3), (10, 0, 2), (7, 0, 1), (5, 4, 6)]),
              (2.0, [(10, 0, 2), (10, 2, 5)]), (9.0, [(4, 0, 1)])]
    assert serve.prefill_groups(chunks, 2.0) == 3
    assert serve.admitted(chunks, 2.0) == 4


def test_time_per_output_token_counts_tokens_after_the_first_readback():
    req = generator.Req(0, 0.0, np.zeros(4, np.int32), 40, 0.0, 0)
    rec = serve.Record(req, 0, 0.0, first_s=1.0, done_s=1.5,
                       tokens=[1] * 40, n_first=30)
    assert serve.tpot_ms(rec) == 50.0
    rec.n_first = 40
    assert serve.tpot_ms(rec) is None

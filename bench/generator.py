"""The one traffic generator: every cell's inputs come from here, made from
the run's ``--seed`` and its traffic file's parameters.

Training traffic (``"kind": "train"``) is token rows of a noisy-permutation
bigram language (copied from the program's ``data/synthetic.make_tokens``,
vectorised over rows), one block of ``tau x workers x b_local`` rows per
round.

Serving traffic (``"kind": "serve"``) is an open-loop schedule. A run of
``seconds`` holds ``round(rate x seconds)`` requests, and every seed gets
the same requests and the same inter-arrival gaps, each in another order:
prompt and output lengths come from fixed quantiles of a lognormal
(``median``, ``sigma``, clipped to ``min`` and ``max``) and are paired,
with the greedy flags, once for all seeds; the gaps come from fixed
quantiles of the exponential. So two seeds differ in which request comes
when, never in what the requests are. As in a recorded trace, no two prompts of a run have the same
length where the clipped range holds enough lengths: ties between
neighbouring quantiles move to the nearest free length. Token ids and
per-request sampling seeds are uniform from the seed. An optional ``bursts`` entry warps the arrival clock
so that the rate is ``factor`` times higher for ``length_s`` of every
``every_s`` seconds, keeping the mean rate.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import List

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per ``stream``; any non-negative seed, of any
    size."""
    return np.random.default_rng([int(seed), int(stream)])


# -- training --------------------------------------------------------------------

def bigram_rows(seed: int, n_rows: int, seq_len: int, vocab: int,
                p_follow: float = 0.8) -> np.ndarray:
    """(n_rows, seq_len + 1) int32: token t+1 = perm[t] with probability
    ``p_follow``, else uniform."""
    rng = rng_for(seed, 1)
    perm = rng.permutation(vocab).astype(np.int32)
    toks = np.empty((n_rows, seq_len + 1), np.int32)
    toks[:, 0] = rng.integers(0, vocab, size=n_rows)
    follow = rng.random((seq_len, n_rows)) < p_follow
    rand = rng.integers(0, vocab, size=(seq_len, n_rows), dtype=np.int32)
    for t in range(seq_len):
        toks[:, t + 1] = np.where(follow[t], perm[toks[:, t]], rand[t])
    return toks


def train_rounds(seed: int, traffic: dict, rows: int, n_rounds: int,
                 vocab: int) -> List[dict]:
    """``n_rounds`` distinct round batches: ``{"tokens", "labels"}`` of
    ``(rows, seq)`` int32 each."""
    toks = bigram_rows(seed, n_rounds * rows, traffic["seq"], vocab,
                       traffic.get("p_follow", 0.8))
    out = []
    for r in range(n_rounds):
        blk = toks[r * rows:(r + 1) * rows]
        out.append({"tokens": np.ascontiguousarray(blk[:, :-1]),
                    "labels": np.ascontiguousarray(blk[:, 1:])})
    return out


# -- serving ---------------------------------------------------------------------

@dataclasses.dataclass
class Req:
    index: int
    due_s: float          # offset from the window's start
    prompt: np.ndarray    # (s,) int32
    n_new: int
    temperature: float    # 0 = greedy
    seed: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at fixed quantiles of the clipped lognormal, in
    ascending order."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf(q) for q in _quantiles(n)])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def distinct(lengths: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """Ascending ``lengths`` with ties moved to the nearest free lengths
    inside ``[lo, hi]``; unchanged where the range is too small."""
    out = lengths.copy()
    if len(out) > hi - lo + 1:
        return out
    for i in range(1, len(out)):
        out[i] = max(out[i], out[i - 1] + 1)
    out[-1] = min(out[-1], hi)
    for i in range(len(out) - 2, -1, -1):
        out[i] = min(out[i], out[i + 1] - 1)
    return out


def prompt_lengths(traffic: dict, n: int) -> np.ndarray:
    p = traffic["prompt"]
    return distinct(lognormal_lengths(p, n), p["min"], p["max"])


def output_lengths(traffic: dict, n: int) -> np.ndarray:
    return lognormal_lengths(traffic["output"], n)


def _warp(u: np.ndarray, seconds: float, bursts: dict) -> np.ndarray:
    """Map arrival instants of a constant-rate clock onto a clock whose rate
    is ``factor`` times higher in the first ``length_s`` of every
    ``every_s`` seconds, with the same mean."""
    every, length, factor = (bursts["every_s"], bursts["length_s"],
                             bursts["factor"])
    hi = factor / (factor * length + (every - length)) * every
    lo = 1.0 / (factor * length + (every - length)) * every
    grid = np.linspace(0.0, seconds, 20001)
    rate = np.where(np.mod(grid, every) < length, hi, lo)
    cum = np.concatenate([[0.0], np.cumsum((rate[1:] + rate[:-1]) / 2
                                           * np.diff(grid))])
    cum *= seconds / cum[-1]
    return np.interp(u, cum, grid)


def open_loop(seed: int, traffic: dict, seconds: float, vocab: int
              ) -> List[Req]:
    """The run's requests in order of their due time."""
    rng = rng_for(seed, 2)
    n = max(1, int(round(traffic["rate"] * seconds)))
    pairing = rng_for(0, 4)          # the same requests for every seed
    lens = prompt_lengths(traffic, n)
    outs = pairing.permutation(output_lengths(traffic, n))
    n_greedy = int(round(traffic.get("greedy_share", 0.0) * n))
    greedy = pairing.permutation(np.arange(n) < n_greedy)
    order = rng.permutation(n)
    lens, outs, greedy = lens[order], outs[order], greedy[order]
    gaps = rng.permutation(-np.log1p(-_quantiles(n)))
    due = (np.cumsum(gaps) - gaps) / gaps.sum() * seconds
    if traffic.get("bursts"):
        due = _warp(due, seconds, traffic["bursts"])
    temp = float(traffic.get("temperature", 0.0))
    seeds = rng.integers(0, 2 ** 31 - 1, size=n)
    reqs = []
    for i in range(n):
        prompt = rng.integers(0, vocab, size=int(lens[i]), dtype=np.int32)
        reqs.append(Req(i, float(due[i]), prompt, int(outs[i]),
                        0.0 if greedy[i] else temp, int(seeds[i])))
    return reqs

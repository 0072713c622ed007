"""Operations and bytes that the work of a cell requires, from its shapes.

These are the least the algorithm needs, never what an implementation
happens to do: recomputed forwards, masked-out attention blocks and extra
copies are not counted, so a share of a peak built on them cannot pass 100%
unless the time is wrong.

Every function takes a configuration as its JSON file holds it
(``bench/configs/<name>.json``). What each layer holds comes from the
configuration's plain reference, ``bench/reference/<reference>.py``, whose
``counts(cfg)`` gives a ``Counts`` beside the reference's equations; this
file only sums them. A new architecture brings its counts in its own
reference.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Iterable, Optional, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class Attention:
    """Softmax attention of a row's new token over its cached tokens."""
    heads: int
    kv_heads: int
    head_dim: int
    window: Optional[int] = None    # tokens a query sees, itself included

    def keys(self, p: int) -> int:
        """Cached tokens the token at position ``p`` attends to."""
        return p + 1 if self.window is None else min(p + 1, self.window)

    def mean_keys(self, seq: int) -> float:
        """Keys per query of a ``seq``-long causal sequence, the triangle
        counted as half the square: ``seq / 2``, or ``W - W^2 / (2 seq)``
        for a window ``W`` shorter than the sequence."""
        w = self.window
        if w is None or w >= seq:
            return seq / 2
        return w - w * w / (2 * seq)


@dataclasses.dataclass(frozen=True)
class Experts:
    """Routed experts: each token multiplies ``top_k`` of ``count``."""
    count: int
    top_k: int
    params: int                     # matmul weights of one expert


@dataclasses.dataclass(frozen=True)
class Layer:
    """What one layer holds: ``matmul``, the weights each token multiplies,
    experts aside; ``other``, weights read but multiplied by no matrix (norm
    scales, conv taps); ``token_flops``, forward FLOPs per token besides the
    matmuls and attention (a recurrence); ``state``, the elements of a
    row's recurrent state, read and written every decode step."""
    matmul: int
    other: int = 0
    attention: Optional[Attention] = None
    experts: Optional[Experts] = None
    token_flops: int = 0
    state: int = 0


@dataclasses.dataclass(frozen=True)
class Counts:
    layers: Tuple[Layer, ...]
    head: int                       # output head weights
    other: int                      # weights outside the layers, read once
    embed_row: int                  # embedding entries a token looks up


def counts(cfg: dict) -> Counts:
    """The configuration's reference's counts."""
    ref = importlib.import_module(f"bench.reference.{cfg['reference']}")
    return ref.counts(cfg)


def _matmul(layer: Layer) -> int:
    e = layer.experts
    return layer.matmul + (e.top_k * e.params if e else 0)


def layer_matmul_params(cfg: dict) -> int:
    """Weights that one token multiplies in the first layer, which in a
    dense decoder is every layer: its matrices and, where it routes to
    experts, ``top_k`` experts."""
    return _matmul(counts(cfg).layers[0])


def matmul_params(cfg: dict) -> int:
    """Every weight that one token multiplies: the layers and the output
    head. The embedding table is a lookup, not a matmul."""
    c = counts(cfg)
    return sum(_matmul(l) for l in c.layers) + c.head


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward of one token of a ``seq``-long causal
    sequence: 6 x matmul weights; per attention layer 12 x its keys per
    query x (heads x head_dim) (QK^T and PV, three passes); 3 x each
    layer's other FLOPs."""
    c = counts(cfg)
    attn = sum(12.0 * a.mean_keys(seq) * a.heads * a.head_dim
               for a in (l.attention for l in c.layers) if a)
    return 6.0 * matmul_params(cfg) + attn + 3.0 * sum(
        l.token_flops for l in c.layers)


def decode_flops(cfg: dict, positions: Iterable[int]) -> float:
    """One decode step over rows at ``positions`` (the position each row's
    new token is written at): 2 x matmul weights and each layer's other
    FLOPs per row, plus each attention layer's QK^T and PV over the keys
    of position ``p``."""
    c = counts(cfg)
    pos = list(positions)
    attn = sum(4 * a.keys(p) * a.heads * a.head_dim
               for a in (l.attention for l in c.layers) if a for p in pos)
    per_row = 2 * matmul_params(cfg) + sum(l.token_flops for l in c.layers)
    return float(per_row * len(pos) + attn)


def kv_bytes(cfg: dict, positions: Iterable[int], kv_dtype_bytes: int
             ) -> float:
    """K and V that attention must read for rows at ``positions``: in each
    attention layer, the keys of position ``p`` of kv heads x head_dim,
    twice."""
    pos = list(positions)
    return float(sum(2 * a.keys(p) * a.kv_heads * a.head_dim
                     * kv_dtype_bytes
                     for a in (l.attention for l in counts(cfg).layers) if a
                     for p in pos))


def decode_bytes(cfg: dict, positions: Iterable[int], weight_bytes: int,
                 kv_dtype_bytes: int,
                 routed: Optional[Sequence[int]] = None) -> float:
    """Bytes one decode step must read: every weight once, the embedding
    rows of its tokens, the K/V of every row, and each row's recurrent
    state, read and written back at the cache's dtype.

    Of an expert layer the step reads the experts its rows routed to,
    ``routed``, one number per expert layer in layer order; without it,
    ``top_k``, the fewest a step can route to."""
    c = counts(cfg)
    pos = list(positions)
    moe = [l.experts for l in c.layers if l.experts]
    n_read = ([e.top_k for e in moe] if routed is None else
              [min(n, e.count) for n, e in zip(routed, moe, strict=True)])
    weights = (sum(l.matmul + l.other for l in c.layers)
               + sum(n * e.params for n, e in zip(n_read, moe))
               + c.head + c.other + len(pos) * c.embed_row) * weight_bytes
    state = 2 * len(pos) * sum(l.state for l in c.layers) * kv_dtype_bytes
    return float(weights) + kv_bytes(cfg, pos, kv_dtype_bytes) + float(state)

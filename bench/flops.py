"""Operations and bytes that the work of a cell requires, from its shapes.

These are the least the algorithm needs, never what an implementation
happens to do: recomputed forwards, masked-out attention blocks and extra
copies are not counted, so a share of a peak built on them cannot pass 100%
unless the time is wrong.

Every function takes a configuration as its JSON file holds it
(``bench/configs/<name>.json``: ``hidden_size``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``intermediate_size``,
``vocab_size``, ``num_hidden_layers``).
"""
from __future__ import annotations

from typing import Iterable


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    return d, h, kv, hd, cfg["intermediate_size"], cfg["vocab_size"], \
        cfg["num_hidden_layers"]


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one decoder layer that multiply activations: q, k, v, o
    and the gated MLP's three matrices."""
    d, h, kv, hd, ff, _, _ = _dims(cfg)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff


def matmul_params(cfg: dict) -> int:
    """Every weight that multiplies activations: the layers and the output
    head. The embedding table is a lookup, not a matmul."""
    d, _, _, _, _, v, n = _dims(cfg)
    return n * layer_matmul_params(cfg) + d * v


def norm_params(cfg: dict) -> int:
    d, _, _, _, _, _, n = _dims(cfg)
    return (2 * n + 1) * d


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward of one token of a ``seq``-long causal
    sequence: 6 x matmul weights, plus 6 x layers x seq x (heads x head_dim)
    for causal attention (QK^T and PV, half the square, three passes)."""
    d, h, kv, hd, _, _, n = _dims(cfg)
    return 6.0 * matmul_params(cfg) + 6.0 * n * seq * h * hd


def decode_flops(cfg: dict, positions: Iterable[int]) -> float:
    """One decode step over rows at ``positions`` (the position each row's
    new token is written at): 2 x matmul weights per row, plus attention
    over the position's ``p + 1`` cached tokens (QK^T and PV)."""
    d, h, kv, hd, _, _, n = _dims(cfg)
    pos = list(positions)
    attn = sum(4.0 * (p + 1) * h * hd for p in pos) * n
    return 2.0 * matmul_params(cfg) * len(pos) + attn


def kv_bytes(cfg: dict, positions: Iterable[int], kv_dtype_bytes: int
             ) -> float:
    """K and V that attention must read for rows at ``positions``, every
    layer: ``p + 1`` cached tokens of kv heads x head_dim, twice."""
    d, h, kv, hd, _, _, n = _dims(cfg)
    return float(sum(2 * (p + 1) * kv * hd * kv_dtype_bytes
                     for p in positions) * n)


def decode_bytes(cfg: dict, positions: Iterable[int], weight_bytes: int,
                 kv_dtype_bytes: int) -> float:
    """Bytes one decode step must read: every matmul weight and norm scale
    once, the embedding rows of its tokens, and the K/V of every row."""
    d = cfg["hidden_size"]
    pos = list(positions)
    weights = (matmul_params(cfg) + norm_params(cfg)
               + len(pos) * d) * weight_bytes
    return float(weights) + kv_bytes(cfg, pos, kv_dtype_bytes)

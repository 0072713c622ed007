"""FLOP and byte counts from shapes, against hand counts of both
configurations."""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from bench import flops  # noqa: E402


def cfg(name):
    with open(os.path.join(REPO, "bench", "configs", f"{name}.json")) as fh:
        return json.load(fh)


SL = cfg("stablelm-2-1.6b-l4")
YI = cfg("yi-6b-l4")

# per layer: q and o 2*d*h*hd, k and v 2*d*kv*hd, gated MLP 3*d*ff
SL_LAYER = 2 * 2048 * 32 * 64 + 2 * 2048 * 32 * 64 + 3 * 2048 * 5632
YI_LAYER = 2 * 4096 * 32 * 128 + 2 * 4096 * 4 * 128 + 3 * 4096 * 11008


@pytest.mark.parametrize("c,layer,d,v", [(SL, SL_LAYER, 2048, 100352),
                                         (YI, YI_LAYER, 4096, 64000)])
def test_matmul_params_leave_out_the_embedding(c, layer, d, v):
    assert flops.layer_matmul_params(c) == layer
    assert flops.matmul_params(c) == 4 * layer + d * v


def test_train_flops_per_token_stablelm():
    want = 6 * (4 * SL_LAYER + 2048 * 100352) + 6 * 4 * 4096 * 32 * 64
    assert flops.train_flops_per_token(SL, 4096) == want


def test_decode_flops_and_kv_bytes_yi():
    pos = [0, 127, 2047]
    attn = sum(4 * (p + 1) * 32 * 128 for p in pos) * 4
    want = 2 * (4 * YI_LAYER + 4096 * 64000) * 3 + attn
    assert flops.decode_flops(YI, pos) == want
    kv = sum(2 * (p + 1) * 4 * 128 * 2 for p in pos) * 4
    assert flops.kv_bytes(YI, pos, 2) == kv


def test_decode_bytes_reads_weights_once_and_rows_of_the_embedding():
    pos = [10, 20]
    weights = (4 * YI_LAYER + 4096 * 64000) + (2 * 4 + 1) * 4096 + 2 * 4096
    assert flops.decode_bytes(YI, pos, 2, 2) == weights * 2 + \
        flops.kv_bytes(YI, pos, 2)


# -- counts from the configuration's reference --------------------------------

def _program_leaves(c):
    from bench import harness
    from repro.models import abstract_params
    shapes, _ = abstract_params(harness.program_config(c))
    return shapes


def _size(tree):
    if isinstance(tree, dict):
        return sum(_size(v) for v in tree.values())
    n = 1
    for s in tree.shape:
        n *= s
    return n


def _tiny():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import bench_tiny
    return bench_tiny.CONFIG


@pytest.mark.parametrize("name", ["tiny", "yi-6b-l4", "stablelm-2-1.6b-l4"])
def test_dense_reference_counts_every_program_parameter(name):
    c = _tiny() if name == "tiny" else cfg(name)
    shapes = _program_leaves(c)
    counts = flops.counts(c)
    assert len(counts.layers) == c["num_hidden_layers"]
    for i, layer in enumerate(counts.layers):
        assert layer.matmul + layer.other == _size(shapes["layers"][f"L{i}"])
        assert layer.other == _size(shapes["layers"][f"L{i}"]["attn_norm"]) \
            + _size(shapes["layers"][f"L{i}"]["ffn_norm"])
    assert counts.head == _size(shapes["head"])
    assert counts.other == _size(shapes["final_norm"])
    assert counts.embed_row == shapes["embed"]["tok"].shape[1]


def _stub(monkeypatch, name, counts):
    """A reference module that gives only hand-made counts."""
    import types
    mod = types.ModuleType(f"bench.reference.{name}")
    mod.counts = lambda c: counts
    monkeypatch.setitem(sys.modules, f"bench.reference.{name}", mod)
    return {"reference": name}


# One MoE layer, d 8: attention of 2 heads over 1 kv head of 4 (192 weights)
# and a router to 4 experts (32); 2 norms (16); experts of 3 x 8 x 6 = 144
# weights, top 2. Head 8 x 10, final norm 8.
TOY_MOE = flops.Counts(
    layers=(flops.Layer(matmul=192 + 32, other=16,
                        attention=flops.Attention(2, 1, 4),
                        experts=flops.Experts(count=4, top_k=2, params=144)),),
    head=80, other=8, embed_row=8)


def test_toy_moe_layer_by_hand(monkeypatch):
    c = _stub(monkeypatch, "toy_moe", TOY_MOE)
    assert flops.layer_matmul_params(c) == 224 + 2 * 144
    assert flops.matmul_params(c) == 224 + 288 + 80
    # rows at 0 and 5: 2 x 592 per row, attention 4 x (1 + 6) x 2 x 4
    assert flops.decode_flops(c, [0, 5]) == 2 * 592 * 2 + 224
    assert flops.kv_bytes(c, [0, 5], 2) == 2 * (1 + 6) * 1 * 4 * 2
    # seq 6: 6 x 592, and 12 x 3 keys x 8
    assert flops.train_flops_per_token(c, 6) == 6 * 592 + 288


@pytest.mark.parametrize("routed, experts_read", [
    (None, 2),          # the fewest a step can read: top 2
    ([3], 3),           # the experts its rows routed to
    ([9], 4),           # never more than the layer has
])
def test_decode_bytes_reads_the_experts_a_step_routed_to(
        monkeypatch, routed, experts_read):
    c = _stub(monkeypatch, "toy_moe", TOY_MOE)
    # 2 rows: layer 240, experts, head 80, final norm 8, 2 embedding rows
    weights = 240 + experts_read * 144 + 80 + 8 + 2 * 8
    assert flops.decode_bytes(c, [0, 5], 2, 2, routed=routed) == \
        weights * 2 + 112


# A hybrid period of two layers: a recurrent mixer (100 matmul weights, 10
# others, 40 FLOPs a token, 30 state elements a row) and an attention layer
# of 2 heads over 1 kv head of 4 with a 4-token window (50 and 5).
TOY_HYBRID = flops.Counts(
    layers=(flops.Layer(matmul=100, other=10, token_flops=40, state=30),
            flops.Layer(matmul=50, other=5,
                        attention=flops.Attention(2, 1, 4, window=4))),
    head=20, other=3, embed_row=2)


def test_toy_hybrid_period_by_hand(monkeypatch):
    c = _stub(monkeypatch, "toy_hybrid", TOY_HYBRID)
    assert [l.matmul for l in flops.counts(c).layers] == [100, 50]
    assert flops.matmul_params(c) == 170
    # rows at 1 and 9 see 2 and 4 (the window) keys, in the one attention
    assert flops.decode_flops(c, [1, 9]) == (2 * 170 + 40) * 2 \
        + 4 * (2 + 4) * 2 * 4
    assert flops.kv_bytes(c, [1, 9], 2) == 2 * (2 + 4) * 4 * 2
    weights = (110 + 55 + 20 + 3 + 2 * 2) * 2
    # 2 rows read and write 30 state elements each at the cache's 2 bytes
    assert flops.decode_bytes(c, [1, 9], 2, 2) == weights + 96 \
        + 2 * 2 * 30 * 2
    # seq 8 under a window of 4: 4 - 16 / 16 = 3 keys a query; seq 4: 2
    assert flops.train_flops_per_token(c, 8) == 6 * 170 + 12 * 3 * 8 + 120
    assert flops.train_flops_per_token(c, 4) == 6 * 170 + 12 * 2 * 8 + 120


# -- the dense formulas that the reference's counts replaced -----------------
# As this file's module held them before the counts came from the
# configuration's reference, frozen here: the reference must give the same
# numbers to the bit.

def _old_dims(c):
    d, h = c["hidden_size"], c["num_attention_heads"]
    return (d, h, c["num_key_value_heads"], c.get("head_dim") or d // h,
            c["intermediate_size"], c["vocab_size"], c["num_hidden_layers"])


def _old_layer(c):
    d, h, kv, hd, ff, _, _ = _old_dims(c)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff


def _old_matmul(c):
    d, _, _, _, _, v, n = _old_dims(c)
    return n * _old_layer(c) + d * v


def _old_train(c, seq):
    _, h, _, hd, _, _, n = _old_dims(c)
    return 6.0 * _old_matmul(c) + 6.0 * n * seq * h * hd


def _old_decode_flops(c, pos):
    _, h, _, hd, _, _, n = _old_dims(c)
    attn = sum(4.0 * (p + 1) * h * hd for p in pos) * n
    return 2.0 * _old_matmul(c) * len(pos) + attn


def _old_kv_bytes(c, pos, kb):
    _, _, kv, hd, _, _, n = _old_dims(c)
    return float(sum(2 * (p + 1) * kv * hd * kb for p in pos) * n)


def _old_decode_bytes(c, pos, wb, kb):
    d, _, _, _, _, _, n = _old_dims(c)
    weights = (_old_matmul(c) + (2 * n + 1) * d + len(pos) * d) * wb
    return float(weights) + _old_kv_bytes(c, pos, kb)


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("name", ["tiny", "yi-6b-l4", "stablelm-2-1.6b-l4"])
def test_dense_counts_equal_the_formulas_they_replaced(name, seed):
    """250 random sets of rows a seed: 3000 in all."""
    import random
    c = _tiny() if name == "tiny" else cfg(name)
    rng = random.Random(seed)
    assert flops.layer_matmul_params(c) == _old_layer(c)
    assert flops.matmul_params(c) == _old_matmul(c)
    for _ in range(250):
        pos = [rng.randrange(4096) for _ in range(rng.randint(1, 16))]
        seq = rng.randint(1, 4096)
        wb, kb = rng.choice([1, 2, 4]), rng.choice([1, 2, 4])
        got = (flops.train_flops_per_token(c, seq),
               flops.decode_flops(c, pos), flops.kv_bytes(c, pos, kb),
               flops.decode_bytes(c, pos, wb, kb))
        want = (_old_train(c, seq), _old_decode_flops(c, pos),
                _old_kv_bytes(c, pos, kb), _old_decode_bytes(c, pos, wb, kb))
        assert [x.hex() for x in got] == [x.hex() for x in want]

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

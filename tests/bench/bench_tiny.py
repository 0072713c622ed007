"""A benchmark root at a test size: a tiny dense decoder, training and
serving mixes, and cells whose limits suit that size. The harness code is
the repository's own ``bench`` package; only the data files are tiny."""
from __future__ import annotations

import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CONFIG = {"name": "tiny", "source": "test", "reference": "dense_decoder",
          "hidden_size": 64, "intermediate_size": 128,
          "num_attention_heads": 4, "num_key_value_heads": 2,
          "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 256,
          "rope_theta": 10000, "rms_norm_eps": 1e-5,
          "tie_word_embeddings": False,
          "precision": {"params": "float32", "compute": "bfloat16",
                        "kv_cache": "bfloat16"}}
TRAIN = {"kind": "train", "seq": 64, "b_local": 1, "tau": 2,
         "p_follow": 0.8}
CHAT = {"kind": "serve", "rate": 8.0,
        "prompt": {"median": 16, "sigma": 0.5, "min": 4, "max": 36},
        "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
        "temperature": 0.7, "greedy_share": 0.5}
TRAIN_CELL = {"workers": 4, "mesh": None, "spec": "einsum:f32",
              "optimizer": "sgd", "lr": 0.03, "beta": 0.9, "a_tilde": 1.0,
              "m_estimate": 100, "record_chunks": 4}
SERVE_CELL = {"slots": 4, "block_size": 4, "max_len": 48, "chunk": 4}
TRAIN_LIMITS = {"loss_gap": 0.01, "grad_gap": 0.02, "change_gap": 0.02}
SERVE_LIMITS = {"served_gap": 0.1}


def write(root: str) -> str:
    for sub in ("configs", "traffic", "cells", "limits"):
        os.makedirs(os.path.join(root, "bench", sub), exist_ok=True)
    files = {"configs/tiny.json": CONFIG, "traffic/tiny-train.json": TRAIN,
             "traffic/tiny-chat.json": CHAT,
             "cells/tiny-train.json": TRAIN_CELL,
             "cells/tiny-serve.json": SERVE_CELL,
             "limits/tiny-train.json": TRAIN_LIMITS,
             "limits/tiny-serve.json": SERVE_LIMITS}
    for name, obj in files.items():
        with open(os.path.join(root, "bench", name), "w") as fh:
            json.dump(obj, fh)
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    manifest["paths"] = ["bench"]
    manifest["configs"] = [{"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "test size"}]
    manifest["workloads"] = [
        {"name": "tiny-train", "config": "tiny", "traffic": "tiny-train",
         "chips": 1, "why": "test size"},
        {"name": "tiny-serve", "config": "tiny", "traffic": "tiny-chat",
         "chips": 1, "why": "test size"}]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m:
            train = any("train" in w for w in m["workloads"])
            m["workloads"] = ["tiny-train" if train else "tiny-serve"]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(manifest, fh)
    return root

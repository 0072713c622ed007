"""Shared body of the fault tests: run the child at a test size and read
what it found."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import bench_tiny

HERE = os.path.dirname(os.path.abspath(__file__))


def child(tmp_path, kind: str, seed: int = 2 ** 33 + 5) -> dict:
    root = bench_tiny.write(str(tmp_path))
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "bench_fault_child.py"), root,
         kind, str(seed)], capture_output=True, text=True, env=env,
        timeout=600)
    lines = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    assert proc.returncode == 0 and lines, proc.stderr[-4000:]
    return json.loads(lines[-1][len("RESULT "):])

"""The command prints no result and exits nonzero where there is no TPU,
and where the checkout holds nothing but the benchmark."""
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ARGS = ["--workload", "yi6b-serve-chat", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def run(root):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root,
                          capture_output=True, text=True, env=env,
                          timeout=300)


def no_result(proc):
    return not any(line.lstrip().startswith("{")
                   for line in proc.stdout.splitlines())


def test_no_tpu_no_result():
    proc = run(REPO)
    assert proc.returncode == 2 and no_result(proc)
    assert "not a TPU" in proc.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = run(tmp_path)
    assert proc.returncode != 0 and no_result(proc)

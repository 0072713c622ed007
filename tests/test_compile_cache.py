"""The persistent compilation cache is placed from outside: the helper
follows JAX_COMPILATION_CACHE_DIR when it is set and a fixed path in the
checkout otherwise, and importing the package touches neither. Each case
runs in a subprocess so the test process keeps its own cache settings."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SRC = os.path.join(ROOT, "src")

SCRIPT = textwrap.dedent("""
    import json, sys
    import jax
    import repro, repro.train, repro.serve, repro.core, repro.kernels
    seen = {"after_import": jax.config.jax_compilation_cache_dir}
    if sys.argv[1] == "call":
        from repro.launch.cache import enable_compile_cache
        seen["returned"] = enable_compile_cache()
        seen["configured"] = jax.config.jax_compilation_cache_dir
        seen["enabled"] = jax.config.jax_enable_compilation_cache
    print("RESULT", json.dumps(seen))
""")


def _run(mode, cache_env):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = SRC
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    out = subprocess.run([sys.executable, "-c", SCRIPT, mode], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT")][0]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("case", ["env_set", "env_unset", "import_only"])
def test_compile_cache_placement(case, tmp_path):
    if case == "env_set":
        where = str(tmp_path / "cache")
        seen = _run("call", where)
        assert seen["returned"] == seen["configured"] == where
        assert seen["enabled"] is True
    elif case == "env_unset":
        seen = _run("call", None)
        assert seen["after_import"] is None
        assert seen["returned"] == seen["configured"] \
            == os.path.join(ROOT, ".jax_cache")
    else:
        assert _run("import", None)["after_import"] is None

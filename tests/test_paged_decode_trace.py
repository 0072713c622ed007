"""benchmarks/paged_decode_trace.py: it refuses a host without a TPU, and its
trace reduction sums only the named programs on the TPU planes' module
line."""
import importlib.util
import os
import sys
from types import SimpleNamespace as NS

import jax

PATH = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                    "paged_decode_trace.py")


def _tool():
    spec = importlib.util.spec_from_file_location("paged_decode_trace", PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_a_tpu(tmp_path, monkeypatch, capsys):
    tool = _tool()
    monkeypatch.setattr(sys, "path", list(sys.path))
    monkeypatch.setattr(sys, "argv", ["paged_decode_trace.py",
                                      "--out", str(tmp_path)])
    assert tool.main() == 1
    assert capsys.readouterr().out == ""


def test_device_times_sums_named_modules_on_tpu_planes(monkeypatch):
    def ev(name, ns):
        return NS(name=name, duration_ns=ns)

    planes = [
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Modules", events=[
                ev("jit_paged_kernel_ctx160(11)", 5),
                ev("jit_paged_kernel_ctx160(11)", 7),
                ev("jit_paged_kernel_ctx1600(12)", 100),
                ev("jit_paged_ref_ctx160(13)", 3)]),
            NS(name="XLA Ops", events=[
                ev("jit_paged_kernel_ctx160(11)", 1000)])]),
        NS(name="/device:TPU:1", lines=[
            NS(name="XLA Modules", events=[
                ev("jit_paged_kernel_ctx160(11)", 2)])]),
        NS(name="/host:CPU", lines=[
            NS(name="XLA Modules", events=[
                ev("jit_paged_ref_ctx160(13)", 1000)])]),
    ]
    monkeypatch.setattr(jax.profiler, "ProfileData",
                        NS(from_file=lambda path: NS(planes=planes)))
    got = _tool().device_times("unused.xplane.pb",
                               ["paged_kernel_ctx160", "paged_ref_ctx160",
                                "paged_ref_ctx2048"])
    assert got == {"paged_kernel_ctx160": 14, "paged_ref_ctx160": 3,
                   "paged_ref_ctx2048": 0}

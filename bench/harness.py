"""What every cell shares: the manifest, the device check, the compile cache,
compile counting, host spans, the traced window and the result line.

Everything a cell needs is found by name from ``BENCHMARK.json``:

* ``bench/configs/<config>.json`` -- the model configuration as it is run
  (its file is named in the manifest);
* ``bench/traffic/<traffic>.json`` -- the mix; its ``kind`` names the
  driver, ``bench/drivers/<kind>.py``;
* ``bench/cells/<workload>.json`` -- the system's settings in this cell;
* ``bench/limits/<workload>.json`` -- the limits of its correctness numbers,
  set from readings on the chip; a cell without them is refused;
* ``bench/metrics/<metric>.py`` -- one reader per per-layer metric;
* ``bench/reference/<reference>.py`` -- the plain reference the
  configuration names.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(ROOT, ".bench_cache")


class NoChip(RuntimeError):
    """The machine does not hold what the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    settings: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    def driver(self):
        return importlib.import_module(
            f"bench.drivers.{self.traffic['kind']}")

    def reference(self):
        return importlib.import_module(
            f"bench.reference.{self.config['reference']}")


def metric_reader(name: str):
    """``bench/metrics/<name>.py``, loaded by path: metric names may hold
    dots."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    key = f"bench.metrics.{name}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        if spec is None:
            raise FileNotFoundError(path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[key] = mod
    return sys.modules[key]


def _json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(workload: str, root: str = ROOT) -> Cell:
    """The cell named ``workload``, with every file it needs read."""
    manifest = _json(os.path.join(root, "BENCHMARK.json"))
    bench = os.path.join(root, manifest["paths"][0])
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    limits = os.path.join(bench, "limits", f"{workload}.json")
    if not os.path.exists(limits):
        raise KeyError(f"workload {workload!r} has no limits file: its "
                       f"correctness limits are not set")
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=_json(os.path.join(root, cfg_entry["file"])),
        traffic=_json(os.path.join(bench, "traffic", f"{w['traffic']}.json")),
        settings={**_json(os.path.join(bench, "cells", f"{workload}.json")),
                  "limits": _json(limits)},
        end_to_end=[m for m in manifest["end_to_end"]
                    if _applies(m, workload)],
        per_layer=[m for m in manifest["per_layer"]
                   if _applies(m, workload)])


# -- device --------------------------------------------------------------------------

def require_chips(n: int):
    """The first ``n`` devices, which must be TPUs with published peaks."""
    import jax
    from bench import peaks
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"first device is {devs[0].platform!r}, not a TPU")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devs)}")
    try:
        peaks.peaks_for(devs[0].device_kind)
    except peaks.UnknownDevice as e:
        raise NoChip(str(e)) from None
    return devs[:n]


def device_block(devices) -> dict:
    import jax
    d0 = devices[0]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


# -- compilation ---------------------------------------------------------------------

def enable_cache(path: str) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout; every program is written, however quick to compile, and none
    is evicted: a size limit from the environment
    (``JAX_COMPILATION_CACHE_MAX_SIZE``) smaller than the cell's programs
    would make every run compile again."""
    import jax
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


class CompileCounter:
    """Counts programs that JAX compiled or loaded from the persistent
    cache, and how many of them came from the cache; ``names`` counts the
    programs compiled (not loaded) by name, from the compiler's note that
    it writes one to the cache."""

    def __init__(self):
        import collections
        import logging
        from jax._src import monitoring
        self.programs = 0
        self.cache_hits = 0
        self.names = collections.Counter()
        names = self.names

        class _Names(logging.Handler):
            """Reads the compiler's debug notes and passes on only what
            its logger would have passed without them."""

            def emit(self, record):
                if str(record.msg).startswith("'%s' took at least"):
                    names[str(record.args[0])] += 1
                if record.levelno >= level:
                    logging.getLogger("jax").handle(record)

        compiler_log = logging.getLogger("jax._src.compiler")
        level = compiler_log.getEffectiveLevel()
        compiler_log.setLevel(logging.DEBUG)
        compiler_log.propagate = False
        compiler_log.addHandler(_Names(logging.DEBUG))

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.programs += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def snapshot(self) -> Dict[str, int]:
        return {"programs": self.programs, "cache_hits": self.cache_hits,
                "compiled": self.programs - self.cache_hits}


# -- spans and the traced window ---------------------------------------------------------

def span(name: str):
    """A host span the trace reduction labels idle gaps with."""
    import jax
    return jax.profiler.TraceAnnotation(f"bench.{name}")


class Window:
    """The measured window: host-clock start and end, and, when traced, the
    profiler trace of it reduced by ``bench/trace.py``."""

    def __init__(self, workload: str, traced: bool):
        self.traced = traced
        self.dir = os.path.join(CACHE, "trace", workload)
        self.outline = os.path.join(CACHE, "trace", f"{workload}-outline.json")
        self.trace = None
        self.t0 = self.t1 = None
        self._stack = contextlib.ExitStack()

    def __enter__(self):
        import jax
        if self.traced:
            shutil.rmtree(self.dir, ignore_errors=True)
            self._stack.enter_context(jax.profiler.trace(self.dir))
        self._stack.enter_context(span("window"))
        self.t0 = time.perf_counter()
        return self

    def close(self) -> float:
        """End the window (after the caller's fence); returns its seconds."""
        self.t1 = time.perf_counter()
        self._stack.close()
        if self.traced:
            from bench import trace as tr
            path = sorted(glob.glob(os.path.join(
                self.dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
            self.trace = tr.load(path)
            with open(self.outline, "w") as fh:
                json.dump(tr.outline(self.trace), fh)
            shutil.rmtree(self.dir, ignore_errors=True)
        return self.t1 - self.t0

    def __exit__(self, *exc):
        if self.t1 is None:
            self._stack.close()
        return False

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


# -- per-layer metrics ------------------------------------------------------------------

@dataclasses.dataclass
class LayerInputs:
    """What a per-layer reader may read: the reduced trace of the window
    (``bench/trace.py``; the program's spans and their args are in
    ``trace.program_spans``), the chip's peaks, the configuration, and the
    cell's own counts of the work it issued in the window."""
    trace: object
    peaks: dict
    chips: int
    config: dict
    counts: dict


def per_layer(cell: Cell, inputs: LayerInputs) -> Dict[str, dict]:
    """Each reader's value; a reader that finds nothing returns None and
    its metric is left out."""
    out = {}
    for m in cell.per_layer:
        value = metric_reader(m["name"]).read(inputs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def breakdown(trace) -> dict:
    from bench import trace as tr
    return {"device_ops": tr.top_ops(trace), "idle_gaps":
            tr.idle_by_label(trace)}


# -- correctness --------------------------------------------------------------------

NOT_A_NUMBER = 1e300    # what a gap that is not finite prints as


def checks(numbers: Dict[str, float], limits: Dict[str, float]) -> dict:
    """Each compared number beside its limit; a number that is not finite
    is printed as ``NOT_A_NUMBER`` (strict JSON has no infinity)."""
    return {k: {"value": float(v) if math.isfinite(v) else NOT_A_NUMBER,
                "limit": float(limits[k])} for k, v in numbers.items()}


def all_within(chk: dict) -> bool:
    return bool(chk) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in chk.values())


# -- the result line -----------------------------------------------------------------------

def emit(result: dict, chk: dict) -> None:
    """The compared numbers as the last lines of standard error, then the
    result as the last line of standard output, the checks last in it."""
    for k, c in chk.items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps({**result, "checks": chk}), flush=True)


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file: read by the
    ``from_config`` of the module the file names under ``reader``, else by
    ``bench/published.py``. A file that names no reader keeps this one,
    whatever modules are added later."""
    if "reader" in cfg:
        return importlib.import_module(cfg["reader"]).from_config(cfg)
    from bench.published import from_config
    return from_config(cfg)


def same_layout(a: dict, b: dict, path=()) -> Optional[str]:
    """None when two trees of ShapeDtypeStructs agree, else where not."""
    if isinstance(a, dict) != isinstance(b, dict):
        return "/".join(path)
    if not isinstance(a, dict):
        return None if (tuple(a.shape) == tuple(b.shape)) else \
            f"{'/'.join(path)}: {a.shape} vs {b.shape}"
    if sorted(a) != sorted(b):
        return f"{'/'.join(path)}: {sorted(a)} vs {sorted(b)}"
    for k in a:
        bad = same_layout(a[k], b[k], path + (k,))
        if bad:
            return bad
    return None


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)

